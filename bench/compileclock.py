"""JAX's own compile events, counted from any thread."""

from __future__ import annotations

import threading
import time

__all__ = ["CompileClock"]


class CompileClock:
    """Every tracing, lowering and compiling event JAX reports, with the
    host time it ended at (``time.perf_counter``) and its duration."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.events: list = []  # (event, end time, seconds)
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.events.append((event, time.perf_counter(), duration))

    def between(self, t0: float, t1: float) -> list:
        with self._lock:
            return [e for e in self.events if t0 <= e[1] <= t1]

    @property
    def seconds(self) -> float:
        with self._lock:
            return sum(e[2] for e in self.events)
