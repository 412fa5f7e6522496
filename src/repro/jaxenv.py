"""The two pieces of JAX process state the engine depends on.

* :func:`x64` — the float64 scope every engine and kernel entry runs under.
  JAX's config contexts are thread-local, so each thread that calls into
  the engine (``PlanServer`` workers, shard threads) enters it itself; a
  caller never has to.
* :func:`use_compile_cache` — the persistent XLA compilation cache.  When
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
  is changed; otherwise the cache lives at a fixed ``.jax_cache/`` in the
  checkout, because the cache path is part of what makes an entry a hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["x64", "use_compile_cache"]

_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def x64():
    """Context manager: float64 arrays for everything traced inside it."""
    return jax.enable_x64(True)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
