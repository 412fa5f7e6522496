"""Per-shape pivot-schedule autotuner for the Pallas simplex driver.

The compaction-epoch driver in ``batched_simplex`` launches the fused
K-pivot kernel (``repro.kernels.simplex_pivot``) in bounded bursts between
host-side compaction passes.  Two knobs matter per tableau shape:

* ``k_pivots`` — how many pricing→ratio→update rounds fuse into one kernel
  launch.  Larger K amortizes launch + HBM<->VMEM block-move overhead but
  wastes work once lanes converge mid-launch (they ride through masked).
* ``n_launches`` — launches per epoch before the host re-compacts the
  still-active lanes into a dense prefix.  Derived so each epoch covers
  roughly ``_EPOCH_PIVOTS`` pivots regardless of K.

``pivot_schedule(n_rows, n_cols)`` runs a small timed sweep over candidate
K values on a synthetic probe stack of the same tableau shape and memoizes
the winner **in-process** — the cache is a plain dict keyed by
``(n_rows, n_cols, interpret)``, never persisted to disk, so repeated
bucket solves of the same shape pay the sweep exactly once per process
(the format is documented in DESIGN.md §9).  Results are timing decisions
only: every K is bit-identical by construction (the kernel's per-round
active mask), so a "wrong" tune costs time, never correctness.
"""

from __future__ import annotations

import time

import numpy as np

__all__ = ["pivot_schedule", "clear_cache", "cache_snapshot"]

_EPOCH_PIVOTS = 32  # target pivots per epoch between compaction passes
_SWEEP = (1, 2, 4)  # candidate k_pivots values for the timed probe
_PROBE_B = 8  # probe stack batch size
_PROBE_LAUNCHES = 2  # timed launches per candidate (after one warmup)

# (n_rows, n_cols, interpret) -> {"k_pivots", "n_launches", "probe_s_per_pivot"}
_CACHE: dict[tuple[int, int, bool], dict] = {}


def clear_cache() -> None:
    """Drop all memoized schedules (tests / benchmarks)."""
    _CACHE.clear()


def cache_snapshot() -> dict:
    """A copy of the memo for telemetry/bench reporting."""
    return {k: dict(v) for k, v in _CACHE.items()}


def _probe_stack(n_rows: int, n_cols: int):
    """A synthetic [_PROBE_B, R, C] tableau stack that keeps pivoting: random
    positive body, negative objective row, so Dantzig always finds work."""
    rng = np.random.default_rng(n_rows * 1_000_003 + n_cols)
    T = rng.uniform(0.1, 1.0, size=(_PROBE_B, n_rows, n_cols))
    T[:, -1, :] = -rng.uniform(0.1, 1.0, size=(_PROBE_B, n_cols))
    T[:, :, -1] = rng.uniform(0.5, 1.5, size=(_PROBE_B, n_rows))
    basis = np.tile(
        np.arange(n_rows - 1, dtype=np.int32)[None, :], (_PROBE_B, 1)
    )
    it = np.zeros(_PROBE_B, np.int32)
    status = np.full(_PROBE_B, -1, np.int32)  # _RUNNING
    return T, basis, it, status


def pivot_schedule(
    n_rows: int, n_cols: int, interpret: bool | None = None,
    sweep: tuple[int, ...] = _SWEEP,
) -> dict:
    """Pick (k_pivots, n_launches) for tableaux of shape [R=n_rows, C=n_cols].

    Returns the memoized ``{"k_pivots", "n_launches", "probe_s_per_pivot"}``
    entry; the first call per shape runs the timed sweep (a handful of tiny
    kernel launches), subsequent calls are a dict hit.
    """
    from repro.jaxenv import x64
    from repro.kernels.ops import _interp, simplex_pivot

    interp = bool(_interp(interpret))
    key = (int(n_rows), int(n_cols), interp)
    hit = _CACHE.get(key)
    if hit is not None:
        return hit

    T, basis, it, status = _probe_stack(n_rows, n_cols)
    max_iter = _EPOCH_PIVOTS * 4  # plenty of headroom for the probe
    per_pivot: dict[int, float] = {}
    with x64():
        for k in sweep:
            kw = dict(
                ncols_price=n_cols - 1, bland_after=max_iter,
                max_iter=max_iter, k_pivots=int(k), interpret=interp,
            )
            out = simplex_pivot(T, basis, it, status, **kw)  # compile warmup
            out[0].block_until_ready()
            t0 = time.perf_counter()
            for _ in range(_PROBE_LAUNCHES):
                out = simplex_pivot(T, basis, it, status, **kw)
            out[0].block_until_ready()
            per_pivot[int(k)] = (time.perf_counter() - t0) / (
                _PROBE_LAUNCHES * k
            )
    best = min(per_pivot, key=per_pivot.get)
    entry = {
        "k_pivots": best,
        "n_launches": max(1, _EPOCH_PIVOTS // best),
        "probe_s_per_pivot": per_pivot,
    }
    _CACHE[key] = entry
    return entry
