"""What the engine's dense simplex must move through HBM, counted from shapes.

The engine solves a bucket of ``B`` schedule LPs as a ``[B, R, C]`` float64
tableau (``R`` = rows + 1 objective row, ``C`` = variables + slacks + one
dummy column + the right-hand side), one vmapped ``while_loop`` per phase.
Every trip of a phase's loop rewrites every lane's tableau once (finished
lanes are masked, not skipped), so the least traffic of a trip is one read
and one write of ``B * R * C * 8`` bytes.  A phase runs as many trips as its
slowest lane needs: its pivots plus the one trip that finds it optimal, or
the iteration cap.  This counts dense-tableau work; a solver that stops
holding the dense tableau needs a new count.
"""

from __future__ import annotations

__all__ = ["lp_shape", "tableau", "trips", "least_bytes"]

ITER_CAP = 20_000  # the engine's iteration cap per phase


def lp_shape(topology: str, m: int, n_loads: int, returns: bool) -> tuple:
    """(variables, inequality rows, equality rows) of the schedule LP as the
    engine builds it: one installment per load, zero release and
    availability dates (so their rows are dropped), the makespan held above
    the last cell's computations and result messages."""
    T = n_loads
    n = (m - 1) * T + 2 * m * T + ((m - 1) * T if returns else 0) + 1
    if topology == "star":
        fwd = (m - 2) * T + (T - 1)
        back = (m - 1) * T + (m - 2) * T + (T - 1) if returns else 0
    else:
        fwd = (m - 2) * T + (m - 1) * (T - 1) + (m - 2) * (T - 1)
        back = ((m - 1) * T + (m - 2) * T + (m - 1) * (T - 1)
                if returns else 0)
    rows = (fwd + (m - 1) * T + m * (T - 1) + back + m
            + (m - 1 if returns else 0))
    return n, rows, T


def tableau(cfg: dict) -> tuple:
    """(R, C) of one lane's tableau for a configuration file."""
    n, ub, eq = lp_shape(cfg["topology"], cfg["m"], cfg["n_loads"],
                         cfg["return_ratio"] > 0 and cfg["m"] > 1)
    return ub + eq + 1, n + ub + 2


def trips(pivots: list) -> int:
    """Loop trips of one phase of a bucket, from its lanes' pivot counts."""
    return max(min(p + 1, ITER_CAP) for p in pivots)


def least_bytes(cfg: dict, buckets: list) -> float:
    """HBM bytes the buckets' simplex loops must move at the least.

    ``buckets`` holds, per bucket, its lanes' ``(phase-1, phase-2)`` pivots."""
    R, C = tableau(cfg)
    total = 0.0
    for lanes in buckets:
        n_trips = trips([p1 for p1, _ in lanes]) + trips([p2 for _, p2 in lanes])
        total += n_trips * 2 * len(lanes) * R * C * 8
    return total
