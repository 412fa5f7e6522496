"""Pivot rules shared by the batched simplex and its Pallas pivot kernel.

Both ``repro.engine.batched_simplex`` and ``repro.kernels.simplex_pivot``
import the ratio test from here, so the two pivot paths take the same row
on the same tableau; the kernels' reference oracle
(``repro.kernels.ref.simplex_pivot_ref``) carries its own element-by-element
version of the rule.
"""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["harris_row"]

_EPS = 1e-9


def harris_row(colvals, rhs, basis, bland):
    """Harris two-pass ratio test over the entering column; (row, unbounded).

    A pivot must exceed ``_EPS`` times the column's largest magnitude (the
    absolute ``_EPS`` alone where nothing does), so no near-zero pivot
    amplifies the tableau's rounding.  Pass one takes the longest step that
    keeps every basic variable above ``-_EPS``; pass two picks, among the
    rows whose ratio fits in that step, the largest pivot — or, once
    ``bland`` holds, the smallest basis index (the anti-cycling rule).
    On paper-size schedule LPs (Table-2 chains, ~2,200 rows, ~3,500
    pivots) the exact min-ratio rule cycled to the iteration cap or lost
    feasibility on some lanes, even in IEEE float64.
    """
    big = jnp.max(jnp.abs(colvals), initial=0.0)
    pos = colvals > _EPS * jnp.maximum(1.0, big)
    pos = jnp.where(jnp.any(pos), pos, colvals > _EPS)
    safe = jnp.where(pos, colvals, 1.0)
    step = jnp.min(jnp.where(pos, (jnp.maximum(rhs, 0.0) + _EPS) / safe, jnp.inf),
                   initial=jnp.inf)
    fits = pos & (rhs / safe <= step)
    row = jnp.where(
        bland,
        jnp.argmin(jnp.where(fits, basis, jnp.iinfo(jnp.int32).max)),
        jnp.argmax(jnp.where(fits, colvals, -jnp.inf)),
    )
    return row, ~jnp.isfinite(step)
