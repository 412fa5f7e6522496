"""Pallas fused simplex-pivot kernel: up to K full pivot iterations for a
whole ``[B, R, C]`` tableau stack in a single launch.

Per grid step (one batch element, tableau block-resident in VMEM) the kernel
fuses what the vmapped jnp path runs as separate HBM-roundtripping ops:

  1. *Dantzig pricing* over the objective row (with the Bland fallback after
     ``bland_after`` iterations — same anti-cycling rule as
     ``repro.engine.batched_simplex``);
  2. the *ratio test* over the entering column — the Harris rule the
     engine uses too, :func:`repro.pivoting.harris_row`;
  3. the fused update: every other row subtracts ``pcol * prow`` and the
     pivot row becomes ``prow = T[row] / piv``, in one pass over the
     tableau (the engine's ``_fused_pivot``).

``k_pivots`` chains K of these pricing→ratio→update rounds per launch with
the convergence check *in-kernel* (a ``fori_loop`` whose body re-evaluates
the active mask each round — the guide-recommended static-bound-plus-mask
shape): a lane that reaches optimal/unbounded mid-launch passes its
tableau/basis/counters through the remaining rounds untouched, while the
launch overhead (grid dispatch + HBM<->VMEM block moves) amortizes over K
pivots instead of one.  K is a static compile-time parameter; the epoch
driver in ``repro.engine.batched_simplex`` picks it per tableau shape via
the autotune sweep (``repro.engine.autotune``).

Finished batch elements (status != running, or out of iteration budget) are
masked *in-kernel*: their ``pcol`` is zeroed wholesale, so the rank-1 update
is the identity and their tableau/basis/counters pass through unchanged —
which is also why K fused pivots are bit-identical to K single-pivot
launches (parity-tested in tests/test_hotpath.py).

Column/row gathers use one-hot contractions (``T @ e_col``, ``e_row @ T``)
instead of dynamic gathers — MXU-friendly on TPU, and bit-exact (the one-hot
sums add exact zeros), which is what keeps the Pallas backend's pivots
bit-identical to the vmapped reference.

The pure-jnp oracle lives in :func:`repro.kernels.ref.simplex_pivot_ref`;
``interpret=True`` (the default off-TPU, see ``ops._interp``) runs this same
kernel body on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.pivoting import harris_row

__all__ = ["simplex_pivot_kernel", "simplex_pivot_call"]

_EPS = 1e-9
_RUNNING = -1
_OPTIMAL = 0
_UNBOUNDED = 2


def _one_pivot(T, basis, it, status, *, ncols_price: int, bland_after: int,
               max_iter: int):
    """One masked pricing→ratio→update round (the historical kernel body)."""
    R, C = T.shape
    m_rows = R - 1
    active = (status == _RUNNING) & (it < max_iter)

    # ---- pricing: Dantzig, Bland after the anti-cycling threshold ----
    obj = T[-1, :ncols_price]
    neg = obj < -_EPS
    any_neg = jnp.any(neg)
    cidx = jax.lax.broadcasted_iota(jnp.int32, (ncols_price, 1), 0)[:, 0]
    dantzig = jnp.argmin(obj)
    bland = jnp.argmin(jnp.where(neg, cidx, ncols_price))
    col = jnp.where(it < bland_after, dantzig, bland).astype(jnp.int32)

    # ---- entering column via one-hot contraction (exact, no gather) ----
    e_col = (jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)[:, 0] == col)
    pcol_full = T @ e_col.astype(T.dtype)  # [R]
    colvals = pcol_full[:m_rows]

    # ---- ratio test: the Harris rule ----
    row, unbounded = harris_row(colvals, T[:m_rows, -1], basis,
                                it >= bland_after)
    row = row.astype(jnp.int32)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (m_rows, 1), 0)[:, 0]
    e_row = (ridx == row).astype(T.dtype)

    do_pivot = active & any_neg & ~unbounded

    # ---- fused masked rank-1 update (the engine's _fused_pivot) ----
    piv = jnp.where(do_pivot, e_row @ colvals, 1.0)
    prow = (e_row @ T[:m_rows]) / piv  # [C] — the new pivot row
    full_ridx = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)[:, 0]
    is_row = full_ridx == row
    # finished elements: pcol zeroed wholesale and the row left as it was
    pcol = jnp.where(do_pivot & ~is_row, pcol_full, 0.0)
    T = jnp.where((do_pivot & is_row)[:, None], prow[None, :],
                  T - pcol[:, None] * prow[None, :])
    is_col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)[:, 0] == col
    T = jnp.where(do_pivot & is_col[None, :],
                  is_row[:, None].astype(T.dtype), T)

    basis = jnp.where(do_pivot & (ridx == row), col.astype(basis.dtype), basis)
    new_status = jnp.where(
        ~any_neg,
        jnp.int32(_OPTIMAL),
        jnp.where(unbounded, jnp.int32(_UNBOUNDED), jnp.int32(_RUNNING)),
    )
    status = jnp.where(active, new_status, status)
    it = it + jnp.where(do_pivot, jnp.int32(1), jnp.int32(0))
    return T, basis, it, status


def simplex_pivot_kernel(
    T_ref, basis_ref, it_ref, status_ref,
    To_ref, basiso_ref, ito_ref, statuso_ref,
    *, ncols_price: int, bland_after: int, max_iter: int, k_pivots: int = 1,
):
    round_ = functools.partial(
        _one_pivot,
        ncols_price=ncols_price, bland_after=bland_after, max_iter=max_iter,
    )
    carry = (T_ref[0], basis_ref[0], it_ref[0], status_ref[0])
    if k_pivots == 1:
        carry = round_(*carry)
    else:
        # K fused rounds; the active mask inside round_ is the in-kernel
        # convergence check (converged lanes ride through as identity)
        carry = jax.lax.fori_loop(
            0, k_pivots, lambda _, c: round_(*c), carry
        )
    To_ref[0], basiso_ref[0], ito_ref[0], statuso_ref[0] = carry


def simplex_pivot_call(
    T, basis, it, status, *,
    ncols_price: int, bland_after: int, max_iter: int, k_pivots: int = 1,
    interpret: bool = False,
):
    """Up to ``k_pivots`` masked pivot steps for the stack: T [B,R,C], basis
    [B,R-1], it/status [B] int32 -> the same pytree, advanced by <= k_pivots
    pivots each (bit-identical to k_pivots single-pivot calls)."""
    B, R, C = T.shape
    kernel = functools.partial(
        simplex_pivot_kernel,
        ncols_price=ncols_price, bland_after=bland_after, max_iter=max_iter,
        k_pivots=k_pivots,
    )
    spec_T = pl.BlockSpec((1, R, C), lambda b: (b, 0, 0))
    spec_basis = pl.BlockSpec((1, R - 1), lambda b: (b, 0))
    spec_scalar = pl.BlockSpec((1,), lambda b: (b,))
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[spec_T, spec_basis, spec_scalar, spec_scalar],
        out_specs=[spec_T, spec_basis, spec_scalar, spec_scalar],
        out_shape=[
            jax.ShapeDtypeStruct(T.shape, T.dtype),
            jax.ShapeDtypeStruct(basis.shape, basis.dtype),
            jax.ShapeDtypeStruct(it.shape, it.dtype),
            jax.ShapeDtypeStruct(status.shape, status.dtype),
        ],
        interpret=interpret,
    )(T, basis, it, status)
