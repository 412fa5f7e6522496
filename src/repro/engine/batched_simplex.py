"""Batched dense two-phase simplex under ``vmap`` — many small LPs at once.

Solves, for each batch element:   min c.x   s.t.  A_ub x <= b_ub,
A_eq x = b_eq,  x >= 0 — the same problem class as ``repro.core.simplex``,
against which it is cross-checked (tests/test_engine_parity.py).

Fixed-shape reformulation (everything static so ``vmap``/``jit`` apply):

  * rows with negative rhs are flipped row-wise (A *= -1, slack coefficient
    becomes -1), exactly like the NumPy solver;
  * artificial variables are **implicit**: they start basic on eq/flipped
    rows and are never allowed to re-enter once driven out, so their tableau
    columns are never read — the tableau holds only structural + slack
    columns, one inert zero *dummy* column, and the rhs.  Basis ids
    ``> dummy`` denote a still-basic artificial; after phase 1 any zero-level
    survivor is driven out where possible and the rest are remapped onto the
    dummy column (it prices at 0, so it never re-enters).  This keeps the
    tableau ~1/3 the width of the explicit form — the pivot's rank-1 update
    is the memory-bound inner loop, so width is throughput;
  * each pivot is a *single* elementwise pass: every other row subtracts
    ``pcol * prow`` and the pivot row becomes ``prow = T[row]/piv``
    (:func:`_fused_pivot`); ``pcol`` is zeroed wholesale to mask finished
    batch elements;
  * each phase is a ``lax.while_loop`` whose carry holds (tableau, basis,
    iteration, status); JAX's batching rule for ``while_loop`` masks finished
    batch elements automatically;
  * pricing is Dantzig with a Bland fallback after ``max(200, 4 rows)``
    iterations (anti-cycling); the ratio test is Harris's two-pass rule
    with a relative pivot threshold (:func:`harris_row`), which keeps
    paper-size LPs (thousands of rows and pivots) from cycling or drifting
    off the polytope.

Statuses are small ints (see STATUS) so they vectorize.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.jaxenv import x64
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span

__all__ = ["BatchedSimplexResult", "solve_simplex_batched", "STATUS"]

_EPS = 1e-9
STATUS = {
    0: "optimal",
    1: "infeasible",
    2: "unbounded",
    3: "iteration_limit",
    4: "degenerate",  # zero-level artificial left basic after phase 1; the
    # batched path skips the NumPy solver's drive-out pivots (they cost ~m
    # full-tableau passes for a case that essentially never occurs on
    # schedule LPs), so such elements are flagged for the serial fallback
    # instead of being silently mis-solved
    5: "false_optimal",  # an "optimal" exit whose iterate violates a primal
    # constraint beyond the feasibility tolerance — the same silently-lost-
    # pivot escape core.backends._primal_violation guards on the serial
    # path.  Demoted here so the service's certification routes the element
    # to the serial rescue instead of shipping an infeasible plan whose
    # objective reads better than the true optimum.
}

_RUNNING, _OPTIMAL, _UNBOUNDED, _ITER_LIMIT = -1, 0, 2, 3


@dataclasses.dataclass
class BatchedSimplexResult:
    x: np.ndarray  # [B, n]
    objective: np.ndarray  # [B]
    status: np.ndarray  # [B] int — see STATUS
    iterations: np.ndarray  # [B] int (phase 1 + phase 2 pivots)
    iterations_phase1: np.ndarray | None = None  # [B] int — solver telemetry
    iterations_phase2: np.ndarray | None = None  # [B] int
    # the exit basis [B, m_rows]: the column id basic in each row at the
    # final tableau (structural < n, slack in [n, dummy), dummy for retired
    # artificials/redundant rows).  A later solve of a *perturbed* instance
    # with the same shape can seed ``warm_basis`` with it and skip phase 1
    # entirely while it stays primal-feasible.  None when m_rows == 0.
    basis: np.ndarray | None = None
    # [B] bool — True where the warm (basis-seeded, phase-2-only) entry
    # actually served the element; False on cold two-phase solves
    warm_started: np.ndarray | None = None

    @property
    def ok(self) -> np.ndarray:
        return self.status == 0

    def status_str(self, b: int) -> str:
        return STATUS[int(self.status[b])]


def _equilibrate(A, b, c, iters=3):
    """Ruiz scaling toward unit max-magnitudes (same as core.simplex); the
    iteration count is static so this unrolls into a few fused passes."""
    col = jnp.ones(A.shape[1])
    for _ in range(iters):
        rmax = jnp.max(jnp.abs(A), axis=1, initial=0.0)
        r = 1.0 / jnp.sqrt(jnp.where(rmax > 0, rmax, 1.0))
        A = A * r[:, None]
        b = b * r
        cmax = jnp.max(jnp.abs(A), axis=0, initial=0.0)
        s = 1.0 / jnp.sqrt(jnp.where(cmax > 0, cmax, 1.0))
        A = A * s[None, :]
        col = col * s
    return A, b, c * col, col


def _fused_pivot(T, row, col, do_pivot):
    """One-pass masked pivot: returns T after pivoting on (row, col).

    ``prow = T[row]/piv`` becomes the pivot row and every other row
    subtracts ``T[i, col] * prow``, in one elementwise pass.  The pivot row
    is written as ``prow`` itself, not folded into the rank-1 update as
    ``T[row] - (piv-1) * prow``: that form cancels when |piv| is large, and
    the TPU's emulated float64 loses up to 4.6e-8 relative on it against
    ~1e-14 for the division alone (measured on a v5e), which over thousands
    of pivots drifts the tableau off the polytope.  For the same reason the
    entering column is written as the exact unit vector of the pivot row.
    """
    piv = jnp.where(do_pivot, T[row, col], 1.0)
    prow = T[row] / piv
    is_row = jnp.arange(T.shape[0]) == row
    pcol = jnp.where(do_pivot & ~is_row, T[:, col], 0.0)
    T = jnp.where((do_pivot & is_row)[:, None], prow[None, :],
                  T - jnp.outer(pcol, prow))
    is_col = jnp.arange(T.shape[1]) == col
    return jnp.where(do_pivot & is_col[None, :],
                     is_row[:, None].astype(T.dtype), T)


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


def _phase(T, basis, ncols_price, max_iter, bland_after):
    """Run simplex pivots on tableau T until optimal/unbounded/limit."""

    def cond(carry):
        _, _, it, status = carry
        return (status == _RUNNING) & (it < max_iter)

    def body(carry):
        T, basis, it, status = carry
        obj = T[-1, :ncols_price]
        neg = obj < -_EPS
        any_neg = jnp.any(neg)
        dantzig = jnp.argmin(obj)
        bland = jnp.argmin(jnp.where(neg, jnp.arange(ncols_price), ncols_price))
        col = jnp.where(it < bland_after, dantzig, bland)

        row, unbounded = harris_row(T[:-1, col], T[:-1, -1], basis,
                                    it >= bland_after)

        do_pivot = any_neg & ~unbounded
        T = _fused_pivot(T, row, col, do_pivot)
        basis = jnp.where(do_pivot, basis.at[row].set(col), basis)

        status = jnp.where(
            ~any_neg,
            jnp.int32(_OPTIMAL),
            jnp.where(unbounded, jnp.int32(_UNBOUNDED), jnp.int32(_RUNNING)),
        )
        it = it + jnp.where(do_pivot, jnp.int32(1), jnp.int32(0))
        return T, basis, it, status

    T, basis, it, status = lax.while_loop(
        cond, body, (T, basis, jnp.int32(0), jnp.int32(_RUNNING))
    )
    status = jnp.where(status == _RUNNING, jnp.int32(_ITER_LIMIT), status)
    return T, basis, it, status


def _standard_rows(c, A_ub, b_ub, A_eq, b_eq):
    """Equilibrate + sign-flip one LP into its standard-form row block.

    Returns (M, can_slack, c_scaled, col_scale): M is the [m_rows, dummy+2]
    block with columns [structural | slack | dummy | rhs] (the first m_rows
    rows of the tableau, objective row excluded); ``can_slack`` marks the
    rows whose +1 slack can start basic.  Shared by the cold setup and the
    warm (basis-seeded) entry so both see bit-identical coefficients — the
    invariant that makes a carried basis meaningful across a perturbation.
    """
    n = c.shape[0]
    m_ub, m_eq = A_ub.shape[0], A_eq.shape[0]
    m_rows = m_ub + m_eq

    A = jnp.concatenate([A_ub, A_eq], axis=0) if m_rows else jnp.zeros((0, n))
    b = jnp.concatenate([b_ub, b_eq])
    A, b, c, col_scale = _equilibrate(A, b, c)
    neg = b < 0
    A = jnp.where(neg[:, None], -A, A)
    b = jnp.abs(b)
    # slack for <= rows: +1, flipped to -1 when the row was negated; eq rows: 0
    slack_sign = jnp.concatenate([jnp.ones(m_ub), jnp.zeros(m_eq)])
    slack_sign = jnp.where(neg, -slack_sign, slack_sign)

    dummy = n + m_ub  # the inert zero column artificials retire onto
    # columns: [structural | slack | dummy | rhs]
    M = jnp.zeros((m_rows, dummy + 2))
    M = M.at[:, :n].set(A)
    M = M.at[:, -1].set(b)
    rows = jnp.arange(m_rows)
    M = M.at[rows[:m_ub], n + rows[:m_ub]].set(slack_sign[:m_ub])
    can_slack = jnp.concatenate([~neg[:m_ub], jnp.zeros(m_eq, dtype=bool)])
    return M, can_slack, c, col_scale


def _setup_one(c, A_ub, b_ub, A_eq, b_eq):
    """Equilibrate + build the phase-1 tableau/basis for one LP.

    Returns (T, basis, c_scaled, col_scale); T's objective row already holds
    the phase-1 objective (sum of implicit artificials, priced out).
    """
    n = c.shape[0]
    m_ub = A_ub.shape[0]
    m_rows = m_ub + A_eq.shape[0]
    dummy = n + m_ub

    M, can_slack, c, col_scale = _standard_rows(c, A_ub, b_ub, A_eq, b_eq)
    T = jnp.zeros((m_rows + 1, dummy + 2))
    T = T.at[:m_rows].set(M)
    rows = jnp.arange(m_rows)
    # initial basis: the +1 slack where the row kept one, else an (implicit)
    # artificial — ids `dummy + 1 + r`, one per row, ordered like the rows so
    # the ratio test's basis-index tie-break matches the NumPy solver
    basis = jnp.where(can_slack, n + rows, dummy + 1 + rows)

    # ---- phase 1 objective: minimize the sum of (implicit) artificials ----
    # pricing out the basic artificials leaves obj = -sum of their rows; the
    # artificial columns themselves are never read again (no re-entry rule)
    art_basic = ~can_slack
    T = T.at[-1].set(-jnp.sum(jnp.where(art_basic[:, None], T[:m_rows], 0.0), axis=0))
    return T, basis, c, col_scale


def _between_phases(T, basis, st1, c_scaled, *, n, dummy):
    """Phase-1 epilogue + phase-2 objective install for one tableau.

    Zero-level artificials left basic after phase 1: the NumPy solver
    drives them out with up to m_rows extra pivots.  Rows whose structural
    and slack entries are all zero are redundant constraints — inert under
    further pivots — and retire safely onto the dummy column.  A *drivable*
    leftover (nonzero entries) is a degenerate corner that could go unsound
    if a later pivot pushed its implicit artificial positive, so those
    elements are flagged (status 4) and handed to the serial fallback
    rather than paying the drive-out passes batch-wide.
    """
    m_rows = T.shape[0] - 1
    infeasible = (st1 == _OPTIMAL) & (T[-1, -1] < -1e-7)
    is_art = basis > dummy
    zero_level = jnp.abs(T[:m_rows, -1]) <= 1e-9
    has_entries = jnp.any(jnp.abs(T[:m_rows, :dummy]) > 1e-9, axis=1)
    drivable_leftover = jnp.any(is_art & zero_level & has_entries)
    basis = jnp.where(is_art, dummy, basis)

    # ---- phase 2: the user objective on the same tableau ----
    T = T.at[-1].set(0.0)
    T = T.at[-1, :n].set(c_scaled)
    # price out basic variables: obj -= sum_r obj[basis[r]] * T[r]
    coeff = T[-1][basis]  # [m_rows]  (0 for dummy-basic rows)
    T = T.at[-1].add(-coeff @ T[:m_rows])
    return T, basis, infeasible, drivable_leftover


def _extract_one(T, basis, col_scale, c_orig, infeasible, drivable_leftover,
                 st1, st2, it1, it2, *, n, dummy):
    m_rows = T.shape[0] - 1
    xfull = jnp.zeros(dummy + 1).at[basis].set(T[:m_rows, -1])
    x = col_scale * xfull[:n]  # undo column scaling
    obj = c_orig @ x
    status = jnp.where(
        infeasible,
        jnp.int32(1),
        jnp.where(st1 != _OPTIMAL, st1.astype(jnp.int32), st2.astype(jnp.int32)),
    )
    status = jnp.where((status == _OPTIMAL) & drivable_leftover, jnp.int32(4), status)
    bad = (status == 1) | (status == 4)
    x = jnp.where(bad, jnp.nan, x)
    obj = jnp.where(bad, jnp.nan, obj)
    # the exit basis rides out with every solve: it is the warm-start seed
    # for the next solve of a perturbed same-shape instance
    return x, obj, status, it1 + it2, it1, it2, basis


_standard_rows_batch = jax.jit(jax.vmap(_standard_rows))


def _warm_verify(c, A_ub, b_ub, A_eq, b_eq, basis):
    """Basis-seeded verify-first warm entry: accept each carried basis at
    zero pivots when it is still *optimal* under the (perturbed)
    coefficients.

    The standard-form rows are rebuilt for the new coefficients through the
    same jitted ``_standard_rows`` block the cold path compiles (so both
    entries see bit-identical scaled coefficients), then each lane's basis
    matrix is factored once and the simplex exit certificate is checked
    directly: primal feasibility (``B^-1 b >= 0``) and dual feasibility
    (reduced costs ``c - y A >= 0`` with ``B^T y = c_B``).  Both hold — the
    usual case after a small coefficient drift — and the vertex is provably
    optimal with no tableau built and no pivot loop entered, so a lane
    costs ~R^3/3 flops against the cold path's ~pivots x R x C pivot work.
    The factorizations run through numpy's *stacked* LAPACK ``solve`` (one C
    loop over lanes) rather than a vmapped ``jnp.linalg`` call: on CPU the
    batched-LU lowering is an order of magnitude slower than LAPACK's, and
    this one-shot verify has no jit win to amortize that.

    Returns ``(x, obj, accept, basis)`` — lanes with ``accept`` False must
    be cold-solved by the caller: the carried basis was no longer feasible
    or optimal, the factorization was singular/ill-conditioned (non-finite
    solve output or a primal/dual residual above tolerance, e.g. a
    duplicated basis id), or — the ``None`` return — some lane's basis
    matrix was *exactly* singular, which LAPACK reports batch-wide.
    Rejection never changes an answer, only its speed.
    """
    B, n = c.shape
    m_ub = A_ub.shape[1]
    dummy = n + m_ub

    M, _, c_s, col_scale = _standard_rows_batch(c, A_ub, b_ub, A_eq, b_eq)
    M = np.asarray(M)
    c_s = np.asarray(c_s)
    col_scale = np.asarray(col_scale)
    safe = np.clip(basis, 0, dummy - 1)
    Bm = np.take_along_axis(M, safe[:, None, :], axis=2)  # [B, R, R]
    rhs = M[:, :, -1]
    c_cols = np.zeros((B, dummy))
    c_cols[:, :n] = c_s  # slack/dummy columns price at 0
    cB = np.take_along_axis(c_cols, safe, axis=1)
    try:
        with np.errstate(all="ignore"):
            xB = np.linalg.solve(Bm, rhs[..., None])[..., 0]  # basic values
            y = np.linalg.solve(np.swapaxes(Bm, 1, 2), cB[..., None])[..., 0]
    except np.linalg.LinAlgError:
        return None  # an exactly singular basis matrix somewhere: all cold
    with np.errstate(invalid="ignore"):
        red = c_cols - np.einsum("br,brj->bj", y, M[:, :, :dummy])
        primal_resid = np.abs(np.einsum("brk,bk->br", Bm, xB) - rhs).max(axis=1)
        dual_resid = np.abs(np.einsum("brk,br->bk", Bm, y) - cB).max(axis=1)
        scale = np.maximum(1.0, np.abs(M).reshape(B, -1).max(axis=1))
        cscale = np.maximum(1.0, np.abs(c_s).max(axis=1))
        accept = (
            np.isfinite(xB).all(axis=1)
            & np.isfinite(y).all(axis=1)
            & (primal_resid <= 1e-8 * scale)
            & (dual_resid <= 1e-8 * cscale)
            & (xB.min(axis=1, initial=0.0) >= -1e-9)  # still a vertex
            & (red.min(axis=1, initial=0.0) >= -_EPS)  # no column prices in
        )

    xfull = np.zeros((B, dummy))
    np.put_along_axis(xfull, safe, np.where(accept[:, None], xB, 0.0), axis=1)
    x = col_scale * xfull[:, :n]  # undo column scaling
    obj = np.einsum("bn,bn->b", c, x)
    return x, obj, accept, safe


def _solve_one(c, A_ub, b_ub, A_eq, b_eq, max_iter):
    n = c.shape[0]
    m_rows = A_ub.shape[0] + A_eq.shape[0]
    dummy = n + A_ub.shape[0]
    bland_after = max(200, 4 * (m_rows + 1))

    T, basis, c_s, col_scale = _setup_one(c, A_ub, b_ub, A_eq, b_eq)
    T, basis, it1, st1 = _phase(T, basis, dummy, max_iter, bland_after)
    T, basis, infeasible, drivable = _between_phases(
        T, basis, st1, c_s, n=n, dummy=dummy)
    T, basis, it2, st2 = _phase(T, basis, dummy, max_iter, bland_after)
    return _extract_one(T, basis, col_scale, c, infeasible, drivable,
                        st1, st2, it1, it2, n=n, dummy=dummy)


# ---------------------------------------------------------------------------
# The host<->device boundary of one bucket
#
# A cold bucket crosses to the device as one float64 buffer and back as
# one: each crossing waits for the device and then for the GIL, which the
# server's handler threads hold in turn, so their count costs more than
# their bytes.  In: every lane's [c | A_ub | b_ub | A_eq | b_eq], row-major.
# Out: [x | objective | status, iterations, phase 1, phase 2 | basis]; every
# count and basis id is exact in float64.  Not uint32 words: the TPU keeps
# float64 in an emulated form, and its bitcast from words rounds otherwise
# than its transfer of float64 does (plans would change), while the chip's
# compiler lowers no bitcast from float64 to words at all.
# ---------------------------------------------------------------------------


def _pack_lp(c, A_ub, b_ub, A_eq, b_eq) -> np.ndarray:
    """The bucket's LPs as one ``[B, lp_width]`` float64 buffer."""
    B = c.shape[0]
    return np.concatenate([a.reshape(B, -1) for a in (c, A_ub, b_ub, A_eq, b_eq)],
                          axis=1)


def _packed_lp_struct(B, n, m_ub, m_eq, sharding=None):
    """The shape and dtype of ``_pack_lp``'s buffer, for lowering
    ``_solve_batch`` without the arrays."""
    width = n + m_ub * n + m_ub + m_eq * n + m_eq
    return jax.ShapeDtypeStruct((B, width), jnp.float64, sharding=sharding)


def _unpack_lp(lp, n, m_ub, m_eq):
    """In the program: ``_pack_lp``'s buffer back into (c, A_ub, b_ub, A_eq,
    b_eq) by static slices."""
    B = lp.shape[0]
    parts, off = [], 0
    for shape in ((n,), (m_ub, n), (m_ub,), (m_eq, n), (m_eq,)):
        size = int(np.prod(shape))
        parts.append(lp[:, off:off + size].reshape((B,) + shape))
        off += size
    return parts


def _pack_result(x, obj, status, iters, it1, it2, basis):
    """In the program: the extracted results as one ``[B, n + 5 + m_rows]``
    float64 buffer."""
    counts = jnp.stack([status, iters, it1, it2], axis=1)
    return jnp.concatenate([x, obj[:, None], counts.astype(jnp.float64),
                            basis.astype(jnp.float64)], axis=1)


def _unpack_result(out: np.ndarray, n: int):
    """On the host: ``_pack_result``'s buffer as (x, objective, status,
    iterations, phase-1 and phase-2 iterations, basis), numpy views and
    exact integer casts."""
    counts = out[:, n + 1:n + 5].astype(np.int32)
    return (out[:, :n], out[:, n], counts[:, 0], counts[:, 1], counts[:, 2],
            counts[:, 3], out[:, n + 5:].astype(np.int64))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _solve_batch(lp, n, m_ub, m_eq, max_iter):
    """The served program: one packed bucket in, one packed result out."""
    return _pack_result(*jax.vmap(_solve_one, in_axes=(0, 0, 0, 0, 0, None))(
        *_unpack_lp(lp, n, m_ub, m_eq), max_iter))


def _demote_false_optimal(x, status, A_ub, b_ub, A_eq, b_eq):
    """Batched twin of ``core.backends._primal_violation``: demote "optimal"
    elements whose iterate violates a primal constraint beyond the
    feasibility tolerance to status 5 (``false_optimal``).

    The PR-8 campaign caught the serial dense simplex reading "optimal"
    while a port-serialization row was violated by ~0.24 under an objective
    *better* than the true optimum; the batched simplex runs the same pivot
    arithmetic, so the same silently-lost-pivot escape exists here — and
    the service's replay certification alone cannot be relied on to catch
    it (the objective undershoot can sit inside the replay tolerance).  Two
    batched matvecs make "optimal" mean feasible on every solver exit;
    demoted elements route to the serial rescue exactly like
    any other non-optimal status.  Tolerance matches the serial check:
    ``1e-7 * max(1, max|x|)`` per element.
    """
    opt = status == 0
    if not opt.any():
        return status
    B = x.shape[0]
    viol = np.zeros(B)
    with np.errstate(invalid="ignore"):
        if A_ub.shape[1]:
            viol = np.maximum(
                viol, (np.einsum("brn,bn->br", A_ub, x) - b_ub).max(axis=1))
        if A_eq.shape[1]:
            viol = np.maximum(
                viol, np.abs(np.einsum("brn,bn->br", A_eq, x) - b_eq).max(axis=1))
        if x.shape[1]:
            viol = np.maximum(viol, (-x).max(axis=1))
            scale = np.maximum(1.0, np.abs(x).max(axis=1))
        else:
            scale = np.ones(B)
        bad = opt & (viol > 1e-7 * scale)
    return np.where(bad, np.int32(5), status).astype(status.dtype)


def solve_simplex_batched(
    c, A_ub=None, b_ub=None, A_eq=None, b_eq=None, max_iter: int = 20_000,
    warm_basis=None,
) -> BatchedSimplexResult:
    """Solve a batch of LPs of identical shape.

    Arguments are batched along axis 0: c [B, n], A_ub [B, mu, n], b_ub
    [B, mu], A_eq [B, me, n], b_eq [B, me]; pass None for absent families.

    The lanes solved cold cross to the device once and back once: every
    lane's LP goes in as one float64 buffer (``_pack_lp``), the one program
    (``_solve_batch``) slices it apart, and its results come back as
    one float64 buffer that the host splits into the result's arrays
    (``_unpack_result``).  Values are bit-identical to passing and fetching
    each array on its own.  The
    crossings are counted in ``repro_simplex_transfers_total`` and their
    bytes in ``repro_simplex_transfer_bytes_total``, both by ``direction``
    (``to_device``, ``to_host``); a call served wholly warm makes none.

    ``warm_basis`` ([B, m_rows] int, ``-1``-filled rows meaning "no seed")
    enables the basis-seeded entry: elements whose carried basis is entirely
    structural/slack ids are verified against the new coefficients with one
    dense factorization (primal feasibility + reduced-cost optimality, the
    simplex exit certificate) and served at zero pivots when it holds; any
    element whose seed is rejected — no longer feasible or optimal under
    the new coefficients, or singular — falls back to the cold two-phase
    solve transparently.  ``result.warm_started`` records
    which elements the warm entry actually served, and ``result.basis``
    carries every element's exit basis for the *next* replan.  Warm-start
    therefore never changes which elements solve, only how fast.
    """
    c = np.asarray(c, dtype=np.float64)
    B, n = c.shape
    A_ub = np.zeros((B, 0, n)) if A_ub is None else np.asarray(A_ub, dtype=np.float64)
    b_ub = np.zeros((B, 0)) if b_ub is None else np.asarray(b_ub, dtype=np.float64)
    A_eq = np.zeros((B, 0, n)) if A_eq is None else np.asarray(A_eq, dtype=np.float64)
    b_eq = np.zeros((B, 0)) if b_eq is None else np.asarray(b_eq, dtype=np.float64)
    if A_ub.shape[0] != B or A_eq.shape[0] != B:
        raise ValueError("batch dims disagree")
    m_rows = A_ub.shape[1] + A_eq.shape[1]
    with x64():
        x = np.empty((B, n))
        obj = np.empty(B)
        status = np.empty(B, np.int32)
        iters = np.empty(B, np.int32)
        it1 = np.empty(B, np.int32)
        it2 = np.empty(B, np.int32)
        basis_out = np.empty((B, m_rows), np.int64) if m_rows else None
        warm_started = np.zeros(B, dtype=bool)

        cold_idx = np.arange(B)
        if warm_basis is not None and m_rows > 0 and B > 0:
            wb = np.asarray(warm_basis)
            if wb.shape != (B, m_rows):
                raise ValueError(
                    f"warm_basis must be [B={B}, m_rows={m_rows}]; got {wb.shape}")
            wb = wb.astype(np.int64)
            dummy = n + A_ub.shape[1]
            cand_idx = np.flatnonzero(np.all((wb >= 0) & (wb < dummy), axis=1))
            verified = _warm_verify(
                c[cand_idx], A_ub[cand_idx], b_ub[cand_idx],
                A_eq[cand_idx], b_eq[cand_idx], wb[cand_idx],
            ) if cand_idx.size else None
            if verified is not None:
                wx, wobj, ok, wbasis = verified
                # accept only certified warm exits: a rejected seed re-solves
                # cold below, so the warm entry can never worsen an outcome,
                # only speed it up
                good = cand_idx[ok]
                if good.size:
                    x[good] = wx[ok]
                    obj[good] = wobj[ok]
                    status[good] = _OPTIMAL
                    iters[good] = 0
                    it1[good] = 0
                    it2[good] = 0
                    basis_out[good] = wbasis[ok]
                    warm_started[good] = True
                    cold_mask = np.ones(B, dtype=bool)
                    cold_mask[good] = False
                    cold_idx = np.flatnonzero(cold_mask)

        if cold_idx.size:
            met = obs_metrics.get_registry()
            n_cold = len(cold_idx)
            m_ub, m_eq = A_ub.shape[1], A_eq.shape[1]
            # host spans: packing and the call (argument transfer and
            # dispatch; the device runs on asynchronously), the fetch of the
            # packed result (which waits for the device), and the host's
            # feasibility pass
            with span("simplex.dispatch", B=n_cold):
                lp = _pack_lp(c, A_ub, b_ub, A_eq, b_eq)
                if n_cold < B:
                    lp = lp[cold_idx]
                out = _solve_batch(lp, n, m_ub, m_eq, int(max_iter))
            met.inc("repro_simplex_transfers_total", direction="to_device")
            met.inc("repro_simplex_transfer_bytes_total", lp.nbytes,
                    direction="to_device")
            with span("simplex.fetch", B=n_cold):
                out = np.asarray(out)
                (x[cold_idx], obj[cold_idx], status[cold_idx], iters[cold_idx],
                 it1[cold_idx], it2[cold_idx], cbasis) = _unpack_result(out, n)
                if basis_out is not None:
                    basis_out[cold_idx] = cbasis
            met.inc("repro_simplex_transfers_total", direction="to_host")
            met.inc("repro_simplex_transfer_bytes_total", out.nbytes,
                    direction="to_host")

        with span("simplex.demote", B=B):
            status = _demote_false_optimal(x, status, A_ub, b_ub, A_eq, b_eq)
        return BatchedSimplexResult(
            x=x,
            objective=obj,
            status=status,
            iterations=iters,
            iterations_phase1=it1,
            iterations_phase2=it2,
            basis=basis_out,
            warm_started=warm_started,
        )
