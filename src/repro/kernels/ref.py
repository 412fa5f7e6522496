"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Deliberately naive: materialized score matrices, step-by-step scans — no
shared code with the kernels so a bug cannot hide in both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "flash_attention_ref",
    "decode_attention_ref",
    "ssd_scan_ref",
    "rms_norm_ref",
    "simplex_pivot_ref",
    "asap_replay_ref",
]

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=0):
    """q [B,Sq,H,D], k/v [B,Sk,KVH,D] -> [B,Sq,H,D] (GQA broadcast)."""
    B, Sq, H, D = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    kf = jnp.repeat(k, G, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, G, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kf) * (D**-0.5)
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= ki <= qi
    if window > 0:
        mask &= ki > qi - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vf)
    return out.astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, cache_len, *, window=0):
    """q [B,1,H,D], caches [B,Smax,KVH,D] -> [B,1,H,D]."""
    B, _, H, D = q.shape
    Smax, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    kf = jnp.repeat(k_cache, G, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v_cache, G, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), kf) * (D**-0.5)
    idx = jnp.arange(Smax)
    valid = idx < cache_len
    if window > 0:
        valid &= idx > cache_len - 1 - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, vf)
    return out.astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, D):
    """Sequential SSD recurrence. x [b,s,h,p], dt [b,s,h], A/D [h], B/C [b,s,g,n]."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    Bh = jnp.repeat(B, h // g, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, h // g, axis=2).astype(jnp.float32)
    a = jnp.exp(dt.astype(jnp.float32) * A[None, None, :].astype(jnp.float32))
    xbar = (x.astype(jnp.float32) * dt[..., None].astype(jnp.float32))

    def step(state, inp):
        a_t, x_t, B_t, C_t = inp
        state = state * a_t[..., None, None] + x_t[..., :, None] * B_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, C_t)

    init = jnp.zeros((b, h, p, n), dtype=jnp.float32)
    _, ys = jax.lax.scan(
        step,
        init,
        (
            jnp.moveaxis(a, 1, 0),
            jnp.moveaxis(xbar, 1, 0),
            jnp.moveaxis(Bh, 1, 0),
            jnp.moveaxis(Ch, 1, 0),
        ),
    )
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype)


def rms_norm_ref(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)


def _harris_row_ref(colvals, rhs, basis, bland, eps):
    """Harris two-pass ratio test, row by row in plain floats; the pivot
    row, or None when the column is unbounded.

    Candidates: entries above ``eps`` times the column's largest magnitude
    (above ``eps`` alone when none is).  Pass one: the longest step that
    keeps every basic variable above ``-eps``.  Pass two: among candidates
    whose ratio fits that step, the first largest entry — or, under
    ``bland``, the first smallest basis index.
    """
    col = [float(v) for v in colvals]
    rhs = [float(v) for v in rhs]
    big = max([abs(v) for v in col], default=0.0)
    cands = [i for i, v in enumerate(col) if v > eps * max(1.0, big)]
    if not cands:
        cands = [i for i, v in enumerate(col) if v > eps]
    if not cands:
        return None
    step = min((max(rhs[i], 0.0) + eps) / col[i] for i in cands)
    fits = [i for i in cands if rhs[i] / col[i] <= step]
    if bland:
        return min(fits, key=lambda i: int(basis[i]))
    return max(fits, key=lambda i: col[i])


def simplex_pivot_ref(T, basis, it, status, *, ncols_price, bland_after, max_iter):
    """One masked simplex pivot per batch element, element-by-element.

    T [B,R,C], basis [B,R-1], it/status [B] -> the advanced stack.  Dantzig
    pricing with a Bland fallback after ``bland_after``; Harris's two-pass
    ratio test (:func:`_harris_row_ref`); finished/exhausted elements pass
    through.
    Statuses: -1 running, 0 optimal, 2 unbounded.
    """
    eps = 1e-9
    T_out, basis_out, it_out, status_out = [], [], [], []
    for b in range(T.shape[0]):
        Tb, bb, itb, stb = T[b], basis[b], it[b], status[b]
        m_rows = Tb.shape[0] - 1
        if not (stb == -1 and itb < max_iter):  # finished: identity
            T_out.append(Tb), basis_out.append(bb)
            it_out.append(itb), status_out.append(stb)
            continue
        obj = Tb[-1, :ncols_price]
        neg = obj < -eps
        if not bool(jnp.any(neg)):
            T_out.append(Tb), basis_out.append(bb)
            it_out.append(itb), status_out.append(jnp.int32(0))
            continue
        if itb < bland_after:
            col = int(jnp.argmin(obj))
        else:
            col = int(jnp.argmin(jnp.where(neg, jnp.arange(ncols_price), ncols_price)))
        row = _harris_row_ref(Tb[:m_rows, col], Tb[:m_rows, -1], bb,
                              itb >= bland_after, eps)
        if row is None:  # unbounded
            T_out.append(Tb), basis_out.append(bb)
            it_out.append(itb), status_out.append(jnp.int32(2))
            continue
        piv = Tb[row, col]
        Tb = Tb.at[row].divide(piv)
        colv = Tb[:, col].at[row].set(0.0)
        Tb = Tb - colv[:, None] * Tb[row][None, :]
        T_out.append(Tb), basis_out.append(bb.at[row].set(col))
        it_out.append(itb + 1), status_out.append(jnp.int32(-1))
    return (jnp.stack(T_out), jnp.stack(basis_out),
            jnp.stack(it_out).astype(it.dtype), jnp.stack(status_out).astype(status.dtype))


def asap_replay_ref(w_cell, z, latency, tau, vcomm, vcomp, rel, valid, gamma,
                    retr=None, topology="chain"):
    """Step-by-step ASAP replay: w_cell/gamma [B,m,T], z/latency [B,m-1],
    tau [B,m], vcomm/vcomp/rel [B,T], valid [T] -> (cs, ce, ps, pe, mk).

    ``topology`` switches between the chain recurrence (store-and-forward +
    own-port) and the star's one-port-master send chain; passing ``retr``
    ([B, T] per-cell return ratios) activates the result-return phase and
    appends ``(rs, re)`` before ``mk``.
    """
    B, m, T = gamma.shape
    star = topology == "star"
    cs = jnp.zeros((B, m - 1, T))
    ce = jnp.zeros((B, m - 1, T))
    ps = jnp.zeros((B, m, T))
    pe = jnp.zeros((B, m, T))
    rs = jnp.zeros((B, m - 1, T))
    re = jnp.zeros((B, m - 1, T))
    mks = []
    for b in range(B):
        if star:
            vol = gamma[b, 1:, :]
        else:
            vol = jnp.cumsum(gamma[b, ::-1], axis=0)[::-1][1:, :]
        dcomm = (z[b][:, None] * vcomm[b][None, :] * vol
                 + latency[b][:, None]) * valid[None, :]
        dcomp = w_cell[b] * vcomp[b][None, :] * gamma[b]
        if retr is not None:
            dret = (z[b][:, None] * (retr[b] * vcomm[b])[None, :] * vol
                    + latency[b][:, None]) * valid[None, :]
        for t in range(T):
            for i in range(m - 1):
                if star:
                    lo = rel[b, t]
                    if i > 0:
                        lo = jnp.maximum(lo, ce[b, i - 1, t])  # one-port, in cell
                    elif t > 0:
                        lo = jnp.maximum(lo, ce[b, m - 2, t - 1])  # across cells
                else:
                    lo = rel[b, t] if i == 0 else ce[b, i - 1, t]
                    if t > 0:
                        lo = jnp.maximum(lo, ce[b, i, t - 1])  # (2b)/(3b) own-port
                        if i + 1 <= m - 2:
                            lo = jnp.maximum(lo, ce[b, i + 1, t - 1])  # (2)/(3)
                lo = jnp.maximum(lo, 0.0)
                cs = cs.at[b, i, t].set(lo)
                ce = ce.at[b, i, t].set(lo + dcomm[i, t])
            for i in range(m):
                start = tau[b, i] if t == 0 else pe[b, i, t - 1]
                recv = rel[b, t] if i == 0 else ce[b, i - 1, t]
                s = jnp.maximum(start, recv)
                ps = ps.at[b, i, t].set(s)
                pe = pe.at[b, i, t].set(s + dcomp[i, t])
            if retr is not None:
                order = range(m - 1) if star else range(m - 2, -1, -1)
                for i in order:
                    lo = pe[b, i + 1, t]  # (R6)
                    if star:
                        if i > 0:
                            lo = jnp.maximum(lo, re[b, i - 1, t])  # (R1*)
                        elif t > 0:
                            lo = jnp.maximum(lo, re[b, m - 2, t - 1])
                    else:
                        if i + 1 <= m - 2:
                            lo = jnp.maximum(lo, re[b, i + 1, t])  # (R1)
                        if t > 0:
                            lo = jnp.maximum(lo, re[b, i, t - 1])  # (R2b)
                    lo = jnp.maximum(lo, 0.0)
                    rs = rs.at[b, i, t].set(lo)
                    re = re.at[b, i, t].set(lo + dret[i, t])
        mk = jnp.max(pe[b, :, -1])
        if retr is not None:
            mk = jnp.maximum(mk, jnp.max(re[b]))
        mks.append(mk)
    mk = jnp.stack(mks)
    if retr is not None:
        return cs, ce, ps, pe, rs, re, mk
    return cs, ce, ps, pe, mk
