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
