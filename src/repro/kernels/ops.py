"""jit'd public wrappers around the Pallas kernels.

Model code calls these (via ShardingPolicy.attention_impl == "pallas" etc.);
layout munging (head-major transposes, GQA bookkeeping) happens here so the
kernels see clean [B, H, S, D] blocks.  ``interpret`` defaults to True on the
CPU only, so the same call sites run the kernel *body* there for validation
and compile it for the device everywhere else.
"""

from __future__ import annotations

from functools import cache, partial

import jax
import jax.numpy as jnp

from .asap_replay import asap_replay_call
from .decode_attention import decode_attention_call
from .flash_attention import flash_attention_call
from .rmsnorm import rmsnorm_call
from .simplex_pivot import simplex_pivot_call
from .ssd_scan import ssd_scan_call

__all__ = [
    "flash_attention",
    "decode_attention",
    "ssd_scan",
    "rms_norm",
    "simplex_pivot",
    "asap_replay",
    "scheduling_kernels_error",
]


def _interp(interpret):
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _pick_block(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (prefer multiples of 8)."""
    b = min(target, n)
    while n % b:
        b -= 1
    return b


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128,
                    interpret=None):
    """q [B,Sq,H,D], k/v [B,Sk,KVH,D] -> [B,Sq,H,D]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = _pick_block(q.shape[1], block_q)
    bk = _pick_block(k.shape[1], block_k)
    out = flash_attention_call(
        qt, kt, vt, causal=causal, window=window, block_q=bq, block_k=bk,
        interpret=_interp(interpret),
    )
    return out.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, cache_len, *, window=0, block_k=256,
                     interpret=None):
    """q [B,1,H,D], caches [B,Smax,KVH,D], cache_len scalar -> [B,1,H,D]."""
    qt = q.transpose(0, 2, 1, 3)  # [B,H,1,D]
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    bk = _pick_block(k_cache.shape[1], block_k)
    out = decode_attention_call(
        qt, kt, vt, cache_len, window=window, block_k=bk, interpret=_interp(interpret)
    )
    return out.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D, *, chunk=64, interpret=None):
    """SSD chunked scan; see ssd_scan.py for shapes."""
    L = _pick_block(x.shape[1], chunk)
    return ssd_scan_call(
        x, dt.astype(jnp.float32), A.astype(jnp.float32), B, C,
        D.astype(jnp.float32), chunk=L, interpret=_interp(interpret),
    )


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rms_norm(x, w, *, eps=1e-5, block_rows=256, interpret=None):
    return rmsnorm_call(x, w, eps=eps, block_rows=block_rows, interpret=_interp(interpret))


@partial(jax.jit, static_argnames=("ncols_price", "bland_after", "max_iter",
                                   "k_pivots", "interpret"))
def simplex_pivot(T, basis, it, status, *, ncols_price, bland_after, max_iter,
                  k_pivots=1, interpret=None):
    """Up to ``k_pivots`` fused masked pivots over a [B, R, C] tableau stack
    (see simplex_pivot.py); the batched-simplex hot loop calls this per
    launch, with K chosen by the autotune sweep."""
    return simplex_pivot_call(
        T, basis, it, status, ncols_price=ncols_price, bland_after=bland_after,
        max_iter=max_iter, k_pivots=k_pivots, interpret=_interp(interpret),
    )


@partial(jax.jit, static_argnames=("topology", "interpret"))
def asap_replay(w_cell, z, latency, tau, vcomm, vcomp, rel, valid, gamma,
                retr=None, *, topology="chain", interpret=None):
    """Fused ASAP replay of a packed bucket (see asap_replay.py); needs m >= 2.

    ``topology`` selects the chain or star recurrence; passing ``retr``
    ([B, T] per-cell return ratios) activates the result-return phase and
    appends ``(rs, re)`` to the output tuple.  Both are static structure —
    each (topology, returns) combination compiles its own kernel, mirroring
    the arena's bucket key.
    """
    return asap_replay_call(
        w_cell, z, latency, tau, vcomm, vcomp, rel, valid, gamma, retr,
        topology=topology, interpret=_interp(interpret),
    )


@cache
def scheduling_kernels_error() -> str | None:
    """Why the Pallas scheduling kernels cannot run on this backend, or None.

    Probes once per process with a tiny pivot launch (interpret mode on the
    CPU, compiled for the device everywhere else) and caches the answer.
    Only a refusal by the Pallas lowering or the XLA compiler is caught; its
    message is the returned reason, so a caller that needs the kernels can
    fail with the compiler's own words.  Any other exception propagates.
    """
    from repro.jaxenv import x64

    try:
        with x64():
            T = jnp.zeros((1, 2, 3), jnp.float64).at[:, -1, 0].set(-1.0)
            T = T.at[:, 0, 0].set(1.0).at[:, 0, -1].set(1.0)
            out = simplex_pivot(
                T, jnp.ones((1, 1), jnp.int32), jnp.zeros(1, jnp.int32),
                jnp.full(1, -1, jnp.int32),
                ncols_price=2, bland_after=10, max_iter=10,
                interpret=_interp(None),
            )
            status = int(out[3][0])
    except (NotImplementedError, ValueError, jax.errors.JaxRuntimeError) as e:
        return f"{type(e).__name__}: {e}"
    return None if status in (-1, 0, 2) else f"probe pivot returned status {status}"
