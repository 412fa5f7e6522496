"""jit'd public wrappers around the Pallas kernels.

Model code calls these (via ShardingPolicy.attention_impl == "pallas" etc.);
layout munging (head-major transposes, GQA bookkeeping) happens here so the
kernels see clean [B, H, S, D] blocks.  ``interpret`` defaults to True on the
CPU only, so the same call sites run the kernel *body* there for validation
and compile it for the device everywhere else.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_call
from .flash_attention import flash_attention_call
from .rmsnorm import rmsnorm_call
from .ssd_scan import ssd_scan_call

__all__ = [
    "flash_attention",
    "decode_attention",
    "ssd_scan",
    "rms_norm",
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
