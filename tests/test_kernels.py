"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Every kernel is executed with interpret=True (the kernel *body* runs on CPU)
and compared against the independent ref.py oracle with dtype-scaled
tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5), jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype)


def check(a, b, dtype):
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32), **TOL[dtype]
    )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Sq, Sk, H, KVH, D, causal, window)
    (1, 128, 128, 4, 2, 32, True, 0),
    (2, 256, 256, 4, 1, 64, True, 0),
    (1, 256, 256, 8, 8, 16, False, 0),
    (1, 256, 256, 4, 2, 32, True, 96),   # sliding window
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention(case, dtype):
    B, Sq, Sk, H, KVH, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Sq, H, D), dtype)
    k = rand(ks[1], (B, Sk, KVH, D), dtype)
    v = rand(ks[2], (B, Sk, KVH, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    check(out, want, dtype)


def test_flash_attention_block_shapes_invariant():
    """Output must not depend on the BlockSpec tiling."""
    B, S, H, KVH, D = 1, 256, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, KVH, D), jnp.float32)
    v = rand(ks[2], (B, S, KVH, D), jnp.float32)
    outs = [
        ops.flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cache_len", [1, 100, 256])
@pytest.mark.parametrize("window", [0, 64])
def test_decode_attention(cache_len, window, dtype):
    B, H, KVH, D, Smax = 2, 4, 2, 32, 256
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (B, 1, H, D), dtype)
    kc = rand(ks[1], (B, Smax, KVH, D), dtype)
    vc = rand(ks[2], (B, Smax, KVH, D), dtype)
    out = ops.decode_attention(q, kc, vc, cache_len, window=window,
                               block_k=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, cache_len, window=window)
    check(out, want, dtype)


def test_decode_attention_traced_cache_len():
    """cache_len must work as a traced scalar (inside jit/scan serving loops)."""
    B, H, KVH, D, Smax = 1, 2, 1, 16, 128
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (B, 1, H, D), jnp.float32)
    kc = rand(ks[1], (B, Smax, KVH, D), jnp.float32)
    vc = rand(ks[2], (B, Smax, KVH, D), jnp.float32)

    @jax.jit
    def run(n):
        return ops.decode_attention(q, kc, vc, n, block_k=32, interpret=True)

    for n in [1, 7, 128]:
        check(run(n), ref.decode_attention_ref(q, kc, vc, n), jnp.float32)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, h, p, g, n, chunk)
    (1, 64, 2, 16, 1, 16, 16),
    (2, 128, 4, 32, 1, 32, 32),
    (1, 128, 4, 16, 2, 16, 64),   # multi-group
    (1, 96, 2, 16, 1, 16, 32),    # s % chunk == 0 but != power of two
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan(case, dtype):
    b, s, h, p, g, n, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = rand(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), dtype=jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), dtype=jnp.float32) * 0.5)
    B = rand(ks[3], (b, s, g, n), dtype)
    C = rand(ks[0], (b, s, g, n), dtype)
    D = jnp.linspace(0.5, 1.5, h, dtype=jnp.float32)
    out = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, A, B, C, D)
    tol = dict(rtol=3e-4, atol=3e-4) if dtype == jnp.float32 else dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32), **tol)


def test_ssd_scan_matches_model_chunked():
    """Kernel == the XLA ssd_chunked implementation used on the dry-run path."""
    from repro.models.ssm import ssd_chunked

    b, s, h, p, g, n = 1, 128, 2, 16, 1, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), dtype=jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), dtype=jnp.float32) * 0.5)
    B = rand(ks[3], (b, s, g, n), jnp.float32)
    C = rand(ks[0], (b, s, g, n), jnp.float32)
    D = jnp.ones((h,), jnp.float32)
    out = ops.ssd_scan(x, dt, A, B, C, D, chunk=32, interpret=True)
    want = ssd_chunked(x, dt, A, B, C, D, chunk=32)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 64), (2, 8, 128), (3, 5, 96)])
def test_rmsnorm(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = rand(ks[0], shape, dtype)
    w = 1.0 + 0.1 * jax.random.normal(ks[1], (shape[-1],), dtype=jnp.float32)
    out = ops.rms_norm(x, w, interpret=True)
    want = ref.rms_norm_ref(x, w)
    check(out, want, dtype)


# ---------------------------------------------------------------------------
# integration: model attention dispatcher with impl="pallas"
# ---------------------------------------------------------------------------


def test_model_attention_pallas_path():
    from repro.models.attention import attention, naive_attention

    B, S, H, KVH, D = 1, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(ks[0], (B, S, H, D), jnp.float32)
    k = rand(ks[1], (B, S, KVH, D), jnp.float32)
    v = rand(ks[2], (B, S, KVH, D), jnp.float32)
    out = attention(q, k, v, impl="pallas", causal=True, shard_seq=False)
    want = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
