"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Every kernel is executed with interpret=True (the kernel *body* runs on CPU)
and compared against the independent ref.py oracle with dtype-scaled
tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.jaxenv import x64
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


# ---------------------------------------------------------------------------
# scheduling kernels: fused simplex pivot + ASAP replay (float64 paths)
# ---------------------------------------------------------------------------


def _random_tableau_stack(rng, B, R, C):
    T = jnp.asarray(rng.normal(size=(B, R, C)))
    T = T.at[:, :-1, -1].set(jnp.abs(T[:, :-1, -1]))  # feasible rhs
    basis = jnp.asarray(rng.integers(0, C - 2, size=(B, R - 1)))
    return T, basis


@pytest.mark.parametrize("B,R,C", [(1, 2, 4), (4, 5, 8), (3, 7, 12)])
def test_simplex_pivot_kernel_matches_ref(B, R, C):
    rng = np.random.default_rng(0)
    with x64():
        T, basis = _random_tableau_stack(rng, B, R, C)
        it = jnp.zeros(B, jnp.int32)
        status = jnp.full(B, -1, jnp.int32)
        kw = dict(ncols_price=C - 2, bland_after=100, max_iter=50)
        for step in range(3):  # iterate: pivots compound, refs must track
            out = ops.simplex_pivot(T, basis, it, status, interpret=True, **kw)
            want = ref.simplex_pivot_ref(T, basis, it, status, **kw)
            for got, exp, name in zip(out, want, ("T", "basis", "it", "status")):
                np.testing.assert_allclose(
                    np.asarray(got, np.float64), np.asarray(exp, np.float64),
                    rtol=0, atol=1e-12, err_msg=f"{name} at step {step}")
            T, basis, it, status = out


def test_simplex_pivot_kernel_masks_finished_elements():
    rng = np.random.default_rng(1)
    with x64():
        T, basis = _random_tableau_stack(rng, 3, 4, 7)
        it = jnp.asarray([0, 0, 99], jnp.int32)
        status = jnp.asarray([-1, 0, -1], jnp.int32)  # b=1 done, b=2 exhausted
        out = ops.simplex_pivot(T, basis, it, status, ncols_price=5,
                                bland_after=100, max_iter=50, interpret=True)
        # finished/exhausted elements pass through bit-identically
        for b in (1, 2):
            np.testing.assert_array_equal(np.asarray(out[0])[b], np.asarray(T)[b])
            np.testing.assert_array_equal(np.asarray(out[1])[b], np.asarray(basis)[b])
            assert int(out[2][b]) == int(it[b])
        assert int(out[3][1]) == 0  # optimal stays optimal


@pytest.mark.parametrize("bland_after,want_row", [(100, 2), (0, 1)])
def test_simplex_pivot_harris_ratio_test(bland_after, want_row):
    """Column 0 enters.  Row 0's entry is above the absolute pivot threshold
    but below the relative one (an exact min-ratio rule would take it at
    ratio 0); rows 1 and 2 tie
    within Harris's step, where Dantzig takes the larger pivot (row 2) and
    Bland the smaller basis index (row 1)."""
    with x64():
        T = jnp.asarray([[[2e-9, 1.0, 0.0, 0.0],
                          [2.0, 0.0, 0.0, 1.0],
                          [4.0, 1.0, 0.0, 2.0 + 1e-10],
                          [-1.0, 0.0, 0.0, 0.0]]])
        basis = jnp.asarray([[5, 6, 7]], jnp.int32)
        it = jnp.zeros(1, jnp.int32)
        status = jnp.full(1, -1, jnp.int32)
        kw = dict(ncols_price=2, bland_after=bland_after, max_iter=50)
        out = ops.simplex_pivot(T, basis, it, status, interpret=True, **kw)
        want = ref.simplex_pivot_ref(T, basis, it, status, **kw)
        for got, exp in zip(out, want):
            np.testing.assert_allclose(np.asarray(got, np.float64),
                                       np.asarray(exp, np.float64),
                                       rtol=0, atol=1e-12)
        assert np.asarray(out[1])[0].tolist() == [
            0 if r == want_row else b for r, b in enumerate([5, 6, 7])]


def _random_replay_batch(rng, B, m, T):
    mk = lambda *s: jnp.abs(jnp.asarray(rng.normal(size=s)))
    return (mk(B, m, T) + 0.1, mk(B, m - 1) + 0.1, mk(B, m - 1) * 0.01,
            mk(B, m) * 0.1, mk(B, T) + 0.1, mk(B, T) + 0.1, mk(B, T) * 0.2,
            jnp.ones(T), mk(B, m, T) + 0.05)


@pytest.mark.parametrize("B,m,T", [(1, 2, 1), (3, 4, 5), (2, 6, 8)])
def test_asap_replay_kernel_matches_ref(B, m, T):
    rng = np.random.default_rng(2)
    with x64():
        args = _random_replay_batch(rng, B, m, T)
        out = ops.asap_replay(*args, interpret=True)
        want = ref.asap_replay_ref(*args)
        for got, exp, name in zip(out, want, ("cs", "ce", "ps", "pe", "mk")):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(exp), rtol=0, atol=1e-12,
                err_msg=name)


def test_asap_replay_kernel_masks_padded_cells():
    rng = np.random.default_rng(3)
    with x64():
        args = list(_random_replay_batch(rng, 2, 3, 6))
        valid = jnp.asarray([1.0, 1.0, 1.0, 1.0, 0.0, 0.0])
        # padded trailing cells: zero volumes/releases, latency masked by valid
        for i in (4, 5, 6):  # vcomm, vcomp, rel
            args[i] = args[i].at[:, 4:].set(0.0)
        args[8] = args[8].at[:, :, 4:].set(0.0)  # gamma
        args[7] = valid
        cs, ce, ps, pe, mk = ops.asap_replay(*args, interpret=True)
        real_mk = np.max(np.asarray(pe)[:, :, 3], axis=1)
        np.testing.assert_allclose(np.asarray(mk), real_mk, rtol=0, atol=1e-12)


def test_scheduling_kernels_available_probe():
    assert ops.scheduling_kernels_error() is None  # interpret mode on the CPU


def test_pallas_selection_raises_where_kernels_cannot_compile(monkeypatch):
    """Selecting 'pallas' where its kernels do not compile raises with the
    compiler's own reason instead of quietly serving 'batched'.  Steered
    here by turning interpret mode off, which the CPU backend refuses —
    the same path a device that rejects the kernels takes."""
    from repro.api import Policy, Problem, Session

    monkeypatch.setattr(
        ops, "_interp", lambda interpret: False if interpret is None else interpret)
    ops.scheduling_kernels_error.cache_clear()
    try:
        assert "interpret mode" in ops.scheduling_kernels_error()
        problem = Problem(w=[1.0, 2.0], z=[0.5], v_comm=[1.0], v_comp=[1.0])
        with pytest.raises(RuntimeError, match="pallas backend cannot run on cpu"):
            Session(policy=Policy(backend="pallas")).solve(problem)
    finally:
        ops.scheduling_kernels_error.cache_clear()  # re-probe unpatched next time
