"""Kernel micro-bench: Pallas (interpret=True on CPU — a correctness/port
harness, not a wall-clock claim) vs the XLA reference path, plus max-abs-err
against the jnp oracle.  On a real TPU the same harness times the compiled
kernels; here the value is the deltas + the FLOPs bookkeeping.

Every row also carries roofline context (benchmarks.roofline.kernel_roofline):
an analytic FLOP count and minimal-HBM-bytes estimate give the arithmetic
intensity and the binding roof — machine-independent columns — next to the
achieved-vs-peak fractions of the measured run (near zero under CPU
interpret, meaningful when the same harness runs compiled on a TPU).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.models.attention import chunked_attention

from .common import banner, write_csv
from .roofline import kernel_roofline


def _t(fn, *args, n=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _row(kernel, shape, flops, bytes_moved, t_pal, t_xla, err):
    """One CSV row: timings + the roofline placement of the pallas timing."""
    rl = kernel_roofline(flops, bytes_moved, t_pal if np.isfinite(t_pal) else 0.0)
    return [kernel, shape, flops, bytes_moved, t_pal, t_xla, err,
            rl["intensity_flop_per_byte"], rl["achieved_gflops"],
            rl["peak_frac_compute"], rl["peak_frac_memory"], rl["bottleneck"]]


def _scheduling_rows(quick: bool) -> list:
    """The engine's own hot loops: one fused pivot over a synthetic tableau
    stack, and the fused ASAP replay of an arena-shaped bucket."""
    rows = []
    reason = ops.scheduling_kernels_error()
    if reason is not None:
        print(f"  scheduling kernels unavailable here ({reason}) — skipping their rows")
        return rows
    from repro.jaxenv import x64

    key = jax.random.PRNGKey(7)
    with x64():
        # simplex_pivot: [B, R, C] stack, rhs kept feasible so the masked
        # pivot does real pricing + elimination work on every element
        B, R, C = (16, 16, 32) if quick else (64, 16, 32)
        ks = jax.random.split(key, 2)
        T = jax.random.normal(ks[0], (B, R, C), jnp.float64)
        T = T.at[:, :-1, -1].set(jnp.abs(T[:, :-1, -1]) + 1.0)
        basis = jnp.tile(jnp.arange(R - 1, dtype=jnp.int32)[None], (B, 1))
        it = jnp.zeros(B, jnp.int32)
        status = jnp.full(B, -1, jnp.int32)

        def pivot(T, basis, it, status):
            return ops.simplex_pivot(T, basis, it, status, ncols_price=C - 1,
                                     bland_after=8, max_iter=4, interpret=True)

        t_piv = _t(pivot, T, basis, it, status)
        got = pivot(T, basis, it, status)[0]
        want = ref.simplex_pivot_ref(T, basis, it, status, ncols_price=C - 1,
                                     bland_after=8, max_iter=4)[0]
        err = float(jnp.abs(got - want).max())
        # elimination is one fma per tableau cell; traffic is one f64
        # read + write of the stack (pricing/ratio columns are minor)
        flops = 2.0 * B * R * C
        bytes_moved = 8.0 * 2 * B * R * C
        rows.append(_row("simplex_pivot", f"{B}x{R}x{C}", flops, bytes_moved,
                         t_piv, np.nan, err))
        print(f"  simplex_pivot {B}x{R}x{C}: pallas(interp) {t_piv*1e3:.1f}ms "
              f"max_err {err:.2e}")

        # asap_replay: an arena-shaped chain bucket (m procs, T cells)
        B, m, T_ = (16, 4, 8) if quick else (64, 4, 8)
        ks = jax.random.split(key, 4)
        w_cell = jnp.abs(jax.random.normal(ks[0], (B, m, T_), jnp.float64)) + 0.1
        z = jnp.abs(jax.random.normal(ks[1], (B, m - 1), jnp.float64)) * 0.1
        latency = jnp.zeros((B, m - 1), jnp.float64)
        tau = jnp.zeros((B, m), jnp.float64)
        vcomm = jnp.ones((B, T_), jnp.float64)
        vcomp = jnp.ones((B, T_), jnp.float64)
        rel = jnp.zeros((B, T_), jnp.float64)
        valid = jnp.ones(T_, bool)
        g = jnp.abs(jax.random.normal(ks[2], (B, m, T_), jnp.float64)) + 0.01
        g = g / g.sum(axis=1, keepdims=True)

        def replay(w_cell, z, latency, tau, vcomm, vcomp, rel, g):
            return ops.asap_replay(w_cell, z, latency, tau, vcomm, vcomp, rel,
                                   valid, g, topology="chain", interpret=True)

        t_rep = _t(replay, w_cell, z, latency, tau, vcomm, vcomp, rel, g)
        got = replay(w_cell, z, latency, tau, vcomm, vcomp, rel, g)[-1]
        want = ref.asap_replay_ref(w_cell, z, latency, tau, vcomm, vcomp, rel,
                                   valid, g, topology="chain")[-1]
        err = float(jnp.abs(got - want).max())
        # the recurrence does ~6 max/fma ops per (proc, cell); traffic is
        # the packed bucket read + the four event planes written back
        flops = 6.0 * B * m * T_
        bytes_moved = 8.0 * B * T_ * (2 * m + 4 + 4 * m)
        rows.append(_row("asap_replay", f"{B}x{m}x{T_}", flops, bytes_moved,
                         t_rep, np.nan, err))
        print(f"  asap_replay {B}x{m}x{T_}: pallas(interp) {t_rep*1e3:.1f}ms "
              f"max_err {err:.2e}")
    return rows


def main(quick: bool = False) -> dict:
    banner("bench_kernels (Pallas interpret vs XLA vs oracle)")
    rows = []
    key = jax.random.PRNGKey(0)

    cases = [(1, 256, 4, 2, 64)] if quick else [(1, 256, 4, 2, 64), (2, 512, 8, 2, 64)]
    for (B, S, H, KVH, D) in cases:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KVH, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KVH, D), jnp.float32)
        flops = 4 * B * H * S * S * D / 2
        # minimal HBM traffic: q + k + v read, attention output written (f32)
        bytes_moved = 4.0 * (2 * B * S * H * D + 2 * B * S * KVH * D)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        t_pal = _t(lambda q, k, v: ops.flash_attention(q, k, v, interpret=True), q, k, v)
        t_xla = _t(jax.jit(lambda q, k, v: chunked_attention(q, k, v, q_chunk=128, kv_chunk=128)), q, k, v)
        err = float(jnp.abs(ops.flash_attention(q, k, v, interpret=True) - want).max())
        rows.append(_row("flash_attention", f"{B}x{S}x{H}x{D}", flops,
                         bytes_moved, t_pal, t_xla, err))
        print(f"  flash_attention {B}x{S}x{H}x{D}: pallas(interp) {t_pal*1e3:.1f}ms "
              f"xla {t_xla*1e3:.1f}ms  max_err {err:.2e}")

    b, s, h, p, n = 1, 256, 4, 32, 32
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    Bm = jax.random.normal(ks[3], (b, s, 1, n), jnp.float32)
    Cm = jax.random.normal(ks[0], (b, s, 1, n), jnp.float32)
    Dm = jnp.ones((h,))
    want = ref.ssd_scan_ref(x, dt, A, Bm, Cm, Dm)
    t_pal = _t(lambda *a: ops.ssd_scan(*a, chunk=64, interpret=True), x, dt, A, Bm, Cm, Dm)
    err = float(jnp.abs(ops.ssd_scan(x, dt, A, Bm, Cm, Dm, chunk=64, interpret=True) - want).max())
    # state outer-product update + output contraction: 2 fma per (t, h, p, n)
    ssd_flops = 4.0 * b * s * h * p * n
    ssd_bytes = 4.0 * (2 * b * s * h * p + 2 * b * s * n + b * s * h)
    rows.append(_row("ssd_scan", f"{b}x{s}x{h}x{p}x{n}", ssd_flops, ssd_bytes,
                     t_pal, np.nan, err))
    print(f"  ssd_scan {b}x{s}x{h}x{p}: pallas(interp) {t_pal*1e3:.1f}ms  max_err {err:.2e}")

    xw = jax.random.normal(key, (1024, 512), jnp.float32)
    w = jnp.ones((512,))
    want = ref.rms_norm_ref(xw, w)
    t_rms = _t(lambda xw, w: ops.rms_norm(xw, w, interpret=True), xw, w)
    err = float(jnp.abs(ops.rms_norm(xw, w, interpret=True) - want).max())
    rows.append(_row("rms_norm", "1024x512", 3.0 * 1024 * 512,
                     4.0 * (2 * 1024 * 512 + 512), t_rms, np.nan, err))
    print(f"  rms_norm 1024x512: max_err {err:.2e}")

    rows.extend(_scheduling_rows(quick))

    write_csv("kernels.csv", rows,
              ["kernel", "shape", "flops", "bytes", "pallas_interp_s", "xla_s",
               "max_abs_err", "intensity_flop_per_byte", "achieved_gflops",
               "peak_frac_compute", "peak_frac_memory", "bottleneck"])
    claims = {"kernel_errs_small": all(r[6] < 1e-3 for r in rows),
              "scheduling_kernels_benched": any(
                  r[0] in ("simplex_pivot", "asap_replay") for r in rows)}
    for k_, v in claims.items():
        print(f"  CLAIM {k_}: {'OK' if v else 'VIOLATED'}")
    return claims


if __name__ == "__main__":
    main()
