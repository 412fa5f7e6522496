#!/usr/bin/env python3
"""Time the batched simplex's host<->device boundary in isolation.

    python scripts/simplex_boundary.py [--reps 20] [--batches 2,8,10]

No server, no handler threads: one process drives the bucket program on
the default device for buckets of ``chain-table2-n5.cold`` requests
(``bench/traffic.py``, seed 0) at each batch size.  For each size it
prints, as medians over ``--reps`` in microseconds:

* each input as its own ``jax.device_put`` (the five arrays the
  unpacked program takes) and the whole LP as one packed buffer, as
  ``uint32`` words and as ``float64``;
* each output of the unpacked program as its own ``np.asarray``, and the
  same outputs packed on the device into one float64 buffer (the chip's
  compiler lowers no float64 -> uint32 bitcast);
* the whole call, arguments in to results on the host: the unpacked
  program (five arrays in, seven fetches out), the served ``_solve_batch``
  (one float64 buffer each way) and the same fed ``uint32`` words, and
  whether each packed result is bit-identical to the unpacked one (NaN
  payloads included).

Exits non-zero when the served program's result differs.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys
import time
from functools import partial

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]


def bucket_lp(B: int):
    import traffic
    from repro.api import Problem
    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket

    cfg = traffic.load_config("chain-table2-n5")
    insts = [Problem(**traffic.request(cfg, 0, b, 0)).to_instance(1)
             for b in range(B)]
    (bucket,) = InstanceArena(insts, pad_shapes=False).buckets
    lp = build_lp_bucket(bucket)
    return (np.tile(lp.c, (B, 1)), lp.A_ub, lp.b_ub, lp.A_eq, lp.b_eq)


def median_us(fn, reps: int) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--batches", default="2,8,10")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.engine.batched_simplex import (
        _pack_lp, _pack_result, _solve_batch, _solve_one, _unpack_lp,
        _unpack_result)
    from repro.jaxenv import x64

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    solve_lanes = jax.vmap(_solve_one, in_axes=(0, 0, 0, 0, 0, None))

    @partial(jax.jit, static_argnums=(5,))
    def unpacked(c, A_ub, b_ub, A_eq, b_eq, max_iter):  # five arrays in, seven out
        return solve_lanes(c, A_ub, b_ub, A_eq, b_eq, max_iter)

    def from_words(w):  # [B, 2k] uint32 -> [B, k] float64
        return lax.bitcast_convert_type(w.reshape(w.shape[0], -1, 2),
                                        jnp.float64)

    @partial(jax.jit, static_argnums=(1, 2, 3, 4))
    def packed_words(w, n, mu, me, max_iter):  # _solve_batch fed words
        return _pack_result(*solve_lanes(
            *_unpack_lp(from_words(w), n, mu, me), max_iter))

    pack_only = jax.jit(_pack_result)

    def same(a, b) -> bool:
        a, b = np.asarray(a), np.asarray(b)
        return a.shape == b.shape and a.tobytes() == b.astype(a.dtype).tobytes()

    names_in = ("c", "A_ub", "b_ub", "A_eq", "b_eq")
    names_out = ("x", "obj", "status", "iters", "it1", "it2", "basis")
    ok = True
    with x64():
        for B in (int(b) for b in args.batches.split(",")):
            lp = bucket_lp(B)
            n, mu, me = lp[0].shape[1], lp[1].shape[1], lp[3].shape[1]
            host = {"f64": _pack_lp(*lp)}
            host["u32"] = host["f64"].view(np.uint32)
            print(f"\nB={B}: n={n} m_ub={mu} m_eq={me}, LP "
                  f"{host['f64'].nbytes} bytes", flush=True)

            # the LP through the device and back, as float64 and as words
            rt64 = jax.jit(lambda a: a + 0.0)(host["f64"])
            rt32 = jax.jit(lambda w: from_words(w) + 0.0)(host["u32"])
            print(f"  round trip bit-identical to the host: float64 "
                  f"{same(host['f64'], rt64)}, uint32 words "
                  f"{same(host['f64'], rt32)}; to each other "
                  f"{same(rt64, rt32)}", flush=True)

            row = []
            for name, a in list(zip(names_in, lp)) + [
                    (f"packed {k}", a) for k, a in host.items()]:
                us = median_us(lambda: jax.device_put(a).block_until_ready(),
                               args.reps)
                row.append(f"{name} {a.nbytes} B {us:.1f}")
            row.append(f"host pack {median_us(lambda: _pack_lp(*lp), args.reps):.1f}")
            print("  in (us): " + "; ".join(row), flush=True)

            fetch = {k: [] for k in names_out + ("packed f64",)}
            for _ in range(args.reps):
                outs = unpacked(*lp, 20_000)
                jax.block_until_ready(outs)
                for name, o in zip(names_out, outs):
                    t0 = time.perf_counter()
                    np.asarray(o)
                    fetch[name].append(time.perf_counter() - t0)
                p = pack_only(*unpacked(*lp, 20_000))
                p.block_until_ready()
                t0 = time.perf_counter()
                np.asarray(p)
                fetch["packed f64"].append(time.perf_counter() - t0)
            print("  out (us): " + "; ".join(
                f"{k} {statistics.median(v) * 1e6:.1f}"
                for k, v in fetch.items()), flush=True)

            def call_unpacked():
                return [np.asarray(o) for o in unpacked(*lp, 20_000)]

            def call_packed():  # what solve_simplex_batched does
                return _unpack_result(np.asarray(
                    _solve_batch(_pack_lp(*lp), n, mu, me, 20_000)), n)

            def call_words():
                return _unpack_result(np.asarray(packed_words(
                    _pack_lp(*lp).view(np.uint32), n, mu, me, 20_000)), n)

            ref = call_unpacked()
            row = [f"unpacked {median_us(call_unpacked, args.reps):.1f}"]
            for name, call in (("packed f64", call_packed),
                               ("packed u32 in", call_words)):
                bits = all(same(r, g) for r, g in zip(ref, call()))
                ok &= bits or call is call_words
                row.append(f"{name} {median_us(call, args.reps):.1f}"
                           f" (bit-identical {bits})")
            print("  whole call (us): " + "; ".join(row), flush=True)
    return 0 if ok else 1

if __name__ == "__main__":
    sys.exit(main())
