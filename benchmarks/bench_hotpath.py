"""Hot-path microbenches: the four recorded paths of the PR-7 overhaul.

  * **key derivation** — content keys/sec for the bulk grouped-quantize path
    (``instance_content_keys``) vs the per-instance reference
    (``_content_key_single``), plus the memoized re-derive rate.  The bulk
    path stacks same-shape instances into one matrix, quantizes once, and
    hashes precomputed bytes — the acceptance bar is >= 10x per-instance.
  * **warm-cache replay** — ``solve_bulk`` inst/s on a fully warmed cache
    (every instance a hit, re-materialized through the batched
    ``simulate_bucket`` replay) vs the serial hit path (one instance per
    call, the per-instance Python the pre-overhaul hit loop paid per hit).
    Bar: batched >= 5x serial.
  * **session-to-direct ratio** — the chain serving mix through the
    coalescing front door vs raw ``solve_bulk`` (bench_session's helpers at
    the same scale).  Bar: >= 0.9 (the dispatch-slimming target; was 0.65).
  * **pivot-kernel roofline** — the tuned fused K-pivot kernel timed on the
    chain bucket's real tableau shape, placed on the roofline via
    ``benchmarks.roofline.kernel_roofline`` (informational on CPU
    interpret: the intensity/bottleneck columns are machine-independent).

CSV: bench_out/hotpath.csv.  The >=-bars are claims at full scale only
(CI smoke boxes make timing noise); smoke runs record the ratios
informationally.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from repro.core.instance import random_instance

from .common import banner, write_csv

N_KEYS = 4096
N_WARM = 1024
N_SERIAL = 64  # serial-hit comparator instance count (one solve_bulk each)


def _key_instances(rng, n: int) -> list:
    """A mixed population (4 shape groups) so bulk grouping is exercised."""
    insts = []
    for i in range(n):
        topo = "chain" if i % 2 == 0 else "star"
        ret = 0.25 if i % 4 == 3 else 0.0
        insts.append(random_instance(
            rng, m=3 + (i % 2), n_loads=2, q=1, topology=topo,
            return_ratio=ret))
    return insts


def _bench_keys(rng, n: int) -> dict:
    from repro.core.keys import (_MEMO_ATTR, _content_key_single,
                                 instance_content_keys)

    insts = _key_instances(rng, n)

    def fresh():  # drop the memos so every bulk rep really derives
        for inst in insts:
            inst.__dict__.pop(_MEMO_ATTR, None)

    # median of 3 for both paths, gc.collect()ed like every timed loop in
    # this suite: the bulk pass allocates one large parts list per call, so
    # a pending collection from earlier benches lands right inside it and
    # the bulk/per-instance ratio becomes a function of bench ordering
    bulk_t, single_t = [], []
    for _ in range(3):
        fresh()
        gc.collect()
        t0 = time.perf_counter()
        bulk = instance_content_keys(insts)
        bulk_t.append(time.perf_counter() - t0)
        gc.collect()
        t0 = time.perf_counter()
        single = [_content_key_single(i) for i in insts]
        single_t.append(time.perf_counter() - t0)
        assert bulk == single, "bulk keys diverged from the per-instance oracle"
    gc.collect()
    t0 = time.perf_counter()
    memo = instance_content_keys(insts)  # all memo probes now
    memo_s = time.perf_counter() - t0
    assert memo == bulk
    return {
        "per_instance": n / sorted(single_t)[1],
        "bulk": n / sorted(bulk_t)[1],
        "memoized": n / memo_s,
    }


def _bench_warm_cache(problems: list, policy) -> dict:
    from repro.api import Session

    sess = Session(policy=policy)
    sess.solve_bulk(problems)  # cold fill: compile + populate the cache
    sess.solve_bulk(problems[:1])  # compile the single-instance replay rung
    gc.collect()  # same hygiene as bench_session: keep pending full
    # collections (earlier sub-benches' garbage) out of the timed loops
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        sess.solve_bulk(problems)  # every instance a hit -> batched replay
        times.append(time.perf_counter() - t0)
    warm = len(problems) / sorted(times)[len(times) // 2]
    serial_probs = problems[:N_SERIAL]
    t0 = time.perf_counter()
    for p in serial_probs:
        sess.solve_bulk([p])  # hits too, but one instance of Python each
    serial = len(serial_probs) / (time.perf_counter() - t0)
    return {"batched": warm, "serial": serial}


def _bench_pivot_roofline(quick: bool) -> dict | None:
    """Time the tuned K-pivot kernel on the chain bucket's tableau shape."""
    from repro.engine.autotune import _probe_stack, cache_snapshot, pivot_schedule
    from repro.jaxenv import x64
    from repro.kernels.ops import scheduling_kernels_error, simplex_pivot

    from .roofline import kernel_roofline

    if scheduling_kernels_error() is not None:
        return None
    # the chain-mix LP tableau shape (m=3, 2 loads, q=1) as solved by the
    # pallas driver; pivot_schedule memoizes, so a prior pallas solve in
    # this process would already have tuned it
    R, C = 8, 15
    tune = pivot_schedule(R, C)
    k = tune["k_pivots"]
    B = 16 if quick else 64
    T, basis, it, status = _probe_stack(R, C)
    reps = max(1, B // T.shape[0])
    T = np.tile(T, (reps, 1, 1))[:B]
    basis = np.tile(basis, (reps, 1))[:B]
    it = np.tile(it, reps)[:B]
    status = np.tile(status, reps)[:B]
    kw = dict(ncols_price=C - 1, bland_after=10_000, max_iter=10_000,
              k_pivots=k)
    with x64():
        out = simplex_pivot(T, basis, it, status, **kw)  # compile
        out[0].block_until_ready()
        t0 = time.perf_counter()
        n_launch = 2 if quick else 8
        for _ in range(n_launch):
            out = simplex_pivot(T, basis, it, status, **kw)
        out[0].block_until_ready()
        dt = time.perf_counter() - t0
    pivots = B * k * n_launch
    # per pivot per lane: two one-hot contractions + the rank-1 update
    # (~6RC flops); minimal HBM traffic = read + write the tableau block
    rl = kernel_roofline(flops=pivots * 6 * R * C,
                         bytes_moved=pivots * 2 * R * C * 8, seconds=dt)
    rl["k_pivots"] = k
    rl["shape"] = f"{R}x{C}"
    rl["autotune_entries"] = len(cache_snapshot())
    return rl


def main(quick: bool = False) -> dict:
    from repro.api import Policy

    from .bench_session import _direct_throughput, _mix, _session_throughput

    banner("bench_hotpath (keys / warm cache / session ratio / pivot kernel)")
    policy = Policy(backend="batched")
    claims: dict = {}

    n_keys = 512 if quick else N_KEYS
    # dedicated rng per sub-bench: the populations stay identical no matter
    # which sub-benches run or how they're reordered (and the warm/session
    # mix reuses bench_session's seed-0 stream, so the ratio here is
    # measured on the same instances that bench drives)
    keys = _bench_keys(np.random.default_rng(11), n_keys)
    key_speedup = keys["bulk"] / keys["per_instance"]
    print(f"  keys/s: per-instance {keys['per_instance']:9.0f}   "
          f"bulk {keys['bulk']:9.0f} ({key_speedup:.1f}x)   "
          f"memoized {keys['memoized']:9.0f}")

    n_warm = 128 if quick else N_WARM
    problems = _mix(np.random.default_rng(0), n_warm, "chain")
    warm = _bench_warm_cache(problems, policy)
    warm_speedup = warm["batched"] / warm["serial"]
    print(f"  warm-cache hits: batched {warm['batched']:9.0f} inst/s   "
          f"serial {warm['serial']:9.0f} inst/s ({warm_speedup:.1f}x)")

    sess_ips, _ = _session_throughput(problems, policy)
    direct_ips = _direct_throughput(problems, policy)
    ratio = sess_ips / direct_ips
    print(f"  session-to-direct (chain): {sess_ips:9.0f} / {direct_ips:9.0f} "
          f"= {ratio:.2f}")

    rows = [
        ["keys_per_sec", "per_instance", keys["per_instance"]],
        ["keys_per_sec", "bulk", keys["bulk"]],
        ["keys_per_sec", "memoized", keys["memoized"]],
        ["warm_hit_inst_per_sec", "batched", warm["batched"]],
        ["warm_hit_inst_per_sec", "serial", warm["serial"]],
        ["session_to_direct_ratio", "chain", ratio],
    ]
    rl = _bench_pivot_roofline(quick)
    if rl:
        print(f"  pivot kernel ({rl['shape']}, K={rl['k_pivots']}): "
              f"intensity {rl['intensity_flop_per_byte']:.2f} FLOP/B, "
              f"{rl['achieved_gflops']:.2f} GFLOP/s achieved, "
              f"{rl['bottleneck']}-bound on the v5e roofline")
        rows.append(["pivot_intensity_flop_per_byte", rl["shape"],
                     rl["intensity_flop_per_byte"]])
        rows.append(["pivot_achieved_gflops", rl["shape"],
                     rl["achieved_gflops"]])
    write_csv("hotpath.csv", rows, ["metric", "label", "value"])

    if quick:
        claims["bulk_key_speedup"] = round(key_speedup, 1)
        claims["warm_hit_speedup"] = round(warm_speedup, 1)
        claims["session_to_direct_chain"] = round(ratio, 2)
    else:
        claims["bulk_keys_10x"] = key_speedup >= 10.0
        claims["warm_cache_5x_serial_hit"] = warm_speedup >= 5.0
        claims["session_to_direct_ge_090"] = ratio >= 0.9
    for k, v in claims.items():
        if isinstance(v, bool):
            print(f"  CLAIM {k}: {'OK' if v else 'VIOLATED'}")
        else:
            print(f"  CLAIM {k} = {v} (informational at smoke scale)")
    return claims


if __name__ == "__main__":
    main()
