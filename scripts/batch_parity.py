#!/usr/bin/env python3
"""Whether the plan the engine serves for an LP depends on its batch.

    python scripts/batch_parity.py [--n 16] [--loads 50] [--shards 4]

Draws ``n`` Table-2 chains (m=10, heterogeneous powers, latencies,
``loads`` loads, q=1, comm-to-comp 1 byte/FLOP) from seed 0 and solves them
on the default device twice: as one engine bucket of ``n`` lanes
(``solve_bulk``) and as ``shards`` logical shards of ``n / shards`` lanes
each (``solve_bulk_sharded``).  Prints, per lane, the largest gamma
difference, the relative makespan difference and both serving backends.
Runs on any JAX platform; exits non-zero only on an error.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=16)
    ap.add_argument("--loads", type=int, default=50)
    ap.add_argument("--shards", type=int, default=4)
    args = ap.parse_args(argv)

    import jax

    from repro.api import Problem
    from repro.core.instance import random_instance
    from repro.engine.service import solve_bulk
    from repro.jaxenv import use_compile_cache
    from repro.serve.shard import solve_bulk_sharded

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}; compile cache "
          f"{use_compile_cache()}", flush=True)
    rng = np.random.default_rng(0)
    insts = [Problem.from_instance(random_instance(
        rng, m=10, n_loads=args.loads, heterogeneous=True,
        with_latency=True)).to_instance() for _ in range(args.n)]

    t0 = time.perf_counter()
    whole = solve_bulk(insts)
    print(f"one bucket of {args.n}: {time.perf_counter() - t0:.3f} s", flush=True)
    t0 = time.perf_counter()
    split = solve_bulk_sharded(insts, n_shards=args.shards)
    print(f"{args.shards} logical shards: {time.perf_counter() - t0:.3f} s",
          flush=True)
    for i, (a, b) in enumerate(zip(whole, split)):
        print(f"lane {i}: max |gamma diff| "
              f"{float(np.max(np.abs(a.schedule.gamma - b.schedule.gamma))):.3e}, "
              f"rel makespan diff {abs(a.makespan - b.makespan) / a.makespan:.3e}, "
              f"served by {a.backend} / {b.backend}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
