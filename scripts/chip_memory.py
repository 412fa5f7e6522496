#!/usr/bin/env python3
"""Device memory of the chip smoke's paper-size simplex program, two ways.

    python scripts/chip_memory.py

On one TPU, for population A of ``chip_smoke.py`` (16 Table-2 chains, one
engine bucket): compiles the batched simplex program ``_solve_batch`` for
the bucket's shapes on the chip and prints the compiler's
``memory_analysis``; then runs the bucket once through
``solve_simplex_batched`` (the call the engine makes) and prints the
device's ``memory_stats`` before and after.  Whether ``peak_bytes_in_use``
counts the program's temporaries is read off the two.  Exits non-zero
without a TPU.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_memory: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1

    from chip_smoke import lp_bytes, populations, simplex_memory
    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket
    from repro.engine.batched_simplex import solve_simplex_batched
    from repro.jaxenv import use_compile_cache

    print(f"device: {dev.platform} {dev.device_kind}; compile cache "
          f"{use_compile_cache()}", flush=True)
    pop_a = populations(0)[0]
    insts = [p.to_instance() for p in pop_a]
    tableau, inputs = lp_bytes(insts)
    bucket = InstanceArena(insts).buckets[0]
    lp = build_lp_bucket(bucket)
    c = np.tile(lp.c, (bucket.B, 1))
    args = (c, lp.A_ub, lp.b_ub, lp.A_eq, lp.b_eq)
    print(f"population A: B={bucket.B}, LP {lp.A_ub.shape[1] + lp.A_eq.shape[1]}"
          f" x {lp.c.shape[0]}, f64 inputs {inputs}, tableaux {tableau}",
          flush=True)

    t0 = time.perf_counter()
    ma = simplex_memory(insts)
    print(f"compiled on the chip in {time.perf_counter() - t0:.3f} s: "
          f"memory_analysis arguments {ma.argument_size_in_bytes}, "
          f"outputs {ma.output_size_in_bytes}, temporaries "
          f"{ma.temp_size_in_bytes}", flush=True)

    before = dev.memory_stats()
    t0 = time.perf_counter()
    res = solve_simplex_batched(*args)
    after = dev.memory_stats()
    print(f"solve_simplex_batched: {time.perf_counter() - t0:.3f} s, "
          f"statuses {res.status.tolist()}", flush=True)
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        print(f"memory_stats {key}: before {before.get(key)} after "
              f"{after.get(key)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
