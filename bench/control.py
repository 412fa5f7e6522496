#!/usr/bin/env python3
"""The control of the comparison: the plain reference put in the service's
place, computed one precision lower than the configuration states (float32
for float64), must come out not correct.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] [--plans N]

For each seed it takes the requests a run of the cell draws from that seed,
the first ``N`` of them in the order the clients send them (a run checks
about as many), answers each with the reference's optimal fractions rounded
to float32 and their makespan replayed in float32, and prints the numbers
the comparison reads beside their limits, one JSON line per seed.  Exits
non-zero if any seed's control passes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import check  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def control(workload: str, seed: int, plans: int) -> dict:
    cell = run.find_cell(run.manifest(), workload)
    cfg = traffic.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    problems, records = [], []
    k = 0
    while len(records) < plans:
        for c in range(mix["clients"]):
            if len(records) < plans and traffic.checked(mix, seed, c, k):
                p = traffic.request(cfg, seed, c, k)
                records.append({"problem": len(problems), "t_send": 0.0,
                                "t_done": 0.0, "status": "optimal",
                                "rescued": False,
                                "plan": {"problem": dict(p), "gamma": None,
                                         "makespan": None}})
                problems.append(p)
        k += 1
    records = check.control_plans(problems, records)
    correct, numbers, counts = check.compare(problems, records, cfg["limits"])
    return {"workload": workload, "seed": seed, "correct": correct,
            "counts": counts, "checks": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--plans", type=int, default=128)
    args = ap.parse_args(argv)
    passed = False
    for seed in args.seeds:
        out = control(args.workload, seed, args.plans)
        passed |= out["correct"]
        print(json.dumps(out), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
