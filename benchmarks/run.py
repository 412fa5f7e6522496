"""Benchmark orchestrator: one module per paper table/figure + the roofline
report.  ``python -m benchmarks.run [--full]`` (quick mode is the default so
CI stays fast; --full reproduces the paper-scale statistics).

Each bench writes its CSV or document into ``bench_out/`` and returns its
claims (the paper's statements it checks); the run prints every claim and
exits non-zero if one is violated or a bench fails.  These are CPU
reproductions of the paper's results, not the system's speed: that is
measured on the chip by ``bench/`` (``BENCHMARK.json``).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="paper-scale instance counts")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    args = ap.parse_args(argv)
    quick = not args.full

    from . import (bench_campaign, bench_latency_qstar, bench_lp_scaling,
                   bench_motivating_example, bench_table2, bench_theorem1,
                   roofline)

    benches = {
        "motivating_example": bench_motivating_example.main,
        "table2": bench_table2.main,
        "theorem1": bench_theorem1.main,
        "latency_qstar": bench_latency_qstar.main,
        "lp_scaling": bench_lp_scaling.main,
        # CI gives the campaign its own dedicated step
        # (python -m repro.eval --smoke + check_campaign.py)
        "campaign": bench_campaign.main,
        "roofline_single": lambda quick: roofline.main(quick, mesh="single"),
        "roofline_multi": lambda quick: roofline.main(quick, mesh="multi"),
    }
    if args.only:
        keep = set(args.only.split(","))
        benches = {k: v for k, v in benches.items() if k in keep}

    all_claims = {}
    failures = []
    t0 = time.time()
    for name, fn in benches.items():
        try:
            claims = fn(quick) or {}
        except Exception as e:  # keep going; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, f"{type(e).__name__}: {e}"))
            continue
        for k, v in claims.items():
            all_claims[f"{name}.{k}"] = v

    elapsed = time.time() - t0
    print(f"\n=== summary ({elapsed:.1f}s) ===")
    bad = [k for k, v in all_claims.items() if v is False]
    for k, v in sorted(all_claims.items()):
        import numpy as _np
        if not isinstance(v, (bool, _np.bool_)):
            print(f"  --  {k} = {v}")  # informational (counts etc.)
            continue
        print(f"  {'OK ' if v else 'BAD'} {k} = {v}")
    for name, err in failures:
        print(f"  ERR {name}: {err}")
    print(f"{len(all_claims) - len(bad)}/{len(all_claims)} claims OK, "
          f"{len(failures)} bench errors")
    return 1 if (bad or failures) else 0


if __name__ == "__main__":
    sys.exit(main())
