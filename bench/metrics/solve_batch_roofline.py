"""solve_batch_roofline: the least time the device simplex (the
``jit__solve_batch`` program) could take over the time it took, in the
traced stretch of the window.

Least time: the HBM bytes each bucket's dense tableau must move
(``roofline.least_bytes``, from the bucket's size and its lanes' pivots as
the served plans report them) over the chip's HBM bandwidth.  Measured
time: the device time of the program's executions in the stretch.  One
worker solves the buckets one after another and the profiler starts before
the first, so the k-th execution is the k-th bucket to be answered; the
last execution in the stretch is left out, as the profiler may have stopped
inside it.  Reads nothing where a plan of the window was not kept or the
buckets and the executions do not line up."""

import roofline

PROGRAM = "jit__solve_batch"


def read(run):
    lo, hi = run.window_ns
    runs = [(s, e) for s, e, name in run.modules[0]
            if name == PROGRAM and lo <= s and e <= hi][:-1]
    answered = [r for r in run.records if "t_done" in r]
    if not runs or any(r.get("plan") is None for r in answered):
        return None
    buckets: dict = {}
    for r in answered:
        p = r["plan"]
        first, lanes = buckets.setdefault(tuple(p["bucket_id"]), [r["t_done"], []])
        buckets[tuple(p["bucket_id"])][0] = min(first, r["t_done"])
        lanes.append(tuple(p["pivots"]))
    lanes = [v[1] for v in sorted(buckets.values(), key=lambda v: v[0])]
    spans = sorted((s for s in run.spans if s["name"] == "engine.simplex"),
                   key=lambda s: s["start"])
    if len(runs) > min(len(lanes), len(spans)) or any(
            spans[k]["args"]["B"] != len(lanes[k]) for k in range(len(runs))):
        return None
    least = roofline.least_bytes(run.cfg, lanes[:len(runs)])
    measured = sum(e - s for s, e in runs) / 1e9
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] / measured
