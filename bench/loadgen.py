"""Closed-loop load generator, run as a child process that never imports JAX.

Reads one JSON document on standard input::

    {"url": ..., "seconds": S, "drain_s": D, "deadline_s": X,
     "config": {...}, "mix": {...}, "seed": N}

and starts one thread per client of the mix.  Each client is a replanning
controller: from the common start (client ``c`` a few milliseconds after
it) it sends its next request (``traffic.request``) through ``PlanClient``,
waits for the plan, and sends the next one at once, until ``S`` seconds
after the common start.  Requests in
flight at the close are waited for up to ``D`` seconds more.  It prints
``start`` when the window opens and ``closed`` when it closes, each on a line
of its own, and last one JSON document: one record per request sent, times
in seconds from the window's start on this process's clock, and for the
requests ``traffic.checked`` marks the served plan in full.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import traffic  # noqa: E402

STAGGER_S = 0.005


def _record(art) -> dict:
    return {
        "status": art.status,
        "backend": art.backend,
        "cache_hit": bool(art.cache_hit),
        "rescued": any(e.get("kind") == "serial-rescue" for e in art.events),
    }


def _full(art) -> dict:
    telem = art.telemetry or {}
    lp = telem.get("lp", {})
    stages = telem.get("stages", {})
    return {
        "gamma": [[float(v) for v in row] for row in art.gamma],
        "makespan": float(art.makespan),
        "problem": art.to_dict()["problem"],
        "bucket_B": telem.get("bucket", {}).get("B"),
        "pivots": [lp.get("pivots_phase1"), lp.get("pivots_phase2")],
        # identical for every lane of one engine bucket
        "bucket_id": [stages.get("lp_build_s"), stages.get("simplex_s")],
    }


def main() -> int:
    from repro.api.artifact import problem_from_dict
    from repro.serve.client import PlanClient

    job = json.load(sys.stdin)
    cfg, mix, seed = job["config"], job["mix"], job["seed"]
    seconds, drain = job["seconds"], job["drain_s"]
    records: list = []
    lock = threading.Lock()
    late: list = []  # seconds from one reply to the client's next send
    t0 = time.perf_counter() + 0.05
    t_end = t0 + seconds

    def client(c: int) -> None:
        http = PlanClient(job["url"], timeout_s=seconds + drain)
        # the clients start STAGGER_S apart: sixteen connections opened in
        # the same instant overflow the HTTP server's listen backlog of 5
        time.sleep(max(0.0, t0 + c * STAGGER_S - time.perf_counter()))
        last = None
        k = 0
        while True:
            problem = problem_from_dict(dict(
                traffic.request(cfg, seed, c, k), w_per_load=None))
            keep = traffic.checked(mix, seed, c, k)
            t_send = time.perf_counter()
            if t_send >= t_end:
                return
            if last is not None:
                late.append(t_send - last)
            rec = {"client": c, "k": k, "t_send": t_send - t0}
            with lock:
                records.append(rec)
            try:
                art = http.plan(problem, None, job["deadline_s"])
            except Exception as e:  # a failed request is a result, not a crash
                done = {"error": f"{type(e).__name__}: {e}"}
            else:
                done = _record(art)
                if keep:
                    done["plan"] = _full(art)
            last = time.perf_counter()
            done["t_done"] = last - t0
            with lock:
                rec.update(done)
            k += 1

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(mix["clients"])]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t0 - time.perf_counter()))
    print("start", flush=True)
    time.sleep(max(0.0, t_end - time.perf_counter()))
    print("closed", flush=True)
    for t in threads:
        t.join(timeout=max(0.0, t_end + drain - time.perf_counter()))
    with lock:
        out = {"records": [dict(r) for r in records],
               "unfinished": sum(t.is_alive() for t in threads),
               "drain_s": time.perf_counter() - t_end,
               "late_s": {"n": len(late), "max": max(late, default=0.0),
                          "mean": sum(late) / len(late) if late else 0.0}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
