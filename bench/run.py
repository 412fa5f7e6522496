#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It starts the planning service
(``PlanServer``: one worker, the batched engine, HTTP on an ephemeral port),
warms the programs the cell's traffic will run, and then a child process
that never imports JAX (``loadgen.py``) drives the cell's traffic over HTTP
for ``--seconds``.  With ``--trace 0`` it prints the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the program's span
tracer, its first ``TRACE_S`` seconds under the profiler too, and it prints
the cell's per-layer metrics.  Either
way every plan due in the window (or a sample of them drawn from the seed)
is compared with the plain reference once the window has closed, and the
numbers compared are printed beside their limits.

The cell, its configuration and its traffic mix come from ``BENCHMARK.json``
and the files it names: ``configs/<config>.json``, ``traffic/<mix>.json``
and one reader ``metrics/<metric>.py`` per per-layer metric.  The last line
of standard output is the JSON result.  With no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from concurrent.futures import ProcessPoolExecutor  # noqa: E402
import multiprocessing  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import traffic  # noqa: E402

DRAIN_S = 60.0  # how long answers due in the window are waited for
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# the profiler records the first TRACE_S seconds of the window: its device
# trace holds every operation of the simplex's loop, and past a few seconds
# the profiler drops the rest to keep the trace under 2 GB
TRACE_S = 2.0
SOLVE_PROGRAM = "jit__solve_batch"  # one execution per engine bucket


def log(msg: str) -> None:
    print(msg, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(man: dict, name: str) -> dict:
    for cell in man["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def chips(n: int) -> list:
    """The devices the cell runs on; raises :class:`NoChip` without a TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def peaks_for(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise RuntimeError(f"no peaks for device kind {kind!r} in peaks.json")
    return table["devices"][kind]


def to_problem(p: dict):
    from repro.api import Problem

    return Problem(**p)


def warm_up(cfg: dict, mix: dict) -> dict:
    """Make every program the cell's traffic runs ready, one batch size at a
    time (each size is its own program): the simplex and its certifying
    replay.  The simplex is driven with an all-zero LP of the real shape,
    which is optimal at once, so warming costs loading and no pivots.
    Returns the seconds each batch size took."""
    import numpy as np

    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket
    from repro.engine.batched_sim import simulate_bucket
    from repro.engine.batched_simplex import solve_simplex_batched

    rng = np.random.default_rng(0)
    clients = mix["clients"]
    insts = [to_problem(traffic.draw_problem(rng, cfg, cfg["comm_to_comp"][0]))
             .to_instance(1) for _ in range(clients)]
    loads = {}
    for B in range(1, clients + 1):
        t0 = time.perf_counter()
        (bucket,) = InstanceArena(insts[:B], pad_shapes=False).buckets
        lp = build_lp_bucket(bucket)
        solve_simplex_batched(
            np.tile(lp.c, (B, 1)), np.zeros_like(lp.A_ub),
            np.zeros_like(lp.b_ub), np.zeros_like(lp.A_eq),
            np.zeros_like(lp.b_eq))
        simulate_bucket(bucket, np.zeros((B, bucket.m, bucket.T)))
        loads[B] = time.perf_counter() - t0
    return loads


def registry_snapshot() -> dict:
    from repro.obs import metrics as obs_metrics

    return obs_metrics.get_registry().snapshot()


def counter(snap: dict, name: str) -> float:
    """Sum over the label sets of one counter in a registry snapshot."""
    return sum(v for k, v in snap.items() if k.partition("{")[0] == name)


def drive(url: str, cfg: dict, mix: dict, seed: int, seconds: float,
          on_start, on_closed) -> dict:
    """Run the load generator child over the window; returns its output."""
    job = {"url": url, "seconds": seconds, "drain_s": DRAIN_S,
           "deadline_s": seconds + DRAIN_S, "config": cfg, "mix": mix,
           "seed": seed}
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    try:
        child.stdin.write(json.dumps(job))
        child.stdin.close()
        out = None
        for line in child.stdout:
            line = line.strip()
            if line == "start":
                on_start()
            elif line == "closed":
                on_closed()
            elif line:
                out = json.loads(line)
        if child.wait() != 0 or out is None:
            raise RuntimeError(f"load generator failed (exit {child.returncode})")
        return out
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()


def end_to_end(out: dict, seconds: float) -> dict:
    recs = out["records"]
    end = seconds + out["drain_s"]
    ok = [r for r in recs if r.get("status") == "optimal"]
    done = [r for r in ok if r["t_done"] <= seconds]
    lat = [(r.get("t_done", end) - r["t_send"]) * 1e3 for r in recs]
    q = statistics.quantiles(lat, n=100, method="inclusive") if len(lat) > 1 \
        else lat * 99
    return {
        "plans_per_s": {"value": len(done) / seconds, "unit": "plans/s"},
        "latency_p50_ms": {"value": q[49], "unit": "ms"},
        "latency_p95_ms": {"value": q[94], "unit": "ms"},
        "device_plan_share": {
            "value": 100.0 * sum(not r["rescued"] for r in ok) / max(len(ok), 1),
            "unit": "%"},
    }


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(man: dict, cell: dict, run) -> dict:
    """Every per-layer metric this cell lists, from its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in man["per_layer"]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        value = _reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def reference_check(cfg: dict, seed: int, out: dict, limits: dict) -> tuple:
    """The comparison, with the reference LPs solved in a few processes."""
    problems = []
    for rec in out["records"]:
        rec["problem"] = len(problems)
        problems.append(traffic.request(cfg, seed, rec["client"], rec["k"]))
    need = [r["problem"] for r in out["records"] if r.get("plan")]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(max_workers=min(8, os.cpu_count() or 1),
                             mp_context=ctx) as pool:
        optimum = dict(zip(need, pool.map(
            check.optimum, [problems[i] for i in need], chunksize=32)))
    return check.compare(problems, out["records"], limits, optimum)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    # program-level device events only: per-operation events of the
    # simplex's loop overflow the profiler's buffers within seconds
    opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
    return opts


def run(args, require_chip=chips) -> int:
    man = manifest()
    cell = find_cell(man, args.workload)
    cfg = traffic.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    devices = require_chip(cell["chips"])
    t_chip = time.perf_counter() - T_START
    import jax

    from compileclock import CompileClock

    clock = CompileClock()
    peaks = peaks_for(devices[0].device_kind)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.api import Policy
    from repro.obs import trace as obs_trace
    from repro.serve import PlanServer

    server = PlanServer(policy=Policy(backend="batched"), workers=1, port=0,
                        default_deadline_s=None)
    marks: dict = {}
    tracer = None
    stopper: list = []
    stop_lock = threading.Lock()

    def stop_profile():
        with stop_lock:
            if "trace_end" not in marks:
                marks["trace_end"] = time.perf_counter()
                jax.profiler.stop_trace()

    try:
        loads = warm_up(cfg, mix)
        log(f"setup: tpu_start_s={t_chip:.3f} program_loads_s="
            + json.dumps({b: round(s, 3) for b, s in loads.items()})
            + f" compile_s={clock.seconds:.3f}")
        if args.trace:
            # the profiler and the span tracer start together before the
            # window opens; both clocks count from ``trace0``
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            tracer = obs_trace.Tracer("bench")
            jax.profiler.start_trace(TRACE_DIR,
                                     profiler_options=_profile_options())
            obs_trace.activate(tracer)
            tracer.clear()
            marks["trace0"] = time.perf_counter()

        def on_start():
            marks["start"] = time.perf_counter()
            marks["snap0"] = registry_snapshot()
            if tracer is not None:
                stopper.append(threading.Timer(min(TRACE_S, args.seconds),
                                               stop_profile))
                stopper[0].start()

        def on_closed():
            marks["closed"] = time.perf_counter()

        out = drive(f"http://localhost:{server.port}", cfg, mix, args.seed,
                    args.seconds, on_start, on_closed)
        marks["drained"] = time.perf_counter()
        if tracer is not None:
            stopper[0].join()
            obs_trace.activate(None)
        marks["snap1"] = registry_snapshot()
    finally:
        for timer in stopper:
            timer.cancel()
        if tracer is not None:
            stop_profile()
        server.close()
    setup_s = marks["start"] - T_START
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"loadgen: sent={len(out['records'])} unfinished={out['unfinished']} "
        f"drain_s={out['drain_s']:.3f} late_s={json.dumps(out['late_s'])}")

    extra: dict = {}
    if args.trace:
        import devtrace

        modules = devtrace.load_modules(TRACE_DIR, len(devices))
        trace_bytes = sum(os.path.getsize(os.path.join(d, f))
                          for d, _, files in os.walk(TRACE_DIR) for f in files)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host spans and marks count from ``trace0``, device events from
        # the profiler's own start inside ``start_trace``; the k-th simplex
        # execution starts just after the k-th simplex span opens, which
        # gives the shift from the one clock to the other
        events = tracer.events()
        shift = devtrace.clock_shift(
            modules[0], SOLVE_PROGRAM,
            [e["ts_us"] * 1e3 for e in events if e["name"] == "engine.simplex"])
        spans = [dict(e, start=e["ts_us"] * 1e3 + shift,
                      end=(e["ts_us"] + e["dur_us"]) * 1e3 + shift)
                 for e in events]
        lo_ns, hi_ns = ((marks[k] - marks["trace0"]) * 1e9 + shift
                        for k in ("start", "trace_end"))
        # the trace holds the stretch whole while it has a simplex execution
        # for every simplex span that closed well before the profiler stopped
        closed = sum(sp["name"] == "engine.simplex" and sp["end"] < hi_ns - 2e8
                     for sp in spans)
        whole = max(devtrace.held_until(modules[0], SOLVE_PROGRAM, closed,
                                        hi_ns), lo_ns)
        log(f"trace: programs={json.dumps([len(m) for m in modules])} "
            f"window_ns=[{lo_ns:.0f}, {hi_ns:.0f}] held_until_ns={whole:.0f} "
            f"shift_ns={shift:.0f} bytes={trace_bytes}")
        reduced = devtrace.reduce(modules, lo_ns, whole)
        snap0, snap1 = marks["snap0"], marks["snap1"]
        run_view = types.SimpleNamespace(
            cfg=cfg, mix=mix, seconds=args.seconds, records=out["records"],
            spans=spans, trace=reduced, modules=modules,
            window_ns=(lo_ns, whole), peaks=peaks,
            counters={k: snap1.get(k, 0.0) - snap0.get(k, 0.0)
                      for k in snap1 if isinstance(snap1[k], (int, float))},
            counter=counter,
            compiles=clock.between(marks["start"], marks["drained"]))
        metrics = per_layer(man, cell, run_view)
        extra["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in reduced["programs"].items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": devtrace.label_gaps(reduced["gaps"], spans),
        }
        device = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    else:
        metrics = end_to_end(out, args.seconds)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        device = {}

    correct, numbers, counts = reference_check(cfg, args.seed, out,
                                               cfg["limits"])
    log(f"check: {json.dumps(counts)}")
    for rec in [r for r in out["records"] if r.get("status") != "optimal"][:5]:
        log(f"unanswered: client {rec['client']} request {rec['k']} sent at "
            f"{rec['t_send']:.3f} s: {rec.get('status') or rec.get('error')}")
    attempted = len(out["records"])
    failed = int(numbers["unanswered"]["value"])
    dev = devices[0]
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": dict({"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak},
                       **device),
        **extra,
        "checks": numbers,
    }
    for name, v in numbers.items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
