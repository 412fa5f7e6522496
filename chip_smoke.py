#!/usr/bin/env python3
"""Chip smoke test: the planning service's served path, end to end on a TPU.

    python chip_smoke.py            # one chip: the served path
    python chip_smoke.py --chips 4  # four chips: the sharded fan-out only

One chip.  A ``PlanServer`` running the batched engine (one worker) listens
on an ephemeral HTTP port, and a ``PlanClient`` sends it

* population A: 16 chains at the paper's Table-2 protocol (m=10,
  heterogeneous powers, latencies, 50 loads, q=1), each with a
  comm-to-comp ratio drawn from the protocol's grid (``CCRS_FULL``) —
  one engine bucket of about 1 GB of float64 tableau;
* population B: 16 stars with result return (ratio 0.5), m=10, 10 loads,
  ``random_instance``'s other defaults — the second program, with the
  return phase;

first cold (solved on the device), then again warm (cache hits replayed on
the device).  One gate request goes first and keeps the single worker busy
while the 32 cold requests queue, so they coalesce into one batch the way a
burst does.  Every artifact must be optimal and answer its own problem; its
makespan must agree with the host's serial HiGHS solve of the same problem
to 1e-6 relative, the device replay with the host replay to 1e-9, and the
replayed schedule must be feasible.  Every cold plan is served by
``batched``, or by the engine's own serial rescue where it could not
certify a lane — those are printed with the lane's status, ratio and pivot
count, and any other provenance event (fallback, rescue, error) fails the
run.  Every warm plan is a device replay of a cache hit.

Four chips.  Population A solved by ``solve_bulk_sharded`` over all four
devices, by the same fan-out as logical shards on one device, and by one
single-device ``solve_bulk``.  Sharded and logical shards run the same
programs and must agree to 1e-9; the single-device solve's differences
are printed beside them; all three agree with HiGHS to 1e-6.  Each
device's peak memory grew by at least its own shard's float64 LP inputs,
and a replay run from a thread on each device returns float64.

The script needs a TPU: with any other platform it exits non-zero and
prints no result.  The last line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N = 16  # requests per population: one engine bucket each
DEADLINE_S = 900.0  # a cold request pays its bucket's XLA compilation
REL_TOL = 1e-6  # served makespan vs the host's HiGHS solve
REPLAY_TOL = 1e-9  # device replay vs host replay; sharded vs single-device


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, from its own
    monitoring events (any thread)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration


def populations(seed: int, n: int = N, loads_a: int = 50, loads_b: int = 10):
    """(population A, population B, gate, A's comm-to-comp ratios), all
    from ``seed``; the ``Problem``s are made in that order.

    Population A's chains and the gate draw their comm-to-comp ratio
    (bytes/FLOP) from the Table-2 grid; the gate is one more chain of
    population A's kind, not part of A."""
    from benchmarks.bench_table2 import CCRS_FULL
    from repro.api import Problem
    from repro.core.instance import random_instance

    rng = np.random.default_rng(seed)

    def draw(topology, n_loads, return_ratio, **kw):
        return Problem.from_instance(random_instance(
            rng, m=10, n_loads=n_loads, heterogeneous=True, with_latency=True,
            topology=topology, return_ratio=return_ratio, **kw))

    ccrs = [float(rng.choice(CCRS_FULL)) for _ in range(n)]
    pop_a = [draw("chain", loads_a, 0.0, comm_to_comp=r) for r in ccrs]
    pop_b = [draw("star", loads_b, 0.5) for _ in range(n)]
    gate = draw("chain", loads_a, 0.0,
                comm_to_comp=float(rng.choice(CCRS_FULL)))
    return pop_a, pop_b, gate, ccrs


def lp_bytes(instances) -> tuple:
    """float64 bytes of (the simplex tableaux, the LP inputs c/A/b) the
    engine builds and ships to the device for these instances."""
    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket

    tableau = inputs = 0
    for bucket in InstanceArena(instances).buckets:
        lp = build_lp_bucket(bucket)
        n, mu, me = lp.c.shape[0], lp.A_ub.shape[1], lp.A_eq.shape[1]
        tableau += bucket.B * (mu + me + 1) * (n + mu + 2) * 8
        inputs += bucket.B * (n + (mu + me) * (n + 1)) * 8
    return tableau, inputs


def simplex_memory(instances):
    """The compiler's ``memory_analysis`` of the batched simplex program
    the engine runs for these instances' one bucket, compiled for the
    default device (a persistent-cache hit once the bucket has run)."""
    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket
    from repro.engine.batched_simplex import _packed_lp_struct, _solve_batch
    from repro.jaxenv import x64

    (bucket,) = InstanceArena(instances).buckets
    lp = build_lp_bucket(bucket)
    n, mu, me = lp.c.shape[0], lp.A_ub.shape[1], lp.A_eq.shape[1]
    with x64():
        return _solve_batch.lower(
            _packed_lp_struct(bucket.B, n, mu, me), n, mu, me,
            20_000,  # solve_simplex_batched's default iteration cap
        ).compile().memory_analysis()


def peak_bytes(device) -> int:
    stats = device.memory_stats()
    check(stats is not None and "peak_bytes_in_use" in stats,
          f"{device} reports no peak_bytes_in_use")
    return int(stats["peak_bytes_in_use"])


def counter(snapshot: dict, name: str, **labels) -> float:
    """Sum of a metric's series whose labels include ``labels``."""
    want = {f"{k}={v}" for k, v in labels.items()}
    total = 0.0
    for key, v in snapshot.items():  # keys render as name{k=v,...}
        base, _, rest = key.partition("{")
        if base == name and want <= set(rest.rstrip("}").split(",")):
            total += v
    return total


def burst(client, problems, pool) -> tuple:
    """Send every problem at once; returns (artifacts, wall seconds)."""
    t0 = time.perf_counter()
    futs = [pool.submit(client.plan, p, None, DEADLINE_S) for p in problems]
    return [f.result() for f in futs], time.perf_counter() - t0


def wait_for(cond, what: str, timeout_s: float = 120.0) -> None:
    t_end = time.monotonic() + timeout_s
    while not cond():
        check(time.monotonic() < t_end, f"timed out waiting for {what}")
        time.sleep(0.01)


def check_artifacts(arts, problems, refs, backend: str, cache_hit: bool,
                    label: str) -> dict:
    """Hold each served artifact to the contract; returns summary numbers,
    with the engine's serial rescues listed (index, reason, pivots)."""
    from repro.core.schedule import check_feasible

    worst_ref = worst_replay = 0.0
    rescues = []
    for i, (art, ref) in enumerate(zip(arts, refs)):
        where = f"{label}[{i}]"
        check(art.status == "optimal", f"{where}: status {art.status}")
        check(art.cache_hit == cache_hit, f"{where}: cache_hit {art.cache_hit}")
        if art.events:
            # the engine could not certify its own vertex and solved this
            # one on the host instead: a correct plan, counted and reported
            (ev,) = art.events
            check(ev["kind"] == "serial-rescue", f"{where}: events {art.events}")
            lp = art.telemetry["lp"]
            rescues.append((i, ev["reason"],
                            lp["pivots_phase1"] + lp["pivots_phase2"]))
        else:
            check(art.backend == backend, f"{where}: served by {art.backend}")
        check(art.problem == problems[i], f"{where}: answered another problem")
        worst_ref = max(worst_ref, abs(art.makespan - ref) / ref)
        sched = art.schedule()  # host replay of the served fractions
        errs = check_feasible(sched)
        check(not errs, f"{where}: replay infeasible: {errs[:3]}")
        worst_replay = max(worst_replay,
                           abs(art.makespan - sched.makespan) / sched.makespan)
    check(worst_ref <= REL_TOL,
          f"{label}: makespan vs HiGHS {worst_ref:.3e} > {REL_TOL}")
    check(worst_replay <= REPLAY_TOL,
          f"{label}: device vs host replay {worst_replay:.3e} > {REPLAY_TOL}")
    return {"vs_highs": worst_ref, "vs_host_replay": worst_replay,
            "rescues": rescues}


def float64_in_thread(instances, gammas, device=None) -> str:
    """The engine's replay run from a fresh thread (as a server worker or a
    shard runs it, on ``device`` when given): its device outputs must come
    back float64."""
    import contextlib

    import jax

    from repro.engine.arena import InstanceArena
    from repro.engine.batched_sim import simulate_bucket

    out: dict = {}

    def run():
        bucket = InstanceArena(instances).buckets[0]
        with (jax.default_device(device) if device is not None
              else contextlib.nullcontext()):
            res = simulate_bucket(bucket, bucket.gamma_padded(gammas))
        out["dtypes"] = {str(a.dtype) for a in res if a is not None}

    t = threading.Thread(target=run)
    t.start()
    t.join(timeout=DEADLINE_S)
    check(not t.is_alive(), "replay thread did not finish")
    check(out.get("dtypes") == {"float64"}, f"thread outputs {out.get('dtypes')}")
    return "float64"


def serve_phase(device, seed: int = 0, n: int = N, loads_a: int = 50,
                loads_b: int = 10) -> None:
    """The one-chip path: cold and warm populations through the HTTP front."""
    from repro.api import Policy, Session
    from repro.obs import metrics as obs_metrics
    from repro.serve import PlanClient, PlanServer

    pop_a, pop_b, gate, ccrs = populations(seed, n, loads_a, loads_b)
    problems = pop_a + pop_b
    bytes_a, inputs_a = lp_bytes([p.to_instance() for p in pop_a])
    bytes_b = lp_bytes([p.to_instance() for p in pop_b])[0]
    log(f"population A: {n} chains m=10 loads={loads_a} q=1, comm-to-comp "
        f"{ccrs}, tableau bytes {bytes_a}")
    log(f"population B: {n} stars m=10 loads={loads_b} return_ratio=0.5, "
        f"tableau bytes {bytes_b}")

    t0 = time.perf_counter()
    refs = [a.makespan for a in
            Session(policy=Policy(backend="auto")).solve_bulk(problems)]
    log(f"host reference (serial auto -> HiGHS): {len(refs)} solves in "
        f"{time.perf_counter() - t0:.3f} s")

    clock = CompileClock()
    met = obs_metrics.get_registry()
    with PlanServer(policy=Policy(backend="batched"), workers=1, port=0,
                    default_deadline_s=DEADLINE_S) as server, \
            ThreadPoolExecutor(max_workers=len(problems) + 2) as pool:
        client = PlanClient(f"http://localhost:{server.port}",
                            timeout_s=DEADLINE_S + 60)
        log(f"plan server on port {server.port}: backend batched, 1 worker")

        # gate: occupies the worker so the cold burst queues up behind it
        c0, t0 = clock.seconds, time.perf_counter()
        gate_f = pool.submit(client.plan, gate, None, DEADLINE_S)
        wait_for(lambda: met.value("repro_serve_admitted_total") >= 1
                 and server.healthz()["queue_depth"] == 0, "the gate's dequeue")
        cold_f = pool.submit(burst, client, problems, pool)
        wait_for(lambda: server.healthz()["queue_depth"] == len(problems)
                 or gate_f.done(), "the cold burst's admission")
        check(not gate_f.done(), "the gate finished before the burst queued")
        gate_art = gate_f.result()
        check(gate_art.ok and all(e["kind"] == "serial-rescue"
                                  for e in gate_art.events),
              f"gate: {gate_art.status} via {gate_art.backend}")
        log(f"phase gate: 1 request, wall {time.perf_counter() - t0:.3f} s, "
            f"compile {clock.seconds - c0:.3f} s, served by {gate_art.backend}"
            + "".join(f" ({e['kind']}: {e['reason']})" for e in gate_art.events))

        c0 = clock.seconds
        cold, cold_s = cold_f.result()
        sizes = sorted({a.telemetry["bucket"]["B"] for a in cold})
        check(sizes == [n], f"cold requests solved in buckets of {sizes}, not {n}")
        cold_sum = check_artifacts(cold, problems, refs, "batched", False, "cold")
        pivots = [a.telemetry["lp"]["pivots_phase1"]
                  + a.telemetry["lp"]["pivots_phase2"] for a in cold]
        log(f"phase cold: {len(cold)} requests over HTTP, wall {cold_s:.3f} s "
            f"(compile {clock.seconds - c0:.3f} s), buckets of {sizes[0]}, "
            f"pivots per LP A {min(pivots[:n])}..{max(pivots[:n])} "
            f"B {min(pivots[n:])}..{max(pivots[n:])}, "
            f"max rel makespan vs HiGHS {cold_sum['vs_highs']:.3e}, "
            f"device vs host replay {cold_sum['vs_host_replay']:.3e}")
        rescues = cold_sum["rescues"]
        log(f"serial rescues: {len(rescues)} of {len(cold)} cold requests"
            + "".join(f"; #{i} ({'chain ccr ' + str(ccrs[i]) if i < n else 'star'})"
                      f" {reason} after {piv} pivots"
                      for i, reason, piv in rescues))
        peak = peak_bytes(device)

        c0 = clock.seconds
        warm, warm_s = burst(client, problems, pool)
        warm_sum = check_artifacts(warm, problems, refs, "batched+cache", True,
                                   "warm")
        log(f"phase warm: {len(warm)} requests over HTTP, wall {warm_s:.3f} s "
            f"(compile {clock.seconds - c0:.3f} s), all cache hits replayed, "
            f"max rel makespan vs HiGHS {warm_sum['vs_highs']:.3e}, "
            f"device vs host replay {warm_sum['vs_host_replay']:.3e}")

    dtype = float64_in_thread([p.to_instance() for p in pop_b],
                              [a.gamma for a in warm[n:]])
    snap = met.snapshot()
    fallbacks = counter(snap, "repro_engine_fallback_total")
    events = counter(snap, "repro_session_events_total")
    log(f"metrics: pivots phase1 "
        f"{counter(snap, 'repro_simplex_pivots_total', phase='1'):.0f} "
        f"phase2 {counter(snap, 'repro_simplex_pivots_total', phase='2'):.0f}, "
        f"cache hits {counter(snap, 'repro_cache_hits_total'):.0f} "
        f"misses {counter(snap, 'repro_cache_misses_total'):.0f}, "
        f"fallbacks {fallbacks:.0f}, provenance events {events:.0f}, "
        f"requests ok {counter(snap, 'repro_serve_requests_total', status='optimal'):.0f}")
    n_rescued = len(rescues) + len(gate_art.events)
    check(fallbacks == n_rescued and events == n_rescued
          and counter(snap, "repro_session_events_total",
                      kind="serial-rescue") == n_rescued,
          "provenance events other than the reported serial rescues")
    check(not warm_sum["rescues"], "a warm request was not a device replay")
    log(f"outputs: {dtype} from a worker thread")
    ma = simplex_memory([p.to_instance() for p in pop_a])
    log(f"device peak_bytes_in_use {peak} (population A: f64 LP inputs "
        f"{inputs_a}, tableaux {bytes_a}); its simplex program compiled for "
        f"{device.device_kind}: memory_analysis arguments "
        f"{ma.argument_size_in_bytes}, temporaries {ma.temp_size_in_bytes}")
    check(peak >= inputs_a, "population A's LP never sat on the device")
    check(ma.temp_size_in_bytes >= bytes_a,
          "population A's simplex program holds less than its tableaux")


def _parity(xs, ys) -> tuple:
    """(max |gamma diff|, max rel makespan diff, lanes whose serving
    backend differs) between two result lists."""
    g = mk = 0.0
    for x, y in zip(xs, ys):
        g = max(g, float(np.max(np.abs(x.schedule.gamma - y.schedule.gamma))))
        mk = max(mk, abs(x.makespan - y.makespan) / y.makespan)
    return g, mk, [i for i, (x, y) in enumerate(zip(xs, ys))
                   if x.backend != y.backend]


def shard_phase(devices, seed: int = 0, n: int = N, loads_a: int = 50) -> None:
    """The four-chip path: the sharded fan-out over real devices, against
    the same fan-out run as logical shards on one device and against one
    single-device ``solve_bulk``.

    Sharded and logical shards run the same bucket chunks (B=4) through the
    same programs, so they must agree to 1e-9: the only difference is where
    each chunk ran.  The single-device solve runs one B=16 program instead;
    its differences are printed beside them and held to HiGHS's makespan
    (1e-6) only.  On the CPU all three agree exactly; on a TPU a B=4 and a
    B=16 program have ended on different optimal vertices of the same LP
    (PERF.md, open questions)."""
    from repro.api import Policy, Session
    from repro.engine.service import solve_bulk
    from repro.obs import metrics as obs_metrics
    from repro.serve.shard import solve_bulk_sharded

    pop_a, _, _, ccrs = populations(seed, n, loads_a)
    insts = [p.to_instance() for p in pop_a]
    per_tableau, per_inputs = lp_bytes(insts[:1])
    met = obs_metrics.get_registry()
    base = [peak_bytes(d) for d in devices]
    log(f"population A: {n} chains m=10 loads={loads_a} q=1, comm-to-comp "
        f"{ccrs}")

    t0 = time.perf_counter()
    sharded = solve_bulk_sharded(insts, devices=devices)
    log(f"sharded: {n} chains over {len(devices)} devices in "
        f"{time.perf_counter() - t0:.3f} s")
    peaks = [peak_bytes(d) for d in devices]
    snap = met.snapshot()
    elems = [counter(snap, "repro_serve_shard_elements_total", shard=str(i))
             for i in range(len(devices))]
    for i, d in enumerate(devices):
        dtype = float64_in_thread(insts[:1], [sharded[0].schedule.gamma], d)
        log(f"  shard {i} on {d}: {elems[i]:.0f} LPs, peak_bytes_in_use "
            f"{base[i]} -> {peaks[i]} (its f64 LP inputs "
            f"{elems[i] * per_inputs:.0f}, tableaux {elems[i] * per_tableau:.0f})"
            f", replay outputs {dtype} from a thread on it")

    t0 = time.perf_counter()
    logical = solve_bulk_sharded(insts, n_shards=len(devices))
    log(f"logical shards on {devices[0]}: {n} chains in "
        f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    single = solve_bulk(insts)
    log(f"single-device solve_bulk on {devices[0]}: {n} chains in "
        f"{time.perf_counter() - t0:.3f} s")
    refs = [a.makespan for a in
            Session(policy=Policy(backend="auto")).solve_bulk(pop_a)]
    worst_ref = max(abs(r.makespan - ref) / ref
                    for rs in (sharded, single) for r, ref in zip(rs, refs))
    for name, res in (("sharded", sharded), ("single-device", single)):
        rescued = [i for i, r in enumerate(res) if r.backend != "batched"]
        log(f"{name}: serial rescues {len(rescued)} of {n} {rescued}")
    g_log, mk_log, be_log = _parity(sharded, logical)
    g_one, mk_one, be_one = _parity(sharded, single)
    log(f"parity sharded vs logical shards: max |gamma diff| {g_log:.3e}, "
        f"max rel makespan diff {mk_log:.3e}, lanes served differently "
        f"{be_log}")
    log(f"parity sharded vs single-device: max |gamma diff| {g_one:.3e}, "
        f"max rel makespan diff {mk_one:.3e}, lanes served differently "
        f"{be_one}")
    log(f"max rel makespan vs HiGHS (sharded, single-device) {worst_ref:.3e}")

    for i, d in enumerate(devices):
        check(elems[i] > 0, f"shard {i} got no work")
        check(peaks[i] - base[i] >= elems[i] * per_inputs,
              f"{d} never held shard {i}'s LP inputs")
    for s, r, o in zip(sharded, logical, single):
        check(s.ok and r.ok and o.ok, f"status {s.status}/{r.status}/{o.status}")
    check(g_log <= REPLAY_TOL and mk_log <= REPLAY_TOL and not be_log,
          "sharded and logical-shard results disagree")
    check(worst_ref <= REL_TOL, "makespans disagree with HiGHS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path; 4: the sharded fan-out only")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    from repro.jaxenv import use_compile_cache

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        shard_phase(devices[:4])
    else:
        serve_phase(devices[0])
    log(f"total wall {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
