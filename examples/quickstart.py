"""Quickstart: the paper's contribution through the one front door.

Everything routes through ``repro.api`` — a declarative (Problem, Policy)
pair handed to a Session (DESIGN.md §7):

1. The §3 motivating instance (2 processors, 2 loads, lambda=3/4) solved
   optimally with the Fig. 6 LP on several backends, vs the heuristics it
   supersedes.
2. A STAR platform with a result-return phase through the exact same
   session — plus the versioned PlanArtifact: JSON out, JSON in, replayed
   bit-identically (ship plans between processes).
3. Serving-style traffic: async ``submit()`` tickets coalescing into
   micro-batched engine solves.
4. The same LP scheduling real training batches on a heterogeneous chain,
   with the cost-aware Theorem-1 auto-T* chooser stated as Policy, and one
   training step per plan cell on CPU.

Migration (old call -> new call):

  =====================================  =====================================
  historical entry point                 repro.api front door
  =====================================  =====================================
  solve(inst, backend="b")               session.solve(problem, Policy(
                                             installments=q, backend="b"))
  solve_batch(insts)                     session.solve_bulk(problems)
  Planner.plan(batches, q, backend)      planner.plan(...) (unchanged shim) or
                                         session.solve(planner.to_problem(b),
                                             Policy(installments=q, ...))
  Planner.plan_auto_T(b, t_max, cost)    session.solve(problem, Policy(
                                             auto_t=True, t_max=...,
                                             installment_cost=...))
  PlanService().submit/flush/result      session.submit(...) -> ticket;
                                         ticket.result() / session.flush()
  LPResult / SolveReport                 PlanArtifact (versioned, JSON
                                         round-trippable, with provenance)
  =====================================  =====================================

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp
import numpy as np

from repro.api import PlanArtifact, Policy, Problem, Session
from repro.config import ShardingPolicy, TrainConfig, get_arch, smoke_variant
from repro.core import available_backends
from repro.core.closed_form import example_instance, star_single_load_makespan
from repro.core.heuristics import multi_inst, simple, single_inst
from repro.core.planner import BatchSpec, LinkSpec, Planner, StageSpec
from repro.data import batch_load_spec, make_batch
from repro.models import init_params
from repro.runtime import make_train_state, make_train_step

# one session owns the backend handles, the solution cache, and the submit
# queue — every solve below goes through it
session = Session()

# ------------------------------------------------------------------- 1
print("=== the paper's example: 2 identical processors, lambda = 3/4 ===")
paper = Problem.from_instance(example_instance(0.75))
art = session.solve(paper, Policy(installments=2))  # Fig. 6, Q=2 installments
print(f"LP (Fig. 6, Q=2 installments): makespan = {art.makespan:.6f}"
      f"  (paper's hand schedule: 781/653 * 3/4 = {781 / 653 * 0.75:.6f})")

print(f"registered solver backends: {available_backends()}")
for backend in ("simplex", "batched"):
    a = session.solve(paper, Policy(installments=2, backend=backend))
    print(f"{backend:>8} backend agrees: makespan = {a.makespan:.6f} "
          f"(served by {a.backend}, status={a.status})")
for name, fn in [("SIMPLE", simple), ("SINGLEINST", single_inst),
                 ("MULTIINST", lambda i: multi_inst(i, cap=300))]:
    r = fn(example_instance(0.75))
    print(f"{name:>10}: makespan = {r.makespan:.6f}"
          + ("  (FAILED)" if r.failed else ""))
print("gamma (fraction of each load per processor per installment):")
print(np.array_str(art.gamma, precision=4, suppress_small=True))

# ------------------------------------------------------------------- 2
print("\n=== a star platform with result return + the shippable artifact ===")
# a one-port master + 3 heterogeneous workers on a uniform-speed bus;
# return_ratio=0.25 makes every computed fraction ship a quarter of its
# input volume back to the master before the load counts as done
star = Problem(topology="star", w=[0.8, 1.2, 0.6, 1.5], z=0.3,
               v_comm=[1.0], v_comp=[1.0])
star_art = session.solve(star, Policy(backend="batched"))
cf = star_single_load_makespan(np.array(star.w), np.array(star.z), 1.0, 1.0)
print(f"star (bus) single load: LP makespan = {star_art.makespan:.6f}, "
      f"closed form = {cf:.6f} (equal on uniform links)")
ret = Problem(topology="star", w=[0.8, 1.2, 0.6, 1.5], z=0.3,
              v_comm=[1.0], v_comp=[1.0], return_ratio=0.25)
ret_art = session.solve(ret, Policy(backend="batched"))
print(f"with result return (ratio 0.25): makespan = {ret_art.makespan:.6f} "
      f"(last return arrives at {float(ret_art.schedule().ret_end.max()):.6f})")

# the artifact is the wire format: JSON out, JSON in, replay — bit-identical
wire = ret_art.to_json()
shipped = PlanArtifact.from_json(wire)
assert shipped.to_json() == wire, "artifact round-trip must be bit-identical"
print(f"artifact v{shipped.version}: {len(wire)} JSON bytes, "
      f"replayed makespan = {shipped.schedule().makespan:.6f}, "
      f"provenance: backend={shipped.backend}, cache_hit={shipped.cache_hit}")

# ------------------------------------------------------------------- 3
print("\n=== serving-style traffic: coalescing async submission ===")
rng = np.random.default_rng(0)
from repro.core.instance import random_instance
bursty = Session(policy=Policy(backend="batched"), max_batch=8)
tickets = [bursty.submit(Problem.from_instance(
    random_instance(rng, m=3, n_loads=2, q=1))) for _ in range(20)]
makespans = [t.result().makespan for t in tickets]
st = bursty.stats()
print(f"20 staggered submits -> {st['flushes']} engine flushes "
      f"(max_batch=8); mean makespan {np.mean(makespans):.3f}s")

# ---------------------------------------------------------------- 3bis
print("\n=== the flight recorder: spans + metrics (DESIGN.md §8) ===")
# session.trace() records spans for everything inside the block; the saved
# file is Chrome trace-event JSON (open in chrome://tracing or Perfetto)
burst_probs = [Problem.from_instance(
    random_instance(rng, m=3, n_loads=2, q=1)) for _ in range(8)]
with bursty.trace() as tr:
    bursty.solve_bulk(burst_probs)
stage_us = {n: tr.total_us(n) for n in
            ("engine.lp_build", "engine.simplex", "engine.replay")}
print(f"traced {len(tr)} spans over {tr.total_us('session.trace')/1e3:.1f}ms: "
      + ", ".join(f"{n.split('.')[1]} {us/1e3:.1f}ms"
                  for n, us in stage_us.items()))
# tr.save("session.trace.json")  # ship it to chrome://tracing

# re-solving the same problems hits the cache: plans re-materialize through
# the batched bucket replay (no per-instance Python, no pivots), and the
# hit artifacts say so — cache_hit + replay-stage seconds (DESIGN.md §9)
bursty.solve_bulk(burst_probs)  # first hit pass compiles the replay rung
hit = bursty.solve_bulk(burst_probs)[0]
print(f"warm re-solve: cache_hit={hit.cache_hit}, backend={hit.backend}, "
      f"bucket B={hit.telemetry['bucket']['B']} replayed in "
      f"{hit.telemetry['stages']['replay_s']*1e3:.2f}ms, "
      f"pivots={hit.telemetry['lp']['pivots_phase1']}"
      f"+{hit.telemetry['lp']['pivots_phase2']}")

# every solve also feeds the process metrics registry (one key schema for
# cache/session/engine/simplex; `serve --metrics-port` exposes it to scrapes)
from repro.obs import get_registry
snap = get_registry().snapshot()
print("metrics: "
      f"engine bulk solves = {snap.get('repro_engine_bulk_solves_total{path=batched}', 0):.0f}, "
      f"cache hits = {snap.get('repro_cache_hits_total', 0):.0f}, "
      f"phase-2 pivots = {snap.get('repro_simplex_pivots_total{path=batched,phase=2}', 0):.0f}")
# and the artifact carries its own telemetry: per-stage seconds + LP stats
tel = tickets[0].result().telemetry
if tel and "lp" in tel:
    print(f"first ticket's telemetry: bucket B={tel['bucket']['B']}, "
          f"pivots={tel['lp']['pivots_phase1']}+{tel['lp']['pivots_phase2']}, "
          f"simplex {tel['stages']['simplex_s']*1e3:.1f}ms")

# ---------------------------------------------------------------- 3ter
print("\n=== live replanning: platform events -> warm-started re-solves ===")
from repro.runtime.replan import EventStreamReplanner, SpeedObserved

# one replanner tracks one evolving problem (the star-with-returns instance
# from section 2) through its own session; each apply() folds the event,
# re-solves, and publishes to the attached subscription (DESIGN.md §11)
live = Session(policy=Policy(backend="batched"))
replanner = EventStreamReplanner(live, ret, Policy(backend="batched"))
sub = replanner.subscription  # or: live.subscribe(problem, policy)
snap = sub.next(timeout=5.0)  # first update: the initial plan snapshot
print(f"  initial plan: makespan = {snap.makespan:.6f}")
for k in range(3):
    # a worker drifts slower: coefficient-only, so the previous exit basis
    # seeds a verify-first warm entry (zero pivots when it certifies)
    replanner.apply(SpeedObserved(index=2, w=0.6 * (1.0 + 0.05 * (k + 1))))
    update = sub.next(timeout=5.0)  # long-poll the plan feed
    prov = update.events[-1]  # {"kind": "replan", ...} provenance event
    print(f"  {prov['trigger']}(w={replanner.problem.w[2]:.3f}): "
          f"makespan = {update.makespan:.6f}, warm={prov['warm']}, "
          f"pivots={prov['pivots_phase1']}+{prov['pivots_phase2']}")
replanner.close()
assert sub.next(timeout=0.1) is None, "closed feed must drain to None"

# ------------------------------------------------------------- 3quater
print("\n=== the planning service: persistent store + HTTP front door ===")
import os
import tempfile

from repro.serve import PlanClient, PlanServer

# a PlanServer is N worker Sessions behind one bounded admission queue;
# store= persists every solved plan to a sqlite file keyed by the problem's
# quantized content hash, so a RESTARTED server (or a sibling process)
# replays instead of re-solving (DESIGN.md §12)
store_path = os.path.join(tempfile.mkdtemp(prefix="quickstart_"),
                          "plans.sqlite")
serve_policy = Policy(backend="batched")
with PlanServer(store=store_path, workers=2, policy=serve_policy,
                port=0) as server:
    client = PlanClient(f"http://localhost:{server.port}")
    served = client.plan(ret)  # the star-with-returns problem over HTTP
    assert served.diff(ret_art) == {}, "served plan must match direct solve"
    print(f"served over HTTP :{server.port}: makespan = "
          f"{served.makespan:.6f} (diff()-clean vs the direct solve), "
          f"healthz = {client.healthz()['status']}")
with PlanServer(store=store_path, workers=1, policy=serve_policy) as restarted:
    warm = restarted.plan(ret)  # a fresh process over the same store file
    print(f"restarted server: cache_hit={warm.cache_hit} "
          f"(store hits = {restarted.cache.store_hits}) — the warm-restart "
          f"win bench_serve gates at >= 5x")

# ------------------------------------------------------------------- 4
print("\n=== the same LP scheduling real training batches on a chain ===")
cfg = smoke_variant(get_arch("llama3.2-3b"))
policy = ShardingPolicy(attn_chunk=16)
tcfg = TrainConfig(lr=1e-3, warmup_steps=0, total_steps=10)
B, S = 8, 32
load = batch_load_spec(cfg, B, S)

# a heterogeneous 3-stage chain scaled so one batch ~ 40ms of compute
speed = load.flops_per_sample * B / 0.04
stages = [StageSpec("pod0", speed), StageSpec("pod1", speed / 2),
          StageSpec("pod2", speed / 3)]
links = [LinkSpec(bytes_per_sec=load.bytes_per_sample * B / 0.01, startup_sec=1e-4)] * 2
planner = Planner(stages, links, session=session)
# the cost-aware Theorem-1 chooser, stated declaratively: each installment
# is charged a fixed overhead, so unlike the pure linear model T* is finite
auto = planner.plan_auto_T([load, load], t_max=4, installment_cost=2e-4,
                           backend="serial")
print("auto-T sweep (0.2ms/installment): "
      + ", ".join(f"q={q}: {auto.makespans[q] * 1e3:.2f}ms"
                  for q in sorted(auto.makespans))
      + f" -> T* = {auto.t_star}")
plan = auto.plan
print(f"planned makespan: {plan.makespan * 1e3:.2f} ms "
      f"(T* = {auto.t_star} installments/load, artifact "
      f"t_star = {plan.artifact.t_star})")
for t, (n, j) in enumerate(plan.cells):
    print(f"  load {n}, installment {j}: samples/stage = "
          f"{[int(x) for x in plan.samples[t]]}")

params = init_params(cfg, policy, seed=0, dtype=jnp.float32)
state = make_train_state(params, tcfg)
step = make_train_step(cfg, policy, tcfg)
for i in range(3):
    batch = {k: jnp.asarray(v) for k, v in make_batch(cfg, B, S, step=i).items()}
    state, metrics = step(state, batch)
    print(f"train step {i}: loss = {float(metrics['loss']):.4f}")
print("quickstart OK")
