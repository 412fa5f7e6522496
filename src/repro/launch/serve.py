"""Serving driver: batched prefill + token-by-token decode.

The multi-load analogue for inference: N request batches are the paper's N
divisible loads; the DLT planner decides how many requests of each batch each
chain stage serves and in how many installments (``--plan`` prints that
schedule next to its simulated makespan; examples/serve_multiload.py goes
deeper).  The decode loop itself runs the same ``serve_step`` the dry-run
lowers for the decode_* shape cells.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \\
      --batch 4 --prompt-len 32 --gen-len 16

``--serve`` switches to the long-lived planning service instead (no model
stack): a :class:`repro.serve.PlanServer` — worker Sessions behind a
bounded admission queue, an optional persistent plan store shared across
restarts/replicas, ``/healthz`` + ``/metrics``, graceful drain on SIGINT::

  PYTHONPATH=src python -m repro.launch.serve --serve --serve-port 8080 \\
      --serve-store /tmp/plans.sqlite --serve-workers 4
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ShardingPolicy, get_arch, smoke_variant
from repro.core.planner import BatchSpec, LinkSpec, Planner, StageSpec
from repro.data import make_batch
from repro.jaxenv import use_compile_cache
from repro.models import decode_flops_per_token, init_params, prefill
from repro.runtime import make_serve_step
from repro.launch.mesh import HW


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="model architecture for the decode demo "
                         "(required unless --serve)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    # BooleanOptionalAction gives the --no-greedy negation; the historical
    # `action="store_true", default=True` made the flag impossible to turn off
    ap.add_argument("--greedy", action=argparse.BooleanOptionalAction, default=True,
                    help="greedy (argmax) decoding; --no-greedy samples from "
                         "the softmax with --temperature")
    ap.add_argument("--temperature", type=float, default=1.0,
                    help="softmax temperature for --no-greedy sampling")
    ap.add_argument("--plan", type=int, default=0,
                    help="also DLT-plan N request batches over a 4-stage platform")
    ap.add_argument("--plan-backend", default="batched",
                    help="solver-backend registry entry for --plan and for "
                         "the --serve plan server's policy (see "
                         "repro.core.available_backends())")
    ap.add_argument("--topology", default="chain", choices=("chain", "star"),
                    help="platform family for --plan: the paper's linear "
                         "chain, or a one-port master star (stage 0 holds "
                         "the data, every other stage on its own link)")
    ap.add_argument("--return-ratio", type=float, default=0.0,
                    help="result bytes returned to the source per input "
                         "byte (>0 adds the result-return phase to the plan)")
    ap.add_argument("--auto-t", type=int, default=0, metavar="T_MAX",
                    help="with --plan: sweep 1..T_MAX installments through "
                         "the engine and report the cost-aware T*")
    ap.add_argument("--installment-cost", type=float, default=1e-3,
                    help="fixed per-installment overhead (seconds) charged "
                         "by the --auto-t sweep")
    ap.add_argument("--serve", action="store_true",
                    help="run the long-lived planning service "
                         "(repro.serve.PlanServer) instead of the decode demo")
    ap.add_argument("--serve-port", type=int, default=0, metavar="PORT",
                    help="HTTP port for --serve (0 = ephemeral, printed)")
    ap.add_argument("--serve-workers", type=int, default=2,
                    help="worker Sessions behind the admission queue")
    ap.add_argument("--serve-store", default=None, metavar="PATH",
                    help="persistent plan store (sqlite file) shared across "
                         "restarts and sibling replicas; default in-memory")
    ap.add_argument("--serve-queue-limit", type=int, default=256,
                    help="bounded admission queue depth (backpressure: a "
                         "full queue rejects with HTTP 429)")
    ap.add_argument("--serve-deadline", type=float, default=30.0,
                    help="default per-request deadline (seconds)")
    ap.add_argument("--serve-shards", type=int, default=None, metavar="N",
                    help="fan engine buckets out over N shards per solve "
                         "(default: single-device)")
    ap.add_argument("--serve-duration", type=float, default=None,
                    metavar="SECONDS",
                    help="with --serve: drain and exit after this long "
                         "(default: run until SIGINT)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record spans for the whole run (serve + planning) "
                         "and write Chrome trace-event JSON to PATH — open "
                         "in chrome://tracing or Perfetto (DESIGN.md §8)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the process metrics registry as Prometheus "
                         "text on http://localhost:PORT/metrics for the "
                         "duration of the run")
    args = ap.parse_args(argv)
    if not args.serve and args.arch is None:
        ap.error("--arch is required (unless running --serve)")
    use_compile_cache()

    # observability surfaces (repro.obs): both are no-cost when unset
    metrics_server = None
    if args.metrics_port is not None:
        from repro.obs import start_metrics_server

        metrics_server = start_metrics_server(args.metrics_port)
        # server_address reports the real port even for --metrics-port 0
        print(f"metrics: http://localhost:{metrics_server.server_address[1]}/metrics")
    tracer = prev_tracer = None
    if args.trace_out is not None:
        from repro.obs import Tracer, activate

        tracer = Tracer()
        prev_tracer = activate(tracer)
    try:
        if args.serve:
            _run_server(args)
        else:
            _run(args)
    finally:
        if tracer is not None:
            from repro.obs import activate

            activate(prev_tracer)
            tracer.save(args.trace_out)
            print(f"trace: {args.trace_out} ({len(tracer)} spans)")
        if metrics_server is not None:
            metrics_server.shutdown()


def _run_server(args):
    """The --serve mode: stand up a PlanServer and run until stopped.

    Admitted work always drains before exit (SIGINT and --serve-duration
    both go through ``PlanServer.close()``), so Ctrl-C never drops a plan.
    """
    from repro.api import Policy
    from repro.serve import PlanServer

    server = PlanServer(
        policy=Policy(backend=args.plan_backend),
        store=args.serve_store,
        workers=args.serve_workers,
        queue_limit=args.serve_queue_limit,
        default_deadline_s=args.serve_deadline,
        n_shards=args.serve_shards,
        port=args.serve_port,
    )
    print(f"plan server: http://localhost:{server.port}/v1/plan "
          f"(backend {args.plan_backend}, {args.serve_workers} workers, "
          f"queue {args.serve_queue_limit}, "
          f"store={args.serve_store or 'in-memory'})")
    print(f"  healthz: http://localhost:{server.port}/healthz   "
          f"metrics: http://localhost:{server.port}/metrics")
    try:
        if args.serve_duration is not None:
            time.sleep(args.serve_duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        print("draining...")
    finally:
        server.close()
        st = server.cache.stats()
        print(f"drained. cache: {st.get('hits', 0)} hit / "
              f"{st.get('misses', 0)} miss"
              + (f", store: {st['store']['entries']} rows persisted"
                 if "store" in st else ""))


def _run(args):

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    policy = ShardingPolicy(attention_impl="chunked", attn_chunk=min(1024, args.prompt_len))
    max_len = args.prompt_len + args.gen_len

    params = init_params(cfg, policy, seed=args.seed, dtype=jnp.float32)
    batch = make_batch(cfg, args.batch, args.prompt_len, step=0, seed=args.seed)
    toks = jnp.asarray(batch["tokens"])

    t0 = time.time()
    logits, cache, pos = prefill(
        params, cfg, policy, toks,
        jnp.asarray(batch["patches"]) if "patches" in batch else None,
        max_len=max_len,
    )
    t_prefill = time.time() - t0
    serve_step = jax.jit(make_serve_step(cfg, policy), donate_argnums=(1,))

    sample_key = jax.random.PRNGKey(args.seed + 1)

    def sample(lg, key):
        if args.greedy:
            nxt = jnp.argmax(lg[:, -1:], axis=-1)
        else:  # stochastic decoding: one categorical draw per sequence
            scaled = lg[:, -1, :] / jnp.maximum(args.temperature, 1e-6)
            nxt = jax.random.categorical(key, scaled, axis=-1)[:, None]
        if cfg.family == "audio" and nxt.ndim == 2:
            nxt = nxt[..., None].repeat(cfg.num_codebooks, -1) if nxt.shape[-1] != cfg.num_codebooks else nxt
        return nxt.astype(jnp.int32)

    out_tokens = []
    sample_key, k0 = jax.random.split(sample_key)
    nxt = sample(logits, k0)
    t1 = time.time()
    for i in range(args.gen_len):
        logits, cache = serve_step(params, cache, nxt, jnp.int32(pos + i))
        sample_key, ki = jax.random.split(sample_key)
        nxt = sample(logits, ki)
        out_tokens.append(np.asarray(nxt))
    t_decode = time.time() - t1
    n_tok = args.gen_len * args.batch
    print(f"arch={cfg.name} prefill {args.batch}x{args.prompt_len} in {t_prefill:.2f}s; "
          f"decoded {n_tok} tokens in {t_decode:.2f}s "
          f"({n_tok / max(t_decode, 1e-9):.1f} tok/s on {jax.default_backend()})")
    gen = np.concatenate(out_tokens, axis=1)
    print("sample tokens:", gen[0, :8].reshape(-1)[:8].tolist())

    if args.plan:
        # DLT multi-load plan: N request batches over a heterogeneous 4-stage
        # platform (--topology picks the chain or the one-port master star),
        # speeds scaled to the workload (a batch ~50ms/stage, transfer ~15ms)
        # so the schedule is non-trivial.  The backend comes from the solver
        # registry (--plan-backend); with the default batched engine the
        # solve itself is vmapped, and a second identical planning tick (the
        # common serving case) hits the solution cache.
        fl = decode_flops_per_token(cfg, args.prompt_len) * args.gen_len
        base_speed = fl * args.batch / 0.05
        base_bw = 4.0 * args.prompt_len * args.batch / 0.015
        stages = [StageSpec(f"pod{i}", base_speed / (1 + 0.15 * i)) for i in range(4)]
        links = [LinkSpec(base_bw, 50e-6)] * 3
        loads = [BatchSpec(num_samples=args.batch, bytes_per_sample=4.0 * args.prompt_len,
                           flops_per_sample=fl,
                           return_bytes_per_sample=args.return_ratio * 4.0 * args.prompt_len)
                 for _ in range(args.plan)]
        # one Session is the whole serving state: backend handles, solution
        # cache, and the coalescing submit queue (repro.api — DESIGN.md §7)
        from repro.api import Policy, Session

        use_engine = args.plan_backend == "batched"
        session = Session(policy=Policy(installments=2,
                                        backend=args.plan_backend))
        planner = Planner(stages, links, topology=args.topology,
                          session=session)
        plan = planner.plan(loads, q=2, backend=args.plan_backend)
        art = plan.artifact
        print(f"DLT plan for {args.plan} request batches over 4 "
              f"{args.topology} stages: makespan={plan.makespan * 1e3:.3f}ms "
              f"(backend={art.backend}, artifact v{art.version}, "
              f"{len(art.to_json())} JSON bytes)")
        for t, (n, j) in enumerate(plan.cells):
            print(f"  load {n} installment {j}: "
                  f"requests/stage={[int(x) for x in plan.samples[t]]}")
        # a replanning tick with an unchanged platform state: with an engine
        # backend this is a pure solution-cache hit, visible in the artifact
        plan2 = planner.plan(loads, q=2, backend=args.plan_backend)
        tick = (f"replan tick: makespan={plan2.makespan * 1e3:.3f}ms "
                f"cache_hit={plan2.artifact.cache_hit}")
        if use_engine:
            st = session.stats().get("cache", {})
            tick += f" cache={st.get('hits', 0)} hit / {st.get('misses', 0)} miss"
        print(tick)
        if args.auto_t:
            # cost-aware installment chooser: one bulk sweep up the q ladder
            res = planner.plan_auto_T(
                loads, t_max=args.auto_t,
                installment_cost=args.installment_cost,
                backend=args.plan_backend,
            )
            swept = ", ".join(
                f"q={q}: {res.makespans[q] * 1e3:.3f}ms"
                f"+{(res.costs[q] - res.makespans[q]) * 1e3:.3f}ms"
                for q in sorted(res.makespans)
            )
            print(f"auto-T sweep (installment cost "
                  f"{args.installment_cost * 1e3:.3f}ms): {swept}")
            print(f"  -> T* = {res.t_star} installments/load, "
                  f"cost-aware makespan {res.costs[res.t_star] * 1e3:.3f}ms")


if __name__ == "__main__":
    main()
