"""Plan service: the engine's front door.

``solve_bulk`` evaluates a whole population of instances:

  1. cache lookup on the quantized-instance hash (hits replay instantly);
  2. misses are packed into exact ``(m, T, q)`` buckets (arena.py), their
     Fig.-6 LPs stacked (rows zero-padded to the bucket max — a ``0.x <= 0``
     row is inert) and solved by the batched simplex in one ``vmap``;
  3. every solved gamma batch is ASAP-replayed through the batched simulator
     (the same replay-validation contract as ``repro.core.solver.solve``);
  4. any batch element the batched path could not certify (non-optimal
     status, or replay exceeding the LP objective beyond tolerance) falls
     back to the serial NumPy solver — the engine is an accelerator, never a
     correctness compromise.

``BatchedBackend`` exposes this path through the solver-backend registry
(``repro.core.backends``; registered lazily as ``"batched"``), and
``PlanService`` wraps it in a submit/flush request queue for serving
call-sites (launch/serve.py --plan, runtime replans).
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core.backends import SolveReport, SolveRequest, SolverBackend, get_backend
from repro.core.instance import Instance
from repro.core.schedule import Schedule
from repro.core.simulator import simulate
from repro.core.solver import LPResult, solve
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span

from .arena import InstanceArena
from .batched_lp import build_lp_bucket
from .batched_sim import simulate_bucket
from .batched_simplex import STATUS, solve_simplex_batched
from .cache import CachedSolution, SolutionCache

__all__ = ["solve_bulk", "BatchedBackend", "PallasBackend", "PlanService"]

_REPLAY_TOL = 1e-6


def _result_from_gamma(
    inst: Instance, gamma: np.ndarray, lp_makespan: float, backend: str,
    sched: Schedule | None = None,
) -> LPResult:
    if sched is None:
        sched = simulate(inst, gamma)
    return LPResult(
        schedule=sched,
        lp_makespan=float(lp_makespan),
        objective_value=float(sched.makespan),
        backend=backend,
        status="optimal",
        n_vars=-1,
        n_rows=-1,
    )


def _replay_hits(instances, hit_idx, sols, results, label, use_pallas,
                 cache_s, met) -> None:
    """Re-materialize cached gammas through the batched ASAP replay.

    Hits used to call the serial ``simulate(inst, gamma)`` loop one instance
    at a time; packing them into (ladder-padded) arena buckets and replaying
    each bucket in one vmapped/Pallas ``simulate_bucket`` launch keeps a
    warm-cache ``solve_bulk`` out of per-instance Python entirely.  Every
    hit gets the full v2 telemetry shape (stages/bucket/lp + ``cache_hit``)
    so :meth:`PlanArtifact.diff` works across hit/miss pairs.
    """
    t0 = time.perf_counter()
    telem_slots: list = []  # (result index, bucket info) — timed after replay
    with span("engine.hit_replay", n=len(hit_idx)):
        arena = InstanceArena([instances[i] for i in hit_idx], pad_shapes=True)
        for bucket in arena.buckets:
            g = bucket.gamma_padded(
                [sols[hit_idx[j]].gamma for j in bucket.indices])
            cs, ce, ps, pe, rs, re, mk = simulate_bucket(
                bucket, g, use_pallas=use_pallas)
            if rs is not None:
                rs, re = bucket.unpad(rs), bucket.unpad(re)
            cs, ce = bucket.unpad(cs), bucket.unpad(ce)
            ps, pe = bucket.unpad(ps), bucket.unpad(pe)
            bucket_info = {"B": bucket.B, "topology": bucket.topology,
                           "m": bucket.m_real, "T": bucket.T_real,
                           "q": [int(x) for x in bucket.q]}
            for b in range(bucket.B):
                gi = hit_idx[bucket.indices[b]]
                sol = sols[gi]
                sched = Schedule(
                    instance=bucket.instances[b],
                    gamma=np.asarray(sol.gamma, dtype=np.float64),
                    comm_start=cs[b],
                    comm_end=ce[b],
                    comp_start=ps[b],
                    comp_end=pe[b],
                    makespan=float(mk[b]),
                    ret_start=rs[b] if rs is not None else None,
                    ret_end=re[b] if re is not None else None,
                )
                results[gi] = _result_from_gamma(
                    bucket.instances[b], sol.gamma, sol.lp_makespan,
                    label + "+cache", sched=sched,
                )
                telem_slots.append((gi, bucket_info))
    replay_s = time.perf_counter() - t0
    met.observe("repro_engine_stage_seconds", replay_s,
                stage="hit_replay", path=label)
    for gi, bucket_info in telem_slots:
        # cached solutions are only ever optimal certified gammas; their
        # pivot counts were spent (and recorded) at miss time
        results[gi].telemetry = {
            "stages": {"cache_lookup_s": cache_s, "replay_s": replay_s},
            "bucket": dict(bucket_info),
            "lp": {"pivots_phase1": 0, "pivots_phase2": 0,
                   "status": "optimal"},
            "cache_hit": True,
        }


def solve_bulk(
    instances: list,
    objective: str = "makespan",
    cache: SolutionCache | None = None,
    fallback: bool = True,
    validate: bool = True,
    use_pallas: bool = False,
    warm_starts: list | None = None,
    devices: list | None = None,
    n_shards: int | None = None,
) -> list:
    """Solve many instances at once; returns ``LPResult``s in caller order.

    Only the paper's makespan objective runs on the batched path; other
    objectives delegate to the serial solver per instance.  ``validate``
    is forwarded to the serial solver on the (rare) uncertified-element
    fallback — the batched path itself always certifies by replay.

    ``use_pallas=True`` routes the simplex pivots and the ASAP replay
    through the fused Pallas kernels (repro.kernels.simplex_pivot /
    asap_replay); results and statuses are parity-identical to the vmapped
    path, only the reported ``backend`` label changes to ``"pallas"``.

    ``warm_starts`` (optional, parallel to ``instances``) carries per-
    instance exit bases from a previous solve of a perturbed sibling; rows
    with a usable basis enter the simplex phase-2-only (replan hot path),
    everything else — ``None`` entries, shape mismatches, rejected seeds —
    solves cold, identically to omitting the argument.  The exit basis of
    every engine-solved instance rides back in
    ``result.telemetry["lp"]["final_basis"]`` for the *next* replan.

    ``devices``/``n_shards`` fan the arena buckets out across local JAX
    devices (or logical thread shards) via :mod:`repro.serve.shard` —
    deterministic assignment, parity-locked results; both ``None`` (the
    default) keeps the single-device path below.
    """
    label = "pallas" if use_pallas else "batched"
    if objective != "makespan":
        return [solve(inst, objective=objective, validate=validate) for inst in instances]
    if devices is not None or n_shards is not None:
        from repro.serve.shard import solve_bulk_sharded  # deferred: serve pkg

        return solve_bulk_sharded(
            instances, objective=objective, cache=cache, fallback=fallback,
            validate=validate, use_pallas=use_pallas, warm_starts=warm_starts,
            devices=devices, n_shards=n_shards,
        )

    met = obs_metrics.get_registry()
    met.inc("repro_engine_bulk_solves_total", path=label)
    with span("engine.solve_bulk", n=len(instances), path=label):
        n = len(instances)
        results: list = [None] * n
        t0 = time.perf_counter()
        with span("engine.cache_lookup", n=n):
            if cache is not None:
                # bulk key derivation + one batched LRU pass — the per-
                # instance quantize/hash loop was ~90% of warm-cache wall
                keys = cache.keys(instances, objective)
                sols = cache.lookup_many(keys)
            else:
                keys = [None] * n
                sols = [None] * n
            pending = [i for i, sol in enumerate(sols) if sol is None]
            hit_idx = [i for i in range(n) if sols[i] is not None]
        cache_s = time.perf_counter() - t0
        if hit_idx:
            _replay_hits(instances, hit_idx, sols, results, label,
                         use_pallas, cache_s, met)
        if not pending:
            return results

        t0 = time.perf_counter()
        with span("engine.pack", n=len(pending)):
            arena = InstanceArena([instances[i] for i in pending], pad_shapes=False)
        pack_s = time.perf_counter() - t0

        for bucket in arena.buckets:
            _solve_bucket(bucket, instances, results, keys, pending, cache,
                          label, use_pallas, fallback, validate, met,
                          {"cache_lookup_s": cache_s, "pack_s": pack_s},
                          warm_starts)
    return results


def _solve_bucket(bucket, instances, results, keys, pending, cache, label,
                  use_pallas, fallback, validate, met, shared_stages,
                  warm_starts=None) -> None:
    """Solve one packed bucket in place: LP build -> batched simplex ->
    batched ASAP replay -> certify-or-rescue, with per-stage timings and
    solver telemetry recorded on every report (DESIGN.md §8)."""
    B = bucket.B
    q_label = "-".join(str(int(x)) for x in bucket.q)
    bucket_t0 = time.perf_counter()
    with span("engine.bucket", B=B, topology=bucket.topology,
              m=bucket.m_real, T=bucket.T_real, q=q_label):
        t0 = time.perf_counter()
        with span("engine.lp_build", B=B):
            lp = build_lp_bucket(bucket)
            c = np.tile(lp.c, (B, 1))  # objective pattern is bucket-constant
        lp_build_s = time.perf_counter() - t0

        n_rows = lp.A_ub.shape[1] + lp.A_eq.shape[1]
        wb = None
        if warm_starts is not None:
            wb = bucket.basis_padded(
                [warm_starts[pending[i]] for i in bucket.indices], n_rows)

        t0 = time.perf_counter()
        with span("engine.simplex", B=B, rows=len(lp.b_ub) + len(lp.b_eq)):
            res = solve_simplex_batched(c, lp.A_ub, lp.b_ub, lp.A_eq, lp.b_eq,
                                        use_pallas=use_pallas, warm_basis=wb)
        simplex_s = time.perf_counter() - t0
        if wb is not None:
            met.inc("repro_simplex_warm_starts_total",
                    int(res.warm_started.sum()), path=label)
        met.inc("repro_simplex_pivots_total",
                int(res.iterations_phase1.sum()), phase="1", path=label)
        met.inc("repro_simplex_pivots_total",
                int(res.iterations_phase2.sum()), phase="2", path=label)
        for code, count in zip(*np.unique(res.status, return_counts=True)):
            met.inc("repro_simplex_status_total", int(count),
                    status=STATUS[int(code)], path=label)

        gammas = lp.gamma_of(res.x)
        lp_mks = lp.makespan_of(res.x)

        # replay every solved gamma through the batched ASAP simulator
        # (rs/re are None unless the bucket activates the return phase)
        t0 = time.perf_counter()
        with span("engine.replay", B=B):
            cs, ce, ps, pe, rs, re, mk = simulate_bucket(
                bucket, bucket.gamma_padded(list(gammas)), use_pallas=use_pallas)
        replay_s = time.perf_counter() - t0

        stages = dict(shared_stages, lp_build_s=lp_build_s,
                      simplex_s=simplex_s, replay_s=replay_s)
        bucket_info = {"B": B, "topology": bucket.topology,
                       "m": bucket.m_real, "T": bucket.T_real,
                       "q": [int(x) for x in bucket.q]}

        def telem(b: int, extra: dict | None = None) -> dict:
            lp_info = {
                "pivots_phase1": int(res.iterations_phase1[b]),
                "pivots_phase2": int(res.iterations_phase2[b]),
                "status": res.status_str(b),
                # warm-start provenance: whether the seed served this element,
                # and the exit basis (JSON-safe ints) the next replan may seed
                # from — the basis rides the artifact, not solver state
                "warm": bool(res.warm_started[b]) if res.warm_started is not None else False,
            }
            if res.basis is not None:
                lp_info["final_basis"] = [int(v) for v in res.basis[b]]
            out = {
                "stages": dict(stages),
                "bucket": dict(bucket_info),
                "lp": lp_info,
            }
            if extra:
                out.update(extra)
            return out

        for b in range(B):
            gi = pending[bucket.indices[b]]
            inst = bucket.instances[b]
            certified = (
                res.status[b] == 0
                and np.isfinite(lp_mks[b])
                and mk[b] <= lp_mks[b] * (1 + _REPLAY_TOL) + 1e-9
            )
            if not certified:
                if not fallback:
                    raise RuntimeError(
                        f"batched solve failed for instance {gi}: "
                        f"status={res.status_str(b)} replay={mk[b]} lp={lp_mks[b]}"
                    )
                met.inc("repro_engine_fallback_total", path=label,
                        reason=res.status_str(b))
                t0 = time.perf_counter()
                with span("engine.serial_rescue", index=gi,
                          status=res.status_str(b)):
                    results[gi] = solve(inst, objective="makespan",
                                        validate=validate)
                results[gi].telemetry = telem(b, {
                    "serial_rescue": {
                        "reason": res.status_str(b),
                        "seconds": time.perf_counter() - t0,
                        "backend": results[gi].backend,
                    },
                })
                if cache is not None and results[gi].ok:
                    cache.put(keys[gi], CachedSolution(
                        gamma=results[gi].schedule.gamma,
                        lp_makespan=results[gi].lp_makespan,
                        backend="serial",
                    ))
                continue
            sched = Schedule(
                instance=inst,
                gamma=gammas[b],
                comm_start=cs[b],
                comm_end=ce[b],
                comp_start=ps[b],
                comp_end=pe[b],
                makespan=float(mk[b]),
                ret_start=rs[b] if rs is not None else None,
                ret_end=re[b] if re is not None else None,
            )
            results[gi] = _result_from_gamma(
                inst, gammas[b], lp_mks[b], label, sched=sched
            )
            results[gi].telemetry = telem(b)
            if cache is not None:
                cache.put(keys[gi], CachedSolution(
                    gamma=gammas[b], lp_makespan=float(lp_mks[b]), backend=label
                ))
    bucket_s = time.perf_counter() - bucket_t0
    met.observe("repro_engine_bucket_solve_seconds", bucket_s,
                topology=bucket.topology, m=bucket.m_real, T=bucket.T_real,
                q=q_label, path=label)
    for stage, dt in (("lp_build", lp_build_s), ("simplex", simplex_s),
                      ("replay", replay_s)):
        met.observe("repro_engine_stage_seconds", dt, stage=stage, path=label)


class BatchedBackend(SolverBackend):
    """The engine's bulk path behind the ``SolverBackend`` registry.

    ``solve_many`` routes makespan requests through :func:`solve_bulk`
    (cache-first, bucketed, vmapped); requests the batched path cannot
    express — other objectives (whose ``weights``/``beta`` must be honored)
    or an explicit ``cross_check`` — delegate to the serial reference solver
    with their full request, so no request field is ever silently dropped.
    Reports come back in caller order with their requests attached.
    """

    name = "batched"
    use_pallas = False  # subclass hook: route through the fused Pallas kernels

    def __init__(self, cache: SolutionCache | None = None, fallback: bool = True,
                 devices: list | None = None, n_shards: int | None = None):
        super().__init__(cache=cache)
        self.fallback = fallback
        # device-sharded fan-out (repro.serve.shard): both None = single-device
        self.devices = devices
        self.n_shards = n_shards

    def stats(self) -> dict:
        """Cache stats of this backend's solution cache.

        .. deprecated:: PR 6
           A shim kept for the historical surface — the unified view is the
           metrics registry (``repro.obs.metrics.get_registry().snapshot()``,
           key schema in DESIGN.md §8).
        """
        return {
            "backend": self.name,
            "cache": self.cache.stats() if self.cache is not None else None,
        }

    @staticmethod
    def _batchable(req: SolveRequest) -> bool:
        # the batched path solves the paper's makespan objective and
        # certifies by ASAP replay; a cross_check against the *other* serial
        # backend is a serial-only contract, so honor it serially
        return req.objective == "makespan" and not req.cross_check

    def solve_many(self, requests: list) -> list:
        requests = list(requests)
        reports: list = [None] * len(requests)
        # batchable requests keep the bulk path; validate only affects the
        # rare uncertified-element fallback, so group by it
        by_validate: dict[bool, list[int]] = {}
        for i, req in enumerate(requests):
            if self._batchable(req):
                by_validate.setdefault(req.validate, []).append(i)
        for validate, bulk_idxs in by_validate.items():
            warm = [requests[i].warm_basis for i in bulk_idxs]
            results = solve_bulk(
                [requests[i].instance for i in bulk_idxs],
                objective="makespan",
                cache=self.cache,
                fallback=self.fallback,
                validate=validate,
                use_pallas=self.use_pallas,
                warm_starts=warm if any(w is not None for w in warm) else None,
                devices=self.devices,
                n_shards=self.n_shards,
            )
            for i, res in zip(bulk_idxs, results):
                reports[i] = SolveReport.from_result(res, requests[i])
        for i, req in enumerate(requests):
            if reports[i] is None:
                reports[i] = get_backend("auto").solve(req)
        return reports


class PallasBackend(BatchedBackend):
    """The batched engine with its hot loops in fused Pallas kernels.

    Same bulk path, cache semantics, certification-by-replay, and serial
    fallback contract as :class:`BatchedBackend` — the simplex pivots and
    the ASAP replay just run in ``repro.kernels.simplex_pivot`` /
    ``asap_replay`` (interpret-mode on the CPU).  Statuses and every
    :class:`SolveReport` field behave identically; ``report.backend`` says
    ``"pallas"``.  Where the kernels cannot run (probed once via
    ``scheduling_kernels_error``) construction raises with the lowering's or
    compiler's reason: selecting ``pallas`` never silently runs ``batched``.
    """

    name = "pallas"
    use_pallas = True

    def __init__(self, cache: SolutionCache | None = None, fallback: bool = True):
        super().__init__(cache=cache, fallback=fallback)
        from repro.kernels.ops import scheduling_kernels_error

        reason = scheduling_kernels_error()
        if reason is not None:
            import jax

            raise RuntimeError(
                f"the pallas backend cannot run on {jax.default_backend()}: "
                f"{reason}")


@dataclasses.dataclass
class _Ticket:
    index: int


class PlanService:
    """Batching request front-end over the batched backend.

    .. deprecated:: PR 5
       A thin shim over :class:`repro.api.Session` — the one front door
       that also coalesces by bucket size and deadline and returns
       versioned :class:`repro.api.PlanArtifact`\\ s.  New code should use a
       Session directly; this class keeps the historical submit/flush/
       result surface (reports, integer tickets, bounded retention) alive.

    Ticket lifecycle (the enforced semantics, regression-tested in
    tests/test_api_session.py): ``result()`` on a not-yet-flushed ticket
    auto-flushes first; ``flush()`` with an empty queue is an idempotent
    no-op; tickets older than the ``max_results`` retention window raise
    ``KeyError`` loudly instead of returning stale reports.
    """

    def __init__(
        self,
        cache: SolutionCache | None = None,
        objective: str = "makespan",
        max_results: int = 65536,
        backend: str = "batched",
    ):
        import warnings

        warnings.warn(
            "PlanService is deprecated: use repro.api.Session (submit/flush "
            "with coalescing, PlanArtifact results) instead",
            DeprecationWarning,
            stacklevel=2,
        )
        if backend not in ("batched", "pallas"):
            raise ValueError(
                f"PlanService fronts the engine backends ('batched', 'pallas'); got {backend!r}"
            )
        from repro.api import Policy, Session

        # explicit-flush semantics: the session never flushes on queue size
        self._session = Session(
            policy=Policy(backend=backend, objective=objective),
            cache=cache if cache is not None else SolutionCache(),
            max_batch=None,
        )
        self.objective = objective
        self.max_results = max_results
        self.backend = self._session.backend(backend)
        self._pending: list = []  # PlanTickets submitted since the last flush
        self._results: list = []
        self._base = 0  # absolute ticket index of _results[0]

    @property
    def cache(self) -> SolutionCache:
        return self._session.cache

    @property
    def session(self):
        """The underlying :class:`repro.api.Session` (migration escape hatch)."""
        return self._session

    def submit(self, work) -> _Ticket:
        """Queue an :class:`Instance` or a :class:`SolveRequest`; returns a ticket."""
        self._pending.append(self._session.submit(work))
        return _Ticket(index=self._base + len(self._results) + len(self._pending) - 1)

    def flush(self) -> list:
        """Solve everything queued; returns the new reports (queue order).

        Idempotent: flushing an empty queue is a no-op returning ``[]``.
        """
        if not self._pending:
            return []
        batch, self._pending = self._pending, []
        try:
            self._session.flush()
            res = [t.report() for t in batch]
        except BaseException:
            # keep the batch queued so ticket indices stay aligned and the
            # next flush still reports every ticket.  Solver errors have
            # already resolved their tickets to failed artifacts inside the
            # Session, so that flush yields status="error" reports for them
            # (not a re-solve); interrupts leave tickets unresolved and DO
            # re-solve on the next flush.
            self._pending = batch + self._pending
            raise
        self._results.extend(res)
        # bound retained results so a long-running serving loop cannot grow
        # without limit; tickets older than the window raise in result()
        excess = len(self._results) - self.max_results
        if excess > 0:
            del self._results[:excess]
            self._base += excess
        return res

    def result(self, ticket: _Ticket):
        """The report for ``ticket`` — auto-flushes when it is still queued."""
        if ticket.index >= self._base + len(self._results):
            self.flush()
        if ticket.index < self._base:
            raise KeyError(
                f"ticket {ticket.index} evicted (retention window "
                f"{self.max_results}); read results at flush() time instead"
            )
        return self._results[ticket.index - self._base]

    def solve_many(self, instances: list) -> list:
        """One-shot convenience: bulk solve in caller order (flushes any
        previously submitted work too)."""
        for inst in instances:
            self.submit(inst)
        return self.flush()[-len(instances):] if instances else []

    def stats(self) -> dict:
        return self.cache.stats()
