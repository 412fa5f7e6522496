"""The solver-backend registry and the cost-aware installment sweep.

Gates the new public surface of the multi-layer refactor:
  * registry resolution (names, instances, unknown names, custom backends),
  * SolveRequest/SolveReport threading through solve()/solve_batch()/
    Planner/PlanService,
  * Planner.plan_auto_T — the practical Theorem-1 chooser: with zero
    per-installment cost more installments always (weakly) help, so T*
    rides the ladder top; a positive cost makes T* finite.
"""

import numpy as np
import pytest

from repro.core import (
    SolveReport,
    SolveRequest,
    SolverBackend,
    available_backends,
    get_backend,
    register_backend,
    solve,
    solve_batch,
)
from repro.core.instance import random_instance
from repro.core.planner import BatchSpec, LinkSpec, Planner, StageSpec

_STAGES = [StageSpec(f"s{i}", 1e9 * (1 + 0.3 * i)) for i in range(3)]
_LINKS = [LinkSpec(1e8, 50e-6)] * 2
_BATCHES = [
    BatchSpec(num_samples=64, bytes_per_sample=4096, flops_per_sample=1e7)
    for _ in range(2)
]


# ------------------------------------------------------------------ registry


def test_registry_names_and_resolution():
    names = available_backends()
    for expected in ("auto", "simplex", "scipy", "serial", "batched"):
        assert expected in names
    be = get_backend("simplex")
    assert isinstance(be, SolverBackend)
    assert get_backend("simplex") is be  # default instances are shared
    assert get_backend(be) is be  # instances pass through
    with pytest.raises(ValueError):
        get_backend("nope")
    with pytest.raises(ValueError):
        get_backend(None)


def test_pallas_is_not_a_backend():
    """The engine has one solver path: a policy naming the removed
    ``pallas`` backend fails like any unknown name, naming the ones there
    are."""
    import re

    from repro.api import Policy, Problem, Session

    assert "pallas" not in available_backends()
    problem = Problem(w=[1.0, 2.0], z=[0.5], v_comm=[1.0], v_comp=[1.0])
    with pytest.raises(ValueError, match=re.escape(
            f"unknown solver backend 'pallas'; available: {available_backends()}")):
        Session(policy=Policy(backend="pallas")).solve(problem)

def test_solve_shim_reports_carry_their_request():
    rng = np.random.default_rng(0)
    inst = random_instance(rng, m=3, n_loads=2, q=1)
    rep = solve(inst, backend="simplex")
    assert isinstance(rep, SolveReport)
    assert rep.ok and rep.backend == "simplex"
    assert rep.request is not None and rep.request.instance is inst

    # a backend INSTANCE works anywhere a name does (the deprecation path)
    rep2 = solve(inst, backend=get_backend("simplex"))
    assert rep2.makespan == pytest.approx(rep.makespan, abs=1e-9)


def test_custom_backend_registers_and_serves_requests():
    calls = []

    class Recording(SolverBackend):
        name = "recording"

        def solve(self, request):
            calls.append(request)
            return get_backend("simplex").solve(request)

    register_backend("recording", Recording)
    try:
        rng = np.random.default_rng(1)
        insts = [random_instance(rng, m=2, n_loads=1, q=1) for _ in range(3)]
        reports = solve_batch(insts, backend="recording")
        assert len(calls) == 3 and all(isinstance(c, SolveRequest) for c in calls)
        assert all(r.ok for r in reports)
        # ... including from the Planner front door
        p = Planner(list(_STAGES), list(_LINKS))
        plan = p.plan(_BATCHES, q=2, backend="recording")
        assert plan.makespan > 0 and len(calls) == 4
    finally:
        from repro.core.backends import _FACTORIES

        _FACTORIES.pop("recording", None)


def test_batched_backend_groups_mixed_objectives():
    rng = np.random.default_rng(2)
    insts = [random_instance(rng, m=3, n_loads=2, q=1) for _ in range(4)]
    be = get_backend("batched")
    reqs = [
        SolveRequest(instance=inst,
                     objective="completion" if i % 2 else "makespan")
        for i, inst in enumerate(insts)
    ]
    reports = be.solve_many(reqs)
    assert all(r.ok for r in reports)
    for req, rep in zip(reqs, reports):
        assert rep.request is req
        ref = solve(req.instance, objective=req.objective)
        assert rep.objective_value == pytest.approx(
            ref.objective_value, rel=1e-6, abs=1e-9
        )


def test_batched_backend_honors_weights_beta_and_cross_check():
    # every request field must survive the batched front door: completion
    # weights/beta delegate to the serial solver WITH the request, instead
    # of being silently replaced by the defaults
    rng = np.random.default_rng(4)
    inst = random_instance(rng, m=3, n_loads=2, q=2)
    req = SolveRequest(instance=inst, objective="completion",
                       weights=[5.0, 0.0], beta=0.5)
    batched = get_backend("batched").solve(req)
    ref = get_backend("simplex").solve(
        SolveRequest(instance=inst, objective="completion",
                     weights=[5.0, 0.0], beta=0.5)
    )
    assert batched.ok
    assert batched.objective_value == pytest.approx(
        ref.objective_value, rel=1e-6, abs=1e-9
    )
    # cross_check is a serial-only contract: it must actually run serially,
    # not be silently dropped on the batched path
    checked = get_backend("batched").solve(SolveRequest(instance=inst, cross_check=True))
    assert checked.ok and not checked.backend.startswith("batched")
    # validate=False must NOT forfeit the batched speedup — it only governs
    # the rare uncertified-element fallback
    fast = get_backend("batched").solve(SolveRequest(instance=inst, validate=False))
    assert fast.ok and fast.backend.startswith("batched")


def test_backend_instance_adopts_planner_cache_without_mutation():
    from repro.engine import BatchedBackend
    from repro.engine.cache import SolutionCache

    cache = SolutionCache()
    p = Planner(list(_STAGES), list(_LINKS), cache=cache)
    be = BatchedBackend()  # no cache of its own
    p.plan(_BATCHES, q=2, backend=be)
    again = p.plan(_BATCHES, q=2, backend=be)
    assert again.result.backend == "batched+cache"  # planner cache was used
    assert be.cache is None  # ... without mutating the caller's instance

    # the shared registry default must not leak a caller's cache either
    shared = get_backend("batched")
    p.plan(_BATCHES, q=2, backend=shared)
    assert get_backend("batched").cache is None

    # an instance's own cache is never replaced
    own = SolutionCache()
    be2 = BatchedBackend(cache=own)
    p.plan(_BATCHES, q=2, backend=be2)
    assert be2.cache is own


def test_plan_service_accepts_requests():
    from repro.engine import PlanService

    rng = np.random.default_rng(3)
    svc = PlanService()
    t1 = svc.submit(random_instance(rng, m=3, n_loads=2, q=1))
    t2 = svc.submit(SolveRequest(instance=random_instance(rng, m=3, n_loads=2, q=1)))
    svc.flush()
    assert svc.result(t1).ok and svc.result(t2).ok
    assert svc.result(t2).request is not None


# ------------------------------------------------------------------ plan_auto_T


def test_plan_auto_t_zero_cost_rides_the_ladder_top():
    # Theorem 1: linear model -> LP(q+1) <= LP(q); with no installment cost
    # the sweep keeps improving (or plateaus within the strict tie-break)
    p = Planner(list(_STAGES), list(_LINKS))
    res = p.plan_auto_T(_BATCHES, t_max=4, installment_cost=0.0)
    assert set(res.makespans) == {1, 2, 3, 4}
    ms = [res.makespans[q] for q in (1, 2, 3, 4)]
    for a, b in zip(ms, ms[1:]):
        assert b <= a * (1 + 1e-6) + 1e-9
    assert res.costs == res.makespans
    assert res.plan.makespan == pytest.approx(res.makespans[res.t_star], rel=1e-6)


def test_plan_auto_t_positive_cost_picks_finite_t_star():
    p = Planner(list(_STAGES), list(_LINKS))
    free = p.plan_auto_T(_BATCHES, t_max=4, installment_cost=0.0)
    # a cost far above the largest q-to-q improvement forces T* = 1
    expensive = p.plan_auto_T(_BATCHES, t_max=4, installment_cost=1e3)
    assert expensive.t_star == 1
    assert expensive.t_star <= free.t_star
    # the winning plan is executable: every load's samples fully distributed
    for n, b in enumerate(_BATCHES):
        assert expensive.plan.total_samples(n) == b.num_samples
    # cost model is exactly makespan + cost * installments
    n_loads = len(_BATCHES)
    for q, mk in expensive.makespans.items():
        assert expensive.costs[q] == pytest.approx(mk + 1e3 * q * n_loads)


def test_plan_auto_t_backends_agree_and_cache_reuses():
    from repro.engine.cache import SolutionCache

    cache = SolutionCache()
    p = Planner(list(_STAGES), list(_LINKS), cache=cache)
    batched = p.plan_auto_T(_BATCHES, t_max=3, installment_cost=1e-3)
    serial = p.plan_auto_T(_BATCHES, t_max=3, installment_cost=1e-3, backend="serial")
    assert batched.t_star == serial.t_star
    for q in batched.makespans:
        assert batched.makespans[q] == pytest.approx(
            serial.makespans[q], rel=1e-9, abs=1e-9
        )
    # a second sweep over the same platform state replays from the cache
    again = p.plan_auto_T(_BATCHES, t_max=3, installment_cost=1e-3)
    assert all(r.backend == "batched+cache" for r in again.reports)


def test_chain_replanner_auto_installments():
    from repro.runtime.dlt_runner import ChainReplanner

    rp = ChainReplanner(Planner(list(_STAGES), list(_LINKS)), q=2)
    res = rp.auto_installments(_BATCHES, t_max=3, installment_cost=1e-3)
    assert res.t_star in (1, 2, 3)
    assert res.plan.makespan > 0
