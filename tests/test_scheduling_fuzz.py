"""Differential scheduling fuzz: serial simplex == batched, over every
scenario axis the IR emits.

The paper's claim that the LP dominates the heuristics is only as good as
the solver, and the repository has two implementations of it (the NumPy
reference and the vmapped engine).  This suite generates random
platforms — topology ∈ {chain, star}, heterogeneous ``w``/``z``/``tau``,
release dates, affine latencies (the (2b)/(3b) own-port rows / the star's
one-port master rows), result-return ratios ∈ {0, >0}, ``q`` = 1..4,
``m`` = 2..8 — and asserts both agree on makespans at <= 1e-9 *and* on
status codes.  Schedule LPs are feasible by construction on both topologies,
so the infeasible / unbounded / degenerate status parity is pinned on raw
LP stacks below (those paths are topology-independent: the batched simplex
sees only matrices), including the degenerate star-routing regression at
the backend seam.

Hypothesis drives the generator when available (CI installs it; the
deterministic profile is pinned in conftest.py); a seeded sweep over the
same generator keeps the differential coverage when it is not.  Shapes are
drawn from a fixed menu so the suite compiles a bounded set of programs.
"""

import numpy as np
import pytest

from repro.core.backends import SolveRequest, get_backend
from repro.core.instance import Chain, Instance, Loads, Star
from repro.core.simplex import solve_simplex
from repro.engine import solve_bulk
from repro.engine.batched_simplex import STATUS, solve_simplex_batched

RTOL = 1e-9

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

# (m, n_loads, q) — bounded so the engine compiles a fixed set of
# shapes; spans the smallest legal platform up to the §6 protocol's m=8
SHAPES = [(2, 1, 1), (2, 2, 2), (3, 2, 1), (4, 1, 3), (5, 2, 2),
          (6, 1, 4), (8, 2, 1)]

TOPOLOGIES = ("chain", "star")


def random_platform_instance(rng, m, n_loads, q, with_latency, with_release,
                             with_tau, topology="chain",
                             with_returns=False) -> Instance:
    w = rng.uniform(0.2, 2.0, size=m)
    z = rng.uniform(0.05, 1.0, size=m - 1)
    tau = rng.uniform(0.0, 1.0, size=m) if with_tau else 0.0
    lat = rng.uniform(0.01, 0.2, size=m - 1) if with_latency else 0.0
    v_comp = rng.uniform(0.5, 3.0, size=n_loads)
    v_comm = v_comp * rng.uniform(0.2, 2.0, size=n_loads)
    release = rng.uniform(0.0, 2.0, size=n_loads) if with_release else 0.0
    ret = rng.uniform(0.1, 1.0, size=n_loads) if with_returns else 0.0
    platform_cls = Star if topology == "star" else Chain
    return Instance(
        platform_cls(w=w, z=z, tau=tau, latency=lat),
        Loads(v_comm=v_comm, v_comp=v_comp, release=release, return_ratio=ret),
        q=q,
    )


def assert_two_way_parity(inst: Instance) -> None:
    req = SolveRequest(instance=inst)
    rs = get_backend("simplex").solve(req)
    rb = get_backend("batched").solve(req)
    # statuses must agree; schedule LPs are always feasible, so this is
    # "optimal" both ways (a backend-specific non-optimal would diverge here)
    assert rs.status == rb.status == "optimal", (rs.status, rb.status)
    scale = max(abs(rs.makespan), 1.0)
    assert abs(rb.makespan - rs.makespan) <= RTOL * scale


def _fuzz_case(shape_idx, with_latency, with_release, with_tau, seed,
               topology="chain", with_returns=False):
    m, n_loads, q = SHAPES[shape_idx % len(SHAPES)]
    rng = np.random.default_rng(seed)
    inst = random_platform_instance(
        rng, m, n_loads, q, with_latency, with_release, with_tau,
        topology=topology, with_returns=with_returns)
    assert_two_way_parity(inst)


# ------------------------------------------------------------- feasible fuzz


@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("k", range(len(SHAPES)))
def test_differential_seeded_sweep(k, topology):
    # the non-hypothesis arm: every shape x topology, every extension —
    # including the return phase — toggled on its own seed; runs anywhere
    _fuzz_case(k, with_latency=bool(k % 2), with_release=bool(k % 3 == 1),
               with_tau=bool(k % 3 == 2), seed=1000 + k, topology=topology,
               with_returns=bool(k % 2 == 0))


if HAVE_HYPOTHESIS:

    @settings(max_examples=16, deadline=None)
    @given(
        shape_idx=st.integers(0, len(SHAPES) - 1),
        with_latency=st.booleans(),
        with_release=st.booleans(),
        with_tau=st.booleans(),
        topology=st.sampled_from(TOPOLOGIES),
        with_returns=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_differential_hypothesis(shape_idx, with_latency, with_release,
                                     with_tau, topology, with_returns, seed):
        _fuzz_case(shape_idx, with_latency, with_release, with_tau, seed,
                   topology=topology, with_returns=with_returns)


def test_bulk_three_way_mixed_population():
    # one solve_bulk call over a mixed-shape population — spanning both
    # topologies and the return phase in the same call, exercising the
    # (topology, returns, m, T, q) bucketing — against serial solves
    rng = np.random.default_rng(7)
    insts = []
    for k, (m, n_loads, q) in enumerate(SHAPES[:4]):
        for topology in TOPOLOGIES:
            insts.append(random_platform_instance(
                rng, m, n_loads, q, bool(k % 2), bool(k % 2 == 0), False,
                topology=topology, with_returns=bool(k % 2)))
    rb = solve_bulk(insts)
    for inst, b in zip(insts, rb):
        assert b.status == "optimal"
        assert b.backend == "batched"
        rs = get_backend("simplex").solve(SolveRequest(instance=inst))
        assert abs(b.makespan - rs.makespan) <= RTOL * max(abs(rs.makespan), 1.0)


# -------------------------------------------- non-optimal status parity


def test_infeasible_status_parity():
    # x <= -1 with x >= 0: phase 1 cannot zero the artificial
    c = np.array([[1.0]])
    A_ub, b_ub = np.array([[[1.0]]]), np.array([[-1.0]])
    rb = solve_simplex_batched(c, A_ub, b_ub)
    ref = solve_simplex(c[0], A_ub[0], b_ub[0])
    assert STATUS[int(rb.status[0])] == ref.status == "infeasible"
    assert np.isnan(rb.objective[0])


def test_unbounded_status_parity():
    # min -x s.t. -x <= 1: x can grow without bound
    c = np.array([[-1.0]])
    A_ub, b_ub = np.array([[[-1.0]]]), np.array([[1.0]])
    rb = solve_simplex_batched(c, A_ub, b_ub)
    ref = solve_simplex(c[0], A_ub[0], b_ub[0])
    assert STATUS[int(rb.status[0])] == ref.status == "unbounded"


def test_degenerate_status_parity():
    # -x - y = 0 with x, y >= 0: phase 1 is immediately optimal with the
    # artificial still basic at zero level on a row with nonzero entries —
    # the batched path flags status 4 (serial-fallback material) while the
    # NumPy solver pays the drive-out pivots and solves it
    c = np.array([[1.0, 1.0]])
    A_eq, b_eq = np.array([[[-1.0, -1.0]]]), np.array([[0.0]])
    rb = solve_simplex_batched(c, A_eq=A_eq, b_eq=b_eq)
    assert int(rb.status[0]) == 4
    assert STATUS[4] == "degenerate"
    assert np.isnan(rb.x[0]).all()
    ref = solve_simplex(c[0], A_eq=A_eq[0], b_eq=b_eq[0])
    assert ref.status == "optimal" and abs(ref.objective) <= 1e-12


def test_mixed_status_batch_parity():
    # a random stack that lands a mix of optimal/infeasible/unbounded in one
    # batch: the engine must agree elementwise with the reference
    rng = np.random.default_rng(42)
    B, n, mu, me = 8, 6, 5, 2
    c = rng.normal(size=(B, n))
    A_ub = rng.normal(size=(B, mu, n))
    b_ub = rng.uniform(0.5, 2, size=(B, mu))
    A_eq = rng.normal(size=(B, me, n))
    b_eq = rng.uniform(-1, 1, size=(B, me))
    rb = solve_simplex_batched(c, A_ub, b_ub, A_eq, b_eq)
    assert len(set(rb.status.tolist())) >= 2, "seed chosen to mix statuses"
    for b in range(B):
        ref = solve_simplex(c[b], A_ub[b], b_ub[b], A_eq[b], b_eq[b])
        assert STATUS[int(rb.status[b])] == ref.status
        if ref.status == "optimal":
            scale = max(abs(ref.objective), 1.0)
            assert abs(rb.objective[b] - ref.objective) <= 1e-9 * scale


# -------------------------------------------- degenerate-element routing


@pytest.mark.parametrize("topology", TOPOLOGIES)
def test_status4_routes_to_serial_identically(monkeypatch, topology):
    # the satellite contract: a degenerate (status-4) element must reach the
    # serial fallback through the batched backend and come back exactly as
    # the serial solver plans it, on either topology.  Degenerate corners
    # essentially never occur on schedule LPs, so force the flag at the
    # solver seam and compare the full fallout.
    import repro.engine.service as service

    real = service.solve_simplex_batched
    seen = []

    def forced(*args, **kwargs):
        res = real(*args, **kwargs)
        seen.append(res.status.shape[0])
        res.status = np.full_like(np.asarray(res.status), 4)
        res.x = np.full_like(np.asarray(res.x), np.nan)
        return res

    monkeypatch.setattr(service, "solve_simplex_batched", forced)
    rng = np.random.default_rng(3)
    inst = random_platform_instance(rng, 3, 2, 2, True, False, False,
                                    topology=topology, with_returns=True)
    from repro.core.solver import solve
    from repro.engine.service import BatchedBackend

    rb = BatchedBackend().solve(SolveRequest(instance=inst))
    rs = solve(inst, objective="makespan")  # the rescue's own serial solve
    assert seen == [1]  # the engine actually hit the seam
    assert rb.status == rs.status == "optimal"
    assert rb.backend == rs.backend  # the *serial* solver's label
    assert rb.backend != "batched"
    np.testing.assert_array_equal(rs.schedule.gamma, rb.schedule.gamma)
    assert rs.makespan == rb.makespan


# ------------------------------------------------- campaign classifier arm


def _classifier_never_anomalous(shape_idx, topology, with_returns,
                                with_release, with_latency, seed):
    """The campaign classifier must agree with this suite by construction:
    on any random Chain/Star instance the LP is <= every feasible heuristic
    (at the heuristic's own installment structure) within 1e-9 — i.e. the
    verdict is never ``anomaly``.  Serial backends keep this compile-free."""
    from repro.api import Policy, Session
    from repro.core.heuristics import ALL_HEURISTICS, run_strategy
    from repro.eval import CLASSES, classify_instance

    m, n_loads, q = SHAPES[shape_idx % len(SHAPES)]
    rng = np.random.default_rng(seed)
    inst = random_platform_instance(
        rng, m, n_loads, q, with_latency, with_release, with_tau=False,
        topology=topology, with_returns=with_returns)
    sess = Session(policy=Policy(backend="simplex"))
    art = sess.solve(inst)
    runs = [run_strategy(n, f, inst) for n, f in ALL_HEURISTICS.items()]
    c = classify_instance(inst, art, runs, rtol=RTOL,
                          matched_solve=sess.solve)
    assert c.label in CLASSES
    assert c.label != "anomaly", (
        f"classifier anomaly on a random instance: {c.anomaly}")
    # and the LP bound holds pointwise against every feasible heuristic
    for name, entry in c.strategies.items():
        if entry["failure"] == "" and entry["makespan"] is not None:
            assert c.effective_lp <= entry["makespan"] * (1 + 1e-7) + 1e-9, (
                f"{name} beat the LP: {entry['makespan']} < {c.effective_lp}")


@pytest.mark.parametrize("topology", TOPOLOGIES)
@pytest.mark.parametrize("k", range(0, len(SHAPES), 2))
def test_campaign_classifier_seeded_sweep(k, topology):
    _classifier_never_anomalous(k, topology, with_returns=bool(k % 2 == 0),
                                with_release=bool(k % 3 == 1),
                                with_latency=bool(k % 2), seed=4000 + k)


if HAVE_HYPOTHESIS:

    @settings(max_examples=12, deadline=None)
    @given(
        shape_idx=st.integers(0, len(SHAPES) - 1),
        topology=st.sampled_from(TOPOLOGIES),
        with_returns=st.booleans(),
        with_release=st.booleans(),
        with_latency=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_campaign_classifier_hypothesis(shape_idx, topology, with_returns,
                                            with_release, with_latency, seed):
        _classifier_never_anomalous(shape_idx, topology, with_returns,
                                    with_release, with_latency, seed)


# ------------------------------------------ mis-convergence golden corpus


def test_false_optimal_golden_all_backends():
    # the PR-8 campaign's mis-convergence instance: the dense simplex used
    # to exit "optimal" with a port-serialization row violated by ~0.24 and
    # an objective *below* the true optimum (976.1527780792386, HiGHS).
    # Every backend — including the batched engine, whose exits now
    # run the same primal-feasibility demotion — must land on the golden.
    from repro.eval import CampaignSpec, full_spec

    spec = full_spec()
    cell_id = "star/ret0.75/rel0/m2/n3/q4/het1/cc0.02"
    cell = next(c for c in spec.cells() if CampaignSpec.cell_id(c) == cell_id)
    inst = spec.materialize(cell, 0)
    golden = 976.1527780792386
    for backend in ("simplex", "auto", "batched"):
        rep = get_backend(backend).solve(SolveRequest(instance=inst))
        assert rep.status == "optimal", (backend, rep.status)
        assert abs(rep.makespan - golden) <= 1e-6 * golden, (
            backend, rep.makespan)


def test_primal_violation_demotes_optimal_exit():
    # unit pin on the engine-side check: forge an "optimal" status over an
    # x that violates A_ub x <= b_ub and assert the demotion to status 5
    # (false_optimal) — the code the golden above routes through
    from repro.engine.batched_simplex import _demote_false_optimal

    x = np.array([[2.0, 0.0], [0.5, 0.5]])
    A_ub = np.tile(np.array([[[1.0, 1.0]]]), (2, 1, 1))
    b_ub = np.array([[1.0], [1.0]])  # row 0 violated by 1.0, row 1 tight
    A_eq = np.zeros((2, 0, 2))
    b_eq = np.zeros((2, 0))
    status = np.zeros(2, dtype=np.int32)
    out = _demote_false_optimal(x, status, A_ub, b_ub, A_eq, b_eq)
    assert out.tolist() == [5, 0]
    assert STATUS[5] == "false_optimal"
    # NaN lanes (infeasible/degenerate exits) must pass through untouched
    xn = np.array([[np.nan, np.nan]])
    sn = np.array([1], dtype=np.int32)
    out2 = _demote_false_optimal(xn, sn, A_ub[:1], b_ub[:1], A_eq[:1], b_eq[:1])
    assert out2.tolist() == [1]


# ------------------------------------------ event-stream equivalence arm


def _event_stream_case(topology, with_returns, warm, backend):
    """A replayed event log must end at the same schedule (<= 1e-9 relative
    makespan) as a cold solve of the final platform state, on a fresh
    session (no shared cache to trivialize the comparison)."""
    from repro.api import Policy, Problem, Session
    from repro.runtime.replan import (EventStreamReplanner, LoadArrived,
                                      ProcessorDown, ProcessorUp,
                                      SpeedObserved)

    rng = np.random.default_rng(hash((topology, with_returns, warm)) % 2**31)
    inst = random_platform_instance(
        rng, 3, 2, 2, with_latency=True, with_release=True, with_tau=False,
        topology=topology, with_returns=with_returns)
    prob = Problem.from_instance(inst)
    sess = Session(Policy(installments=2, backend=backend))
    rp = EventStreamReplanner(sess, prob, warm=warm)
    events = [
        SpeedObserved(1, float(prob.w[1]) * 1.15),
        SpeedObserved(2, float(prob.w[2]) * 0.9),
        LoadArrived(v_comm=0.8, v_comp=1.5, release=0.25,
                    return_ratio=0.5 if with_returns else 0.0, deadline=1e6),
        SpeedObserved(0, float(prob.w[0]) * 1.05),
        ProcessorDown(1, restore_delay=0.1),
        ProcessorUp(w=1.1, z=0.3, latency=0.05, tau=0.2),
        SpeedObserved(1, 0.95),
    ]
    arts = rp.replay(events)
    assert all(a.ok for a in arts), [a.status for a in arts]
    # provenance: every replan is recorded; coefficient events after a basis
    # exists requested warm iff the replanner runs warm
    for a, ev in zip(arts, events):
        tail = a.events[-1]
        assert tail["kind"] == "replan"
        assert tail["trigger"] == type(ev).__name__
        if not isinstance(ev, SpeedObserved):
            assert not tail["warm_requested"]  # structural => cold, always
    if warm and backend == "batched":
        assert any(a.events[-1]["warm"] for a in arts), \
            "warm path never engaged on coefficient events"
    elif warm:  # the serial backends have no warm entry: every replan is cold
        assert not any(a.events[-1]["warm"] for a in arts)
    # the equivalence: final replayed state == cold solve on a FRESH session
    cold = Session(Policy(installments=2, backend=backend)).solve(rp.problem)
    assert cold.ok
    scale = max(abs(cold.makespan), 1.0)
    assert abs(arts[-1].makespan - cold.makespan) <= RTOL * scale, (
        arts[-1].makespan, cold.makespan)
    assert abs(arts[-1].lp_makespan - cold.lp_makespan) <= RTOL * scale


@pytest.mark.parametrize("backend", ["batched", "auto"])
@pytest.mark.parametrize("warm", [True, False])
@pytest.mark.parametrize("topology,with_returns",
                         [("chain", False), ("chain", True),
                          ("star", False), ("star", True)])
def test_event_stream_equivalence(topology, with_returns, warm, backend):
    _event_stream_case(topology, with_returns, warm, backend)


def test_warm_start_simplex_matches_cold_on_perturbation():
    # at the solver layer: warm-started solves of perturbed LPs must land on
    # the same objective as cold solves, with zero phase-1 pivots whenever
    # the carried basis is accepted
    rng = np.random.default_rng(5)
    insts = [random_platform_instance(rng, 4, 2, 2, True, True, False,
                                      topology="star", with_returns=True)
             for _ in range(3)]
    from repro.engine.batched_lp import build_lp_bucket
    from repro.engine.arena import pack_instances

    (bucket,) = pack_instances(insts)
    lp = build_lp_bucket(bucket)
    c = np.tile(lp.c, (bucket.B, 1))
    base = solve_simplex_batched(c, lp.A_ub, lp.b_ub, lp.A_eq, lp.b_eq)
    assert (base.status == 0).all()
    assert base.basis is not None and not base.warm_started.any()
    # perturb the objective/rows mildly (a speed drift) and re-solve warm
    A_ub2 = lp.A_ub * (1 + 1e-3)
    warm = solve_simplex_batched(c, A_ub2, lp.b_ub, lp.A_eq, lp.b_eq,
                                 warm_basis=base.basis)
    cold = solve_simplex_batched(c, A_ub2, lp.b_ub, lp.A_eq, lp.b_eq)
    np.testing.assert_array_equal(warm.status, cold.status)
    np.testing.assert_allclose(warm.objective, cold.objective,
                               rtol=1e-9, atol=1e-12)
    accepted = warm.warm_started
    assert accepted.any(), "no lane accepted the carried basis"
    assert (warm.iterations_phase1[accepted] == 0).all()
    # a rejected/garbage seed must fall back to a cold solve transparently
    bad = np.full_like(base.basis, 10**6)
    fb = solve_simplex_batched(c, A_ub2, lp.b_ub, lp.A_eq, lp.b_eq,
                               warm_basis=bad)
    assert not fb.warm_started.any()
    np.testing.assert_allclose(fb.objective, cold.objective,
                               rtol=1e-12, atol=0)
