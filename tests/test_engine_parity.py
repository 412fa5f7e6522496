"""Engine-vs-NumPy parity: the batched JAX engine must be numerically
interchangeable with the serial reference stack in repro.core.

Covers the acceptance bar of the engine PR:
  * vmapped ASAP simulator == core.simulator.simulate to <= 1e-9 max abs
    deviation on every event time, including padded buckets and the
    (m=2, T=1) edge case;
  * batched simplex == core.simplex (and scipy/HiGHS when present) on
    random LPs, including infeasible/unbounded statuses;
  * solve_bulk == core.solver.solve on random schedule populations,
    including release dates, availability dates, and affine latencies;
  * the solution cache replays identical results.
"""

import numpy as np
import pytest

from repro.core.instance import Chain, Instance, Loads, random_instance
from repro.core.simplex import solve_simplex
from repro.core.simulator import simulate
from repro.core.solver import solve, solve_batch
from repro.engine import (
    InstanceArena,
    SolutionCache,
    makespans,
    simulate_many,
    solve_bulk,
    solve_simplex_batched,
)

ATOL = 1e-9


def _spiced_population(rng, n=18):
    """Mixed-shape instances exercising every §5 extension the arena packs:
    affine latencies, nonzero release/availability dates, unrelated machines.
    Shapes are drawn from a small set so the test compiles few programs."""
    insts = []
    shapes = [(2, 1, 1), (3, 2, 2), (5, 2, 1)]  # (m, n_loads, q)
    for k in range(n):
        m, nl, q = shapes[k % len(shapes)]
        inst = random_instance(rng, m=m, n_loads=nl, q=q,
                               with_latency=bool(k % 2))
        if k % 3 == 1:  # nonzero release + availability dates
            chain = Chain(w=inst.chain.w, z=inst.chain.z,
                          tau=rng.uniform(0, 5, size=m),
                          latency=inst.chain.latency)
            loads = Loads(v_comm=inst.loads.v_comm, v_comp=inst.loads.v_comp,
                          release=rng.uniform(0, 10, size=nl))
            inst = Instance(chain, loads, q=inst.q)
        elif k % 3 == 2:  # unrelated machines
            w_per_load = inst.chain.w[:, None] * rng.uniform(0.5, 2.0, size=(m, nl))
            inst = Instance(inst.chain, inst.loads, q=inst.q, w_per_load=w_per_load)
        insts.append(inst)
    return insts


def _feasible_gamma(rng, inst):
    g = np.abs(rng.normal(size=(inst.m, inst.total_installments))) + 0.1
    cells = list(inst.cells())
    for n in range(inst.N):
        cols = [t for t, (load, _) in enumerate(cells) if load == n]
        g[:, cols] /= g[:, cols].sum()
    return g


# ---------------------------------------------------------------- simulator


@pytest.mark.parametrize("pad_shapes", [False, True])
def test_batched_sim_matches_numpy(pad_shapes):
    rng = np.random.default_rng(0)
    insts = _spiced_population(rng)
    gammas = [_feasible_gamma(rng, inst) for inst in insts]
    scheds = simulate_many(insts, gammas, pad_shapes=pad_shapes)
    for inst, g, got in zip(insts, gammas, scheds):
        ref = simulate(inst, g)
        for field in ("comm_start", "comm_end", "comp_start", "comp_end"):
            dev = np.max(np.abs(getattr(got, field) - getattr(ref, field))) \
                if getattr(ref, field).size else 0.0
            assert dev <= ATOL, (field, dev)
        assert abs(got.makespan - ref.makespan) <= ATOL


def test_batched_sim_m2_T1_edge_case():
    # the smallest legal instance shape: one load, one installment, two
    # processors — exercises the single-link scan and the T=1 recurrence
    rng = np.random.default_rng(1)
    insts = [random_instance(rng, m=2, n_loads=1, q=1) for _ in range(8)]
    gammas = [_feasible_gamma(rng, inst) for inst in insts]
    mks = makespans(insts, gammas, pad_shapes=True)
    for inst, g, mk in zip(insts, gammas, mks):
        assert abs(mk - simulate(inst, g).makespan) <= ATOL


def test_padded_bucket_masks_fake_cells():
    # a bucket padded up the shape ladder (m=3 -> 4, T=3 -> 4) must produce
    # the same times as the exact shapes: padding may never delay anything
    rng = np.random.default_rng(2)
    insts = [random_instance(rng, m=3, n_loads=3, q=1, with_latency=True)
             for _ in range(6)]
    arena = InstanceArena(insts, pad_shapes=True)
    assert all(b.m > b.m_real or b.T > b.T_real for b in arena.buckets), \
        "population was chosen to force ladder padding"
    gammas = [_feasible_gamma(rng, inst) for inst in insts]
    padded = makespans(insts, gammas, pad_shapes=True)
    exact = makespans(insts, gammas, pad_shapes=False)
    ref = [simulate(i, g).makespan for i, g in zip(insts, gammas)]
    np.testing.assert_allclose(padded, ref, atol=ATOL, rtol=0)
    np.testing.assert_allclose(exact, ref, atol=ATOL, rtol=0)


def test_arena_scatter_restores_caller_order():
    rng = np.random.default_rng(3)
    insts = _spiced_population(rng, n=12)
    arena = InstanceArena(insts)
    assert len(arena.buckets) > 1
    flat = arena.scatter([[f"{b.key}/{i}" for i in range(b.B)]
                          for b in arena.buckets])
    for inst, tag in zip(insts, flat):
        key = (inst.topology, inst.has_returns, inst.m,
               inst.total_installments, tuple(inst.q))
        assert tag.startswith(str(key))


# ------------------------------------------------------------------ simplex


def _random_feasible_lp(rng):
    n = int(rng.integers(2, 7))
    mu = int(rng.integers(1, 7))
    me = int(rng.integers(0, 3))
    c = rng.normal(size=n)
    A_ub = rng.normal(size=(mu, n))
    x0 = np.abs(rng.normal(size=n))
    b_ub = np.maximum(rng.normal(size=mu) + 1.0, A_ub @ x0)
    A_eq = rng.normal(size=(me, n)) if me else None
    b_eq = A_eq @ x0 if me else None
    return c, A_ub, b_ub, A_eq, b_eq


def test_batched_simplex_matches_numpy_simplex():
    rng = np.random.default_rng(4)
    try:
        from scipy.optimize import linprog
    except Exception:  # pragma: no cover
        linprog = None
    checked = 0
    for _ in range(40):
        c, A_ub, b_ub, A_eq, b_eq = _random_feasible_lp(rng)
        ref = solve_simplex(c, A_ub, b_ub, A_eq, b_eq)
        res = solve_simplex_batched(
            c[None], A_ub[None], b_ub[None],
            None if A_eq is None else A_eq[None],
            None if b_eq is None else b_eq[None],
        )
        if res.status[0] == 4:  # degenerate corner: flagged for fallback,
            continue  # never silently wrong — correctness is the fallback's
        if ref.status == "optimal":
            assert res.status[0] == 0
            assert res.objective[0] == pytest.approx(ref.objective, rel=1e-9, abs=1e-9)
            if linprog is not None:
                sp = linprog(c, A_ub=A_ub, b_ub=b_ub, A_eq=A_eq, b_eq=b_eq,
                             bounds=(0, None), method="highs")
                if sp.status == 0:
                    assert res.objective[0] == pytest.approx(sp.fun, rel=1e-7, abs=1e-7)
            checked += 1
        elif ref.status == "unbounded":
            assert res.status[0] == 2
    assert checked >= 20  # the generator must actually produce solvable LPs


def test_batched_simplex_batch_axis_and_statuses():
    # one call, three elements: optimal / infeasible / unbounded — statuses
    # must resolve per element, not batch-wide (while_loop masking)
    n = 2
    c = np.array([[1.0, 1.0], [0.0, 1.0], [-1.0, 0.0]])
    A_ub = np.zeros((3, 2, n))
    b_ub = np.zeros((3, 2))
    A_ub[0] = [[-1.0, 0.0], [0.0, -1.0]]
    b_ub[0] = [-1.0, -2.0]  # x >= (1, 2): optimum 3
    A_ub[1] = [[1.0, 0.0], [-1.0, 0.0]]
    b_ub[1] = [-1.0, -1.0]  # x0 <= -1 and x0 >= 1: infeasible
    A_ub[2] = [[0.0, 1.0], [0.0, 0.0]]
    b_ub[2] = [1.0, 0.0]  # min -x0 unconstrained in x0: unbounded
    res = solve_simplex_batched(c, A_ub, b_ub)
    assert list(res.status) == [0, 1, 2]
    assert res.objective[0] == pytest.approx(3.0, abs=1e-9)
    assert np.isnan(res.objective[1])


# ----------------------------------------------------------------- solve_bulk


def test_solve_bulk_matches_serial_solve():
    rng = np.random.default_rng(5)
    insts = _spiced_population(rng, n=12)
    bulk = solve_bulk(insts)
    for inst, got in zip(insts, bulk):
        ref = solve(inst, backend="simplex")
        assert got.ok and ref.ok
        assert got.lp_makespan == pytest.approx(ref.lp_makespan, rel=1e-9, abs=ATOL)
        assert got.makespan == pytest.approx(ref.makespan, rel=1e-9, abs=ATOL)
        # the replayed schedule must be executable: replay == LP at optimum
        assert got.makespan <= got.lp_makespan * (1 + 1e-6) + 1e-9


@pytest.mark.parametrize("seed", [1, 2])
def test_solve_bulk_serves_extreme_comm_ratio_stars(seed):
    """Stars with result return at the Table-2 grid's comm-to-comp extremes
    (m=10, 10 loads): the exact min-ratio rule cycled to the iteration cap
    or lost feasibility here and every one went to the serial rescue; the
    Harris ratio test keeps them on the engine, at HiGHS's optimum."""
    insts = [
        random_instance(np.random.default_rng(seed), m=10, n_loads=10,
                        heterogeneous=True, with_latency=True,
                        topology="star", return_ratio=0.5, comm_to_comp=ccr)
        for ccr in (0.01, 100.0)
    ]
    for inst, got in zip(insts, solve_bulk(insts)):
        assert got.backend == "batched" and got.ok
        ref = solve(inst, backend="scipy")
        assert got.makespan == pytest.approx(ref.makespan, rel=1e-6)


def test_solve_batch_serial_backend_is_reference():
    rng = np.random.default_rng(6)
    insts = [random_instance(rng, m=3, n_loads=2, q=1) for _ in range(4)]
    serial = solve_batch(insts, backend="serial")
    batched = solve_batch(insts, backend="batched")
    for s, b in zip(serial, batched):
        assert b.lp_makespan == pytest.approx(s.lp_makespan, rel=1e-9, abs=ATOL)
    with pytest.raises(ValueError):
        solve_batch(insts, backend="nope")


def test_solution_cache_replays_identical_results():
    rng = np.random.default_rng(7)
    insts = [random_instance(rng, m=3, n_loads=2, q=1) for _ in range(6)]
    cache = SolutionCache()
    first = solve_bulk(insts, cache=cache)
    again = solve_bulk(insts, cache=cache)
    st = cache.stats()
    assert st["hits"] == len(insts) and st["entries"] == len(insts)
    for a, b in zip(first, again):
        assert b.backend == "batched+cache"
        assert b.makespan == pytest.approx(a.makespan, abs=ATOL)
        np.testing.assert_allclose(b.schedule.gamma, a.schedule.gamma, atol=ATOL)
