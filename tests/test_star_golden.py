"""Golden-value regressions for the star topology and the return phase.

Three pins, per the topology-generalization contract:

  * the single-load star LP optimum matches the classical bus-network
    closed form (all-participate equal finish) exactly on uniform-link
    platforms, and is dominated by it on heterogeneous links (where the LP
    may skip a slow-linked worker under the fixed activation order);
  * a 1-worker star degenerates to the m=2 chain: the master-port family
    collapses onto the own-port family, so the motivating example's golden
    numbers (GOLDEN_Q1/GOLDEN_Q2 of test_paper_golden.py) reproduce on a
    Star platform, on every backend;
  * return_ratio = 0 is the paper's model bit-identically: same variable
    layout (no return block), same row counts, same gamma, same makespan as
    an instance built before the return phase existed.
"""

import numpy as np
import pytest

from repro.core.backends import SolveRequest, get_backend
from repro.core.closed_form import (
    star_bus_instance,
    star_single_load_fractions,
    star_single_load_makespan,
)
from repro.core.instance import Chain, Instance, Loads, Star
from repro.core.lp import build_lp
from repro.core.simulator import simulate
from repro.core.solver import solve

# the golden constants of test_paper_golden.py (written out, not imported,
# so a drift there cannot mask one here)
GOLDEN_Q1 = 0.9568965517241379
GOLDEN_Q2 = 781.0 / 653.0 * 0.75


# ----------------------------------------------- closed-form oracle (bus)


@pytest.mark.parametrize("m,seed", [(2, 0), (3, 1), (5, 2), (8, 3)])
def test_single_load_star_lp_matches_bus_closed_form(m, seed):
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.2, 2.0, size=m)
    zc = float(rng.uniform(0.05, 1.0))
    vc, vp = float(rng.uniform(0.5, 3.0)), float(rng.uniform(0.5, 3.0))
    inst = Instance(Star(w=w, z=np.full(m - 1, zc)),
                    Loads(v_comm=[vc], v_comp=[vp]), q=1)
    lp = solve(inst, backend="simplex")
    cf = star_single_load_makespan(w, np.full(m - 1, zc), vc, vp)
    assert lp.ok
    assert abs(lp.makespan - cf) <= 1e-9 * max(abs(cf), 1.0)
    # the closed-form fractions replay to the same makespan
    alpha = star_single_load_fractions(w, np.full(m - 1, zc), vc, vp)
    assert abs(alpha.sum() - 1.0) <= 1e-12
    replay = simulate(inst, alpha.reshape(m, 1))
    assert abs(replay.makespan - cf) <= 1e-9 * max(abs(cf), 1.0)


@pytest.mark.parametrize("backend", ["simplex", "batched", "auto"])
def test_bus_closed_form_on_every_backend(backend):
    inst = star_bus_instance(w=[0.75, 1.5, 0.9], z=0.4)
    cf = star_single_load_makespan([0.75, 1.5, 0.9], [0.4, 0.4], 1.0, 1.0)
    rep = get_backend(backend).solve(SolveRequest(instance=inst))
    assert rep.ok
    assert abs(rep.makespan - cf) <= 1e-9 * max(abs(cf), 1.0)


def test_heterogeneous_links_lp_dominates_closed_form():
    # with a slow link in the middle the fixed-order LP beats all-participate
    # equal finish by skipping that worker — the formula is only a bound
    rng = np.random.default_rng(3)
    dominated = strict = 0
    for _ in range(8):
        m = int(rng.integers(2, 7))
        w = rng.uniform(0.2, 2.0, m)
        z = rng.uniform(0.05, 1.5, m - 1)
        vc, vp = float(rng.uniform(0.5, 3.0)), float(rng.uniform(0.5, 3.0))
        inst = Instance(Star(w=w, z=z), Loads(v_comm=[vc], v_comp=[vp]), q=1)
        lp = solve(inst, backend="simplex")
        cf = star_single_load_makespan(w, z, vc, vp)
        assert lp.ok
        assert lp.makespan <= cf * (1 + 1e-9) + 1e-12
        dominated += 1
        strict += lp.makespan < cf * (1 - 1e-6)
    assert dominated == 8
    assert strict >= 1, "expected at least one strict improvement (worker skip)"


# ------------------------------------------- 1-worker star == m=2 chain


def _star_example(lam: float, q) -> Instance:
    return Instance(Star(w=[lam, lam], z=[1.0]),
                    Loads(v_comm=[1.0, 1.0], v_comp=[1.0, 1.0]), q=q)


@pytest.mark.parametrize("backend", ["simplex", "batched", "auto"])
def test_one_worker_star_reproduces_chain_goldens(backend):
    b = get_backend(backend)
    r1 = b.solve(SolveRequest(instance=_star_example(0.75, q=1)))
    r2 = b.solve(SolveRequest(instance=_star_example(0.75, q=2)))
    assert r1.ok and r2.ok
    assert abs(r1.makespan - GOLDEN_Q1) <= 1e-9
    assert abs(r2.makespan - GOLDEN_Q2) <= 1e-9


def test_one_worker_star_lp_rows_match_chain():
    # the master-port family collapses onto the own-port family at m=2:
    # the two topologies emit the same number of rows with equal matrices
    chain = Instance(Chain(w=[0.75, 0.75], z=[1.0]),
                     Loads(v_comm=[1.0, 1.0], v_comp=[1.0, 1.0]), q=2)
    star = _star_example(0.75, q=2)
    lc, ls = build_lp(chain), build_lp(star)
    assert lc.n_vars == ls.n_vars
    np.testing.assert_array_equal(lc.dense_ub()[0], ls.dense_ub()[0])
    np.testing.assert_array_equal(lc.dense_eq()[0], ls.dense_eq()[0])
    np.testing.assert_array_equal(np.asarray(lc.b_ub), np.asarray(ls.b_ub))


# --------------------------------------- return_ratio = 0 bit-identicality


def test_return_ratio_zero_is_bit_identical_to_no_returns():
    rng = np.random.default_rng(7)
    for Platform in (Chain, Star):
        w = rng.uniform(0.2, 2.0, 4)
        z = rng.uniform(0.05, 1.0, 3)
        lat = rng.uniform(0.01, 0.1, 3)
        vp = rng.uniform(0.5, 3.0, 2)
        vc = vp * rng.uniform(0.2, 2.0, 2)
        plat = Platform(w=w, z=z, latency=lat)
        base = Instance(plat, Loads(v_comm=vc, v_comp=vp), q=2)
        zeroed = Instance(plat, Loads(v_comm=vc, v_comp=vp, return_ratio=0.0), q=2)
        assert not zeroed.has_returns
        lp_base, lp_zero = build_lp(base), build_lp(zeroed)
        # identical layout: no return block, same variable/row counts
        assert lp_zero.off_ret == -1 and lp_base.off_ret == -1
        assert lp_zero.n_vars == lp_base.n_vars
        assert len(lp_zero.b_ub) == len(lp_base.b_ub)
        r_base = solve(base, backend="simplex")
        r_zero = solve(zeroed, backend="simplex")
        assert r_zero.makespan == r_base.makespan  # bit-identical
        np.testing.assert_array_equal(r_zero.schedule.gamma, r_base.schedule.gamma)
        assert r_zero.schedule.ret_start is None


def test_positive_return_ratio_strictly_lengthens_the_schedule():
    rng = np.random.default_rng(9)
    for Platform in (Chain, Star):
        w = rng.uniform(0.2, 2.0, 3)
        z = rng.uniform(0.1, 1.0, 2)
        plat = Platform(w=w, z=z)
        vc, vp = [1.5, 0.8], [1.0, 2.0]
        r0 = solve(Instance(plat, Loads(vc, vp), q=1), backend="simplex")
        r1 = solve(Instance(plat, Loads(vc, vp, return_ratio=0.5), q=1),
                   backend="simplex")
        assert r1.ok and r0.ok
        assert r1.makespan > r0.makespan  # results must still travel back
        assert r1.schedule.ret_end is not None
        assert r1.schedule.ret_end.max() <= r1.makespan + 1e-9


# ------------------------------------------------- topology plumbing edges


def test_star_drop_processor_removes_worker_and_link():
    s = Star(w=[1.0, 2.0, 3.0], z=[0.1, 0.2], tau=[0.0, 0.5, 1.0],
             latency=[0.01, 0.02])
    s2 = s.drop_processor(1)
    np.testing.assert_array_equal(s2.w, [1.0, 3.0])
    np.testing.assert_array_equal(s2.z, [0.2])
    np.testing.assert_array_equal(s2.tau, [0.0, 1.0])
    with pytest.raises(ValueError):
        s.drop_processor(0)  # the master holds the data


def test_heuristics_reject_star_and_return_instances():
    from repro.core.heuristics import simple, single_inst

    star = star_bus_instance(w=[1.0, 2.0], z=0.3)
    with pytest.raises(ValueError, match="chain heuristic"):
        simple(star)
    chain_ret = Instance(Chain(w=[1.0, 2.0], z=[0.3]),
                         Loads([1.0], [1.0], return_ratio=0.5))
    with pytest.raises(ValueError, match="return"):
        single_inst(chain_ret)


def test_adversary_sweep_records_inf_for_star_elements():
    # the sweep contract — inf where a strategy failed — must hold on mixed
    # populations: star elements fail every chain heuristic without
    # aborting the sweep or losing the chain elements' makespans
    from repro.core.heuristics import adversary_sweep, simple

    chain = Instance(Chain(w=[1.0, 2.0], z=[0.3]), Loads([1.0], [1.0]))
    star = star_bus_instance(w=[1.0, 2.0], z=0.3)
    out = adversary_sweep([chain, star, chain], strategies={"SIMPLE": simple},
                          simulator="serial")
    mks = out["SIMPLE"]
    assert np.isfinite(mks[0]) and np.isfinite(mks[2]) and mks[0] == mks[2]
    assert np.isinf(mks[1])
