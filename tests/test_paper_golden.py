"""Golden-value regressions pinning the paper's artifacts.

Future backend work (new kernels, new lowerings, new solvers) must not
silently drift from the numbers the paper publishes:

  * the §3 motivating example — LP(Q=1) equals the §3.2 closed form, and
    LP(Q=2) recovers the hand schedule's 781/653 * lambda exactly;
  * Table 2 — the LP dominates every heuristic on the §6 instance family;
  * Theorem 1 — makespan is monotone non-increasing up the q ladder.

Golden constants are written out explicitly (not recomputed via the code
under test) so a regression in the closed forms cannot mask one in the
solver.
"""

import math

import numpy as np
import pytest

from repro.core.closed_form import example_instance, makespan_1
from repro.core.heuristics import (heuristic_b, multi_inst, simple,
                                   single_inst, single_load)
from repro.core.instance import random_instance
from repro.core.solver import solve
from repro.core.theory import q_monotonicity

# the paper's hand schedule for lambda = 3/4 finishes at (781/653) * (3/4)
GOLDEN_Q2 = 781.0 / 653.0 * 0.75  # 0.897013782542...
# the §3.2 single-installment schedule: 2*lam*(lam^2+lam+1)/(2lam^2+2lam+1)
GOLDEN_Q1 = 0.9568965517241379


# ------------------------------------------------------- motivating example


def test_motivating_example_q1_closed_form():
    lp = solve(example_instance(0.75, q=1))
    assert lp.ok
    assert abs(lp.makespan - GOLDEN_Q1) <= 1e-9
    assert abs(makespan_1(0.75) - GOLDEN_Q1) <= 1e-12


def test_motivating_example_q2_hand_schedule():
    lp = solve(example_instance(0.75, q=2))
    assert lp.ok
    assert abs(lp.makespan - GOLDEN_Q2) <= 1e-9


@pytest.mark.parametrize("backend", ["simplex", "batched", "auto"])
def test_motivating_example_same_golden_on_every_backend(backend):
    from repro.core.backends import SolveRequest, get_backend

    rep = get_backend(backend).solve(
        SolveRequest(instance=example_instance(0.75, q=2)))
    assert rep.ok
    assert abs(rep.makespan - GOLDEN_Q2) <= 1e-9


# ------------------------------------------------------ Table-2 domination


def _table2_instances():
    # the §6 protocol (scaled down): heterogeneous powers, anti-correlated
    # latencies, a spread of communication-to-computation ratios
    rng = np.random.default_rng(20260730)
    return [
        random_instance(rng, m=10, n_loads=5, q=1, comm_to_comp=ccr,
                        with_latency=True)
        for ccr in (0.1, 1.0, 10.0)
    ]


def test_lp_dominates_heuristics_on_table2_family():
    heuristics = [
        ("SIMPLE", simple),
        ("SINGLELOAD", single_load),
        ("SINGLEINST", single_inst),
        ("MULTIINST_100", lambda i: multi_inst(i, cap=100)),
        ("HEURISTIC_B", heuristic_b),
    ]
    for inst in _table2_instances():
        lp1 = solve(inst.with_q(1))
        assert lp1.ok
        for name, fn in heuristics:
            r = fn(inst)
            if getattr(r, "failed", False):
                continue  # a diverged heuristic dominates nothing
            assert lp1.makespan <= r.makespan * (1 + 1e-7) + 1e-9, (
                f"{name} beat the LP: {r.makespan} < {lp1.makespan}")


def test_motivating_example_heuristic_goldens():
    # Table-2-style golden pins on the lambda=3/4 example (exact rationals)
    inst = example_instance(0.75)
    assert abs(simple(inst).makespan - 1.375) <= 1e-9
    assert abs(single_inst(inst).makespan - 0.9825) <= 1e-9
    assert abs(multi_inst(inst, cap=300).makespan - 0.9) <= 1e-9
    lp2 = solve(example_instance(0.75, q=2))
    assert lp2.makespan <= 0.9  # the LP beats the best heuristic


# -------------------------------------------------------- Theorem-1 ladder


def test_theorem1_q_ladder_monotone_and_golden():
    qs = [1, 2, 3, 4]
    ms = q_monotonicity(example_instance(0.75), qs)
    # golden anchors at both ends of the ladder
    assert abs(ms[0] - GOLDEN_Q1) <= 1e-9
    assert abs(ms[1] - GOLDEN_Q2) <= 1e-9
    diffs = np.diff(ms)
    tol = 1e-7 * np.maximum(np.abs(np.asarray(ms[:-1])), 1.0)
    assert (diffs <= tol).all(), ms
    assert (diffs < -1e-12).any(), "q ladder should strictly improve somewhere"


def test_theorem1_q_ladder_random_instance():
    rng = np.random.default_rng(5)
    inst = random_instance(rng, m=5, n_loads=3, q=1)
    ms = q_monotonicity(inst, [1, 2, 3])
    diffs = np.diff(ms)
    tol = 1e-7 * np.maximum(np.abs(np.asarray(ms[:-1])), 1.0)
    assert (diffs <= tol).all(), ms
