"""The batched simplex's host<->device boundary: one packed buffer each way.

A cold bucket goes to the device as one buffer and its results come back
as one (``engine/batched_simplex.py``).  These tests hold the packed path
to an unpacked reference bit for bit, keep the served program one jitted
program named ``jit__solve_batch`` with one array argument (the
benchmark's trace readers find it by that name, one execution per bucket),
and check the transfer counters.
"""

import glob
import re
from collections import Counter
from functools import partial

import jax
import numpy as np
import pytest

from repro.core.instance import random_instance
from repro.engine.arena import InstanceArena
from repro.engine.batched_lp import build_lp_bucket
from repro.engine.batched_simplex import (
    _demote_false_optimal,
    _pack_lp,
    _packed_lp_struct,
    _solve_batch,
    _solve_one,
    solve_simplex_batched,
)
from repro.jaxenv import x64
from repro.obs import metrics as obs_metrics

MAX_ITER = 20_000
FIELDS = ("x", "objective", "status", "iterations", "iterations_phase1",
          "iterations_phase2", "basis")


@partial(jax.jit, static_argnums=(5,))
def _unpacked(c, A_ub, b_ub, A_eq, b_eq, max_iter):
    return jax.vmap(_solve_one, in_axes=(0, 0, 0, 0, 0, None))(
        c, A_ub, b_ub, A_eq, b_eq, max_iter)


def _reference(args, lanes):
    """The unpacked program on ``lanes``: five arrays in, each of its seven
    outputs fetched on its own, then the same feasibility demotion."""
    sub = [a[lanes] for a in args]
    with x64():
        x, obj, st, it, it1, it2, basis = (
            np.asarray(o) for o in _unpacked(*sub, MAX_ITER))
    st = _demote_false_optimal(x, st, *sub[1:])
    return dict(zip(FIELDS, (x, obj, st, it, it1, it2, basis)))


def _lp_args(insts):
    (bucket,) = InstanceArena(insts, pad_shapes=False).buckets
    lp = build_lp_bucket(bucket)
    return (np.tile(lp.c, (bucket.B, 1)), lp.A_ub, lp.b_ub, lp.A_eq, lp.b_eq)


def _table2_chains(B, seed=0):
    """B 10-processor heterogeneous chains with latencies and 5 loads: the
    benchmark cell's shape."""
    rng = np.random.default_rng(seed)
    return _lp_args([random_instance(rng, m=10, n_loads=5, heterogeneous=True,
                                     with_latency=True) for _ in range(B)])


def _stars_with_returns(B=4, seed=1):
    rng = np.random.default_rng(seed)
    return _lp_args([random_instance(rng, m=6, n_loads=3, heterogeneous=True,
                                     with_latency=True, topology="star",
                                     return_ratio=0.5) for _ in range(B)])


def _infeasible_lane():
    """Optimal, infeasible (NaN x and objective) and unbounded lanes, with
    no equality rows."""
    c = np.array([[1.0, 1.0], [0.0, 1.0], [-1.0, 0.0]])
    A_ub = np.zeros((3, 2, 2))
    b_ub = np.zeros((3, 2))
    A_ub[0] = [[-1.0, 0.0], [0.0, -1.0]]
    b_ub[0] = [-1.0, -2.0]
    A_ub[1] = [[1.0, 0.0], [-1.0, 0.0]]
    b_ub[1] = [-1.0, -1.0]
    A_ub[2] = [[0.0, 1.0], [0.0, 0.0]]
    b_ub[2] = [1.0, 0.0]
    return (c, A_ub, b_ub, np.zeros((3, 0, 2)), np.zeros((3, 0)))


def _case(name):
    """(arguments, warm_basis) of one bucket."""
    if name.startswith("chain-B"):
        return _table2_chains(int(name[len("chain-B"):])), None
    if name == "star-returns":
        return _stars_with_returns(), None
    if name == "infeasible-lane":
        return _infeasible_lane(), None
    # warm-subset: the stars drift a little; lane 0 carries no seed, so the
    # cold remainder is a strict subset of the bucket
    c, A_ub, b_ub, A_eq, b_eq = _stars_with_returns()
    base = solve_simplex_batched(c, A_ub, b_ub, A_eq, b_eq)
    seeds = base.basis.copy()
    seeds[0] = -1
    return (c, A_ub * (1 + 1e-3), b_ub, A_eq, b_eq), seeds


@pytest.mark.parametrize("name", ["chain-B1", "chain-B8", "chain-B16",
                                  "star-returns", "infeasible-lane",
                                  "warm-subset"])
def test_packed_path_is_bit_identical_to_unpacked(name):
    args, seeds = _case(name)
    got = solve_simplex_batched(*args, warm_basis=seeds)
    cold = np.flatnonzero(~got.warm_started)
    if name == "warm-subset":
        assert 0 < cold.size < len(got.status)
    if name == "infeasible-lane":
        assert list(got.status) == [0, 1, 2] and np.isnan(got.x[1]).all()
    want = _reference(args, cold)
    for field in FIELDS:
        g = np.asarray(getattr(got, field))[cold]
        w = want[field]
        assert g.dtype == w.dtype and g.shape == w.shape, field
        assert g.tobytes() == w.tobytes(), field  # NaN payloads included


def _jitted_calls(tmp_path, fn):
    """The jitted programs ``fn`` calls, by name and count, off a CPU
    profiler trace (each call shows as a ``PjitFunction(<name>)`` event)."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    calls = Counter()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for event in line.events:
                m = re.fullmatch(r"PjitFunction\((.*)\)", event.name)
                if m:
                    calls[m.group(1)] += 1
    return calls


def test_served_program_is_one_jit_solve_batch_with_one_array(tmp_path):
    args = _table2_chains(2)
    B, n = args[0].shape
    mu, me = args[1].shape[1], args[3].shape[1]
    with x64():
        lowered = _solve_batch.lower(_packed_lp_struct(B, n, mu, me), n, mu,
                                     me, MAX_ITER)
    assert re.search(r"^module @jit__solve_batch\b", lowered.as_text(),
                     re.MULTILINE)
    (arg,) = jax.tree.leaves(lowered.args_info)
    assert arg.shape == _pack_lp(*args).shape

    solve_simplex_batched(*args)  # compiled before the traced calls
    one_call = _jitted_calls(tmp_path / "solve",
                             lambda: solve_simplex_batched(*args))
    assert set(one_call) == {"_solve_batch"}

    def direct():
        with x64():
            np.asarray(_solve_batch(_pack_lp(*args), n, mu, me, MAX_ITER))

    assert one_call == _jitted_calls(tmp_path / "direct", direct)


@pytest.fixture
def registry():
    reg = obs_metrics.MetricsRegistry()
    prev = obs_metrics.set_registry(reg)
    try:
        yield reg
    finally:
        obs_metrics.set_registry(prev)


def test_transfer_counters_count_one_crossing_each_way(registry):
    def counts():
        return {d: (registry.value("repro_simplex_transfers_total", direction=d),
                    registry.value("repro_simplex_transfer_bytes_total",
                                   direction=d))
                for d in ("to_device", "to_host")}

    args = _stars_with_returns()
    B, n = args[0].shape
    m_rows = args[1].shape[1] + args[3].shape[1]
    res = solve_simplex_batched(*args)
    assert counts() == {"to_device": (1, _pack_lp(*args).nbytes),
                        "to_host": (1, B * (n + 5 + m_rows) * 8)}

    warm = solve_simplex_batched(*args, warm_basis=res.basis)
    assert warm.warm_started.all()
    assert counts() == {"to_device": (1, _pack_lp(*args).nbytes),
                        "to_host": (1, B * (n + 5 + m_rows) * 8)}
