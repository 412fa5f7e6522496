"""CPU tests of the benchmark harness: the manifest's rules, the plain
reference, the shape count behind the roofline, the reductions from records
and traces to metrics, the chip guard, and the comparison that decides
``correct`` (the lower-precision control and planted faults must fail it)."""

from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import check  # noqa: E402
import devtrace  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@pytest.fixture(scope="module")
def man():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# ---------------------------------------------------------------- manifest


def test_manifest_names_units_and_files(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    metrics = man["end_to_end"] + man["per_layer"]
    for entry in man["configs"] + man["workloads"] + metrics:
        assert NAME.fullmatch(entry["name"]), entry["name"]
    for m in metrics:
        assert UNIT.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (man["configs"], man["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    for cfg in man["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        assert body["name"] == cfg["name"]
        assert sorted(cfg["reduced"]) == sorted(body["reduced"])
        assert set(body["limits"]) == set(check.NUMBERS)
    for cell in man["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
        assert cell["config"] in {c["name"] for c in man["configs"]}
        assert cell["chips"] in (1, 4)
    for m in man["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
    assert any(m["name"] == "setup_s" for m in man["end_to_end"])
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")


def test_every_per_layer_metric_moves_a_metric_its_cells_report(man):
    cells = {c["name"] for c in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        listed = m.get("workloads", sorted(cells))
        assert set(listed) <= cells
        reporters = e2e[m["moves"]].get("workloads", sorted(cells))
        assert set(listed) <= set(reporters), m["name"]


# --------------------------------------------------------------- reference


def _cfg(topology, m, n_loads, return_ratio):
    return {"topology": topology, "m": m, "n_loads": n_loads,
            "power_flop_per_s": [10e6, 100e6], "link_bit_per_s": [10e6, 100e6],
            "latency_s": [1e-4, 1e-3], "v_comp_flop": [6e9, 60e9],
            "return_ratio": return_ratio,
            "comm_to_comp": [0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 50.0, 100.0]}


@pytest.mark.parametrize("topology,m,loads,ret", [
    ("chain", 10, 6, 0.0), ("star", 10, 5, 0.5), ("chain", 4, 3, 0.5),
    ("star", 3, 4, 0.0)])
def test_reference_agrees_with_the_program(topology, m, loads, ret):
    from repro.api import Policy, Problem, Session
    from repro.core.simulator import simulate

    cfg = _cfg(topology, m, loads, ret)
    rng = np.random.default_rng(7)
    for ratio in (0.01, 1.0, 100.0):
        p = traffic.draw_problem(rng, cfg, ratio)
        best, gamma = reference.solve(p)
        prob = Problem(**p)
        art = Session(policy=Policy(backend="auto")).solve(prob)
        assert abs(art.makespan - best) <= 1e-9 * best
        assert reference.replay(p, gamma) == simulate(prob.to_instance(1), gamma).makespan
        assert abs(reference.replay(p, gamma) - best) <= 1e-9 * best


# ------------------------------------------------------------ shape count


@pytest.mark.parametrize("topology,m,loads,ret", [
    ("chain", 10, 20, 0.0), ("star", 10, 10, 0.5), ("chain", 5, 4, 0.5),
    ("star", 6, 3, 0.0), ("chain", 2, 3, 0.0)])
def test_tableau_count_matches_the_engine_lp(topology, m, loads, ret):
    from repro.api import Problem
    from repro.engine.arena import InstanceArena
    from repro.engine.batched_lp import build_lp_bucket

    cfg = _cfg(topology, m, loads, ret)
    rng = np.random.default_rng(1)
    insts = [Problem(**traffic.draw_problem(rng, cfg, 1.0)).to_instance(1)
             for _ in range(3)]
    lp = build_lp_bucket(InstanceArena(insts).buckets[0])
    n, ub, eq = roofline.lp_shape(topology, m, loads, ret > 0)
    assert (lp.n_vars, lp.A_ub.shape[1], lp.A_eq.shape[1]) == (n, ub, eq)
    R, C = roofline.tableau(cfg)
    assert (R, C) == (ub + eq + 1, n + ub + 2)
    # two lanes, 5+1 and 9+1 trips in phase 1, 2+1 in phase 2
    assert roofline.least_bytes(cfg, [[(5, 2), (9, 0)]]) == (10 + 3) * 2 * 2 * R * C * 8
    assert roofline.trips([roofline.ITER_CAP]) == roofline.ITER_CAP


def test_config_shapes_are_the_documented_tableaux():
    chain = traffic.load_config("chain-table2-n5")
    assert roofline.lp_shape("chain", 10, 20, False) == (581, 863, 20)
    assert roofline.lp_shape("chain", 10, 5, False) == (146, 203, 5)
    assert roofline.lp_shape("star", 10, 10, True) == (381, 467, 10)
    assert roofline.tableau(chain) == (209, 351)
    assert 16 * np.prod(roofline.tableau(chain)) * 8 == 9_389_952


# ------------------------------------------------------ records -> metrics


def test_tails_count_unfinished_requests_at_their_age():
    recs = [{"t_send": 0.0, "t_done": 1.0, "status": "optimal", "rescued": False},
            {"t_send": 0.5, "t_done": 1.5, "status": "optimal", "rescued": True},
            {"t_send": 9.0, "t_done": 10.5, "status": "optimal", "rescued": False}]
    recs += [{"t_send": 8.0}] * 2  # never answered: age at the drain's end
    m = bench_run.end_to_end({"records": recs, "drain_s": 20.0}, 10.0)
    assert m["plans_per_s"]["value"] == pytest.approx(0.2)  # 2 done by 10 s
    assert m["device_plan_share"]["value"] == pytest.approx(200 / 3)
    lat = sorted([1000.0, 1000.0, 1500.0, 22000.0, 22000.0])
    assert m["latency_p50_ms"]["value"] == pytest.approx(lat[2])
    assert m["latency_p95_ms"]["value"] == pytest.approx(22000.0)


@pytest.mark.parametrize("shift", [0.0, 7.0])
def test_trace_reduction_and_gap_labels(shift):
    """The window starts ``shift`` ms after the trace; a program running
    before it counts only from its start."""
    ms = 1e6
    modules = [[(-3 * ms, 2 * ms, "jit__sim_batch"),
                (10 * ms, 40 * ms, "jit__solve_batch"),
                (30 * ms, 50 * ms, "jit__sim_batch"),
                (80 * ms, 90 * ms, "jit__solve_batch")]]
    modules = [[(s + shift * ms, e + shift * ms, n) for s, e, n in modules[0]]]
    red = devtrace.reduce(modules, shift * ms, (100 + shift) * ms)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.052)
    assert red["programs"] == pytest.approx({"jit__solve_batch": 0.04,
                                             "jit__sim_batch": 0.022})
    assert [round((e - s) / ms) for s, e, _ in red["gaps"]] == [8, 30, 10]
    spans = [{"name": "serve.request_batch", "start": shift * ms,
              "end": (95 + shift) * ms},
             {"name": "engine.serial_rescue", "start": (55 + shift) * ms,
              "end": (75 + shift) * ms}]
    labels = devtrace.label_gaps(red["gaps"], spans, top=2)
    assert labels[0] == ["engine.serial_rescue (after jit__sim_batch)",
                         pytest.approx(0.03)]
    assert labels[1][0] == "serve.request_batch (after jit__solve_batch)"
    run = types.SimpleNamespace(trace=red)
    idle = bench_run._reader("device.idle_share")
    assert idle(run) == pytest.approx(48.0)
    assert idle(types.SimpleNamespace(trace=devtrace.reduce([[]], 0.0, 1e9))) is None


def test_per_layer_readers_on_spans_and_counters():
    spans = [{"name": "serve.request_batch", "dur_us": 1e3, "args": {"n": 4}},
             {"name": "serve.request_batch", "dur_us": 1e3, "args": {"n": 12}},
             {"name": "engine.simplex", "dur_us": 3e6, "args": {"B": 12}}]
    counters = {"repro_simplex_pivots_total{path=batched,phase=1}": 900.0,
                "repro_simplex_pivots_total{path=batched,phase=2}": 300.0,
                "repro_simplex_status_total{path=batched,status=optimal}": 11.0,
                "repro_simplex_status_total{path=batched,status=false_optimal}": 1.0}
    run = types.SimpleNamespace(spans=spans, mix={"clients": 16},
                                counters=counters, counter=bench_run.counter,
                                compiles=[("/jax/core/compile/jaxpr_to_mlir_module_duration", 0, 0)])
    read = bench_run._reader
    assert read("serve.batch_fill")(run) == pytest.approx(50.0)
    assert read("engine.simplex_ms_per_plan")(run) == pytest.approx(250.0)
    assert read("simplex.pivots_per_plan")(run) == pytest.approx(100.0)
    assert read("device.compiles_in_window")(run) == 1.0
    empty = types.SimpleNamespace(spans=[], counters={}, counter=bench_run.counter,
                                  mix={"clients": 16})
    for name in ("serve.batch_fill", "engine.simplex_ms_per_plan",
                 "simplex.pivots_per_plan"):
        assert read(name)(empty) is None


def test_clock_shift_is_the_least_lead_of_an_execution_over_its_request():
    runs = [(700.0, 750.0, "jit__solve_batch"), (760.0, 770.0, "jit__sim_batch"),
            (905.0, 990.0, "jit__solve_batch")]
    assert devtrace.clock_shift(runs, "jit__solve_batch", [200.0, 50.0]) == 650.0
    assert devtrace.clock_shift(runs, "jit__solve_batch", []) == 0.0
    assert devtrace.clock_shift([], "jit__solve_batch", [1.0]) == 0.0


def test_held_until_ends_where_the_profiler_dropped_executions():
    runs = [(0, 5, "jit__solve_batch"), (6, 7, "jit__sim_batch"),
            (9, 12, "jit__solve_batch")]
    assert devtrace.held_until(runs, "jit__solve_batch", 2, 20.0) == 20.0
    assert devtrace.held_until(runs, "jit__solve_batch", 3, 20.0) == 12
    assert devtrace.held_until([], "jit__solve_batch", 0, 20.0) == 20.0


def _roofline_run(lanes_per_bucket, runs, keep_all=True):
    """A traced run whose buckets answer in order, bucket k holding the
    lanes ``lanes_per_bucket[k]`` as (phase-1, phase-2) pivots."""
    records, spans = [], []
    for k, lanes in enumerate(lanes_per_bucket):
        spans.append({"name": "engine.simplex", "start": 10.0 * k,
                      "args": {"B": len(lanes)}})
        for j, piv in enumerate(lanes):
            plan = {"bucket_id": [0.1 * k, 0.2 * k], "pivots": list(piv)}
            records.append({"t_done": float(k) + 0.01 * j, "status": "optimal",
                            "plan": plan if keep_all or j else None})
    cfg = _cfg("chain", 4, 3, 0.0)
    return types.SimpleNamespace(
        cfg=cfg, records=records[::-1], spans=spans[::-1],
        modules=[runs], window_ns=(0.0, 1e9),
        peaks={"hbm_bytes_per_s": 1e9})


def test_roofline_reader_lines_up_executions_with_buckets():
    read = bench_run._reader("solve_batch_roofline")
    R, C = roofline.tableau(_cfg("chain", 4, 3, 0.0))
    lanes = [[(4, 1), (2, 3)], [(9, 0)], [(1, 1)]]
    # three executions; the last may be cut short and is left out
    runs = [(1e6, 2e6, "jit__solve_batch"), (2e6, 2.5e6, "jit__sim_batch"),
            (3e6, 7e6, "jit__solve_batch"), (8e6, 9e6, "jit__solve_batch")]
    least = ((5 + 4) * 2 * 2 + (10 + 1) * 2 * 1) * R * C * 8
    assert read(_roofline_run(lanes, runs)) == pytest.approx(
        100.0 * least / 1e9 / 5e-3)
    assert read(_roofline_run(lanes, runs, keep_all=False)) is None
    assert read(_roofline_run(lanes[:1], runs)) is None  # more runs than buckets
    swapped = _roofline_run(lanes, runs)
    swapped.spans[-1]["args"]["B"] = 1  # the first span's batch disagrees
    assert read(swapped) is None
    assert read(_roofline_run(lanes, runs[:1])) is None


# --------------------------------------------------------------- chip guard


def test_runner_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "chain-table2-n5.cold", "--seed", str(2**33 + 5), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ------------------------------------------------------------- comparison


def _records(cfg, seed, n):
    """Records of ``n`` answered requests whose plans are the reference's."""
    problems = [traffic.request(cfg, seed, 0, k) for k in range(n)]
    recs = []
    for k, p in enumerate(problems):
        _, gamma = reference.solve(p)
        recs.append({"problem": k, "t_send": 0.0, "t_done": 1.0,
                     "status": "optimal", "rescued": False,
                     "plan": {"gamma": gamma.tolist(), "problem": dict(p),
                              "makespan": reference.replay(p, gamma)}})
    return problems, recs


@pytest.mark.parametrize("topology,ret", [("chain", 0.0), ("star", 0.5)])
def test_lower_precision_control_fails_the_comparison(topology, ret):
    cfg = dict(traffic.load_config("chain-table2-n5"), n_loads=4,
               topology=topology, return_ratio=ret)
    problems, recs = _records(cfg, 2**32 + 3, 6)
    ok, numbers, _ = check.compare(problems, recs, cfg["limits"])
    assert ok, numbers
    bad, numbers, _ = check.compare(
        problems, check.control_plans(problems, recs), cfg["limits"])
    assert not bad, numbers


def test_comparison_flags_lost_and_misaddressed_answers():
    cfg = dict(traffic.load_config("chain-table2-n5"), n_loads=3, m=4)
    problems, recs = _records(cfg, 11, 3)
    recs[1] = dict(recs[1], plan=dict(recs[1]["plan"],
                                      problem=recs[0]["plan"]["problem"]))
    recs.append({"problem": 0, "t_send": 0.5})  # never answered
    ok, numbers, counts = check.compare(problems, recs, cfg["limits"])
    assert not ok
    assert numbers["wrong_problem"]["value"] == 1
    assert numbers["unanswered"]["value"] == 1
    assert counts["checked"] == 3


# ------------------------------------------ whole runs with planted faults


def _tiny_run(monkeypatch, capsys, tmp_path, cell):
    """One run of ``cell`` on the CPU at a tiny size, with the chip guard
    and the peaks table bypassed; returns the result line."""
    import jax

    # the harness defaults these for its own process; keep them per test
    for name, value in (("JAX_COMPILATION_CACHE_DIR", str(tmp_path)),
                        ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0"),
                        ("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")):
        monkeypatch.setenv(name, value)

    real_cfg, real_mix = traffic.load_config, traffic.load_mix

    def cfg(name):
        return dict(real_cfg(name), m=3, n_loads=2)

    def mix(name):
        return dict(real_mix(name), clients=2)

    monkeypatch.setattr(traffic, "load_config", cfg)
    monkeypatch.setattr(traffic, "load_mix", mix)
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(bench_run, "DRAIN_S", 20.0)
    args = types.SimpleNamespace(workload=cell, seed=2**31 + 99, seconds=1.0, trace=0)
    assert bench_run.run(args, require_chip=lambda n: jax.devices()[:n]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _altered_answer(monkeypatch):
    """Every plan's fractions altered where the engine produces them."""
    from repro.engine import service

    real = service._result_from_gamma

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.schedule = dataclasses.replace(
            res.schedule, gamma=res.schedule.gamma * 1.001)
        return res

    monkeypatch.setattr(service, "_result_from_gamma", altered)


def _half_batch(monkeypatch):
    """The worker solves the first half of each batch (rounded down) and
    hands the rest plans it already has: this batch's, or the last one's."""
    from repro.serve.server import PlanServer

    real = PlanServer._solve_batch
    seen: list = []

    def half(session, jobs):
        keep = len(jobs) // 2 if seen else len(jobs)
        arts = real(session, jobs[:keep])
        spare = arts or seen[-1:]
        seen[:] = arts or seen
        return arts + [spare[i % len(spare)] for i in range(len(jobs) - keep)]

    monkeypatch.setattr(PlanServer, "_solve_batch", staticmethod(half))


def _stale_replay(monkeypatch):
    """The device replay hands back its first result again and again."""
    from repro.engine import service

    real = service.simulate_bucket
    first: list = []

    def stale(bucket, gamma, **kw):
        out = real(bucket, gamma, **kw)
        if not first or first[0][-1].shape != out[-1].shape:
            first[:] = [out]
        return first[0]

    monkeypatch.setattr(service, "simulate_bucket", stale)


@pytest.mark.parametrize("cell,fault", [
    ("chain-table2-n5.cold", None),
    ("chain-table2-n5.cold", _altered_answer), ("chain-table2-n5.cold", _half_batch),
    ("chain-table2-n5.cold", _stale_replay)])
def test_run_is_correct_unless_the_timed_path_is_broken(monkeypatch, capsys,
                                                         tmp_path, cell, fault):
    if fault is not None:
        fault(monkeypatch)
    result = _tiny_run(monkeypatch, capsys, tmp_path, cell)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0
