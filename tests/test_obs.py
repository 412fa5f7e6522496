"""Observability subsystem tests (repro.obs + its wiring, DESIGN.md §8):

* tracer: span nesting/balance under exceptions, the disabled no-op fast
  path (singleton identity — no allocation), Chrome-trace export validity;
* metrics: deterministic snapshots, label rendering, histograms, the
  Prometheus text exposition, the HTTP exposition server, NullRegistry;
* wiring: engine telemetry on reports/artifacts (bit-stable v2 round-trip,
  v1 documents still bit-stable), structured provenance events on the
  serial-rescue / error paths, unified stats shims.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from repro.obs import metrics as om
from repro.obs import trace as ot


@pytest.fixture
def registry(monkeypatch):
    """A fresh process registry for the duration of one test."""
    reg = om.MetricsRegistry()
    prev = om.set_registry(reg)
    yield reg
    om.set_registry(prev)


@pytest.fixture
def tracer():
    tr = ot.Tracer()
    prev = ot.activate(tr)
    yield tr
    ot.activate(prev)


def _chain_problem(seed=0, m=3):
    from repro.api import Problem

    rng = np.random.default_rng(seed)
    return Problem(
        w=rng.uniform(1.0, 3.0, m).tolist(),
        z=rng.uniform(0.05, 0.3, m - 1).tolist(),
        v_comm=rng.uniform(0.5, 1.5, 2).tolist(),
        v_comp=rng.uniform(0.5, 1.5, 2).tolist(),
    )


# --------------------------------------------------------------------------
# tracer
# --------------------------------------------------------------------------


def test_spans_nest_and_balance(tracer):
    with ot.span("outer", k=1):
        with ot.span("inner"):
            pass
        with ot.span("inner"):
            pass
    evs = tracer.events()
    assert [e["name"] for e in evs] == ["outer", "inner", "inner"]
    outer, in1, in2 = evs
    # timestamp containment is the nesting relation Chrome/Perfetto use
    assert outer["ts_us"] <= in1["ts_us"]
    assert in1["ts_us"] + in1["dur_us"] <= outer["ts_us"] + outer["dur_us"] + 1e-6
    assert in2["ts_us"] >= in1["ts_us"] + in1["dur_us"] - 1e-6
    assert outer["args"] == {"k": 1}


def test_spans_balance_under_exceptions(tracer):
    with pytest.raises(ValueError):
        with ot.span("outer"):
            with ot.span("inner"):
                raise ValueError("boom")
    evs = tracer.events()
    # both spans closed and recorded despite the propagating exception…
    assert sorted(e["name"] for e in evs) == ["inner", "outer"]
    # …and each is tagged with the exception class
    assert all(e["args"]["error"] == "ValueError" for e in evs)


def test_span_set_attaches_args(tracer):
    with ot.span("s") as sp:
        sp.set(rows=7)
    assert tracer.events()[0]["args"] == {"rows": 7}


def test_disabled_tracer_is_allocation_free_noop():
    assert ot.get_tracer() is None  # no tracer active in this test
    # the disabled fast path hands out ONE shared singleton: identity (not
    # just equality) across calls proves no per-call span allocation
    spans = {id(ot.span(f"name-{i}", a=i)) for i in range(100)}
    assert spans == {id(ot.NOOP_SPAN)}
    with ot.span("ignored") as sp:
        assert sp is ot.NOOP_SPAN
        sp.set(anything="goes")


def test_chrome_trace_export_valid(tmp_path, tracer):
    with ot.span("a"):
        with ot.span("b", n=2):
            pass
    path = tmp_path / "trace.json"
    tracer.save(str(path))
    d = json.loads(path.read_text())  # valid JSON by construction
    evs = d["traceEvents"]
    assert d["displayTimeUnit"] == "ms"
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "repro"
    complete = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"a", "b"}
    for e in complete:
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
    assert tracer.total_us("a") >= tracer.total_us("b") > 0.0


def test_activate_restores_previous():
    t1, t2 = ot.Tracer(), ot.Tracer()
    assert ot.activate(t1) is None
    try:
        assert ot.activate(t2) is t1
        with ot.span("x"):
            pass
        assert len(t2) == 1 and len(t1) == 0
    finally:
        ot.activate(None)


def test_record_is_a_complete_event_on_the_given_thread(tracer):
    import threading
    import time

    with ot.span("outer"):
        t0 = time.perf_counter_ns()
        t1 = t0 + 2_000_000
        ot.record("wait", t0, t1, request=3)
        ot.record("elsewhere", t0, t1, tid=12345)
    outer, wait, other = (next(e for e in tracer.events() if e["name"] == n)
                          for n in ("outer", "wait", "elsewhere"))
    assert wait["dur_us"] == pytest.approx(2000.0)
    assert wait["args"] == {"request": 3}
    assert wait["tid"] == outer["tid"] == threading.get_ident()
    assert other["tid"] == 12345
    # starts inside its parent; events() lists the parent first
    names = [e["name"] for e in tracer.events()]
    assert names.index("outer") < names.index("wait")
    assert outer["ts_us"] <= wait["ts_us"] <= outer["ts_us"] + outer["dur_us"]


def test_record_without_a_tracer_does_nothing():
    assert ot.get_tracer() is None
    ot.record("ignored", 0, 10, request=1)  # no tracer, no error


def test_annotate_mirrors_each_span_into_the_profiler(monkeypatch):
    import jax.profiler

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = ot.Tracer(annotate=True)
    prev = ot.activate(tr)
    try:
        with ot.span("a"):
            with ot.span("b"):
                pass
        ot.record("c", 0, 1)  # an interval no thread holds: not mirrored
    finally:
        ot.activate(prev)
    assert seen == [("enter", "a"), ("enter", "b"), ("exit", "b"), ("exit", "a")]
    assert len(tr) == 3


def test_tracer_without_annotations_imports_no_jax():
    import os
    import subprocess
    import sys

    code = ("import sys; from repro.obs import trace as ot; "
            "tr = ot.Tracer(); ot.activate(tr)\n"
            "with ot.span('x'): pass\n"
            "assert len(tr) == 1 and 'jax' not in sys.modules")
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60,
                   env=dict(os.environ, PYTHONPATH=src))


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------


def test_snapshot_deterministic_across_identical_runs():
    def run():
        reg = om.MetricsRegistry()
        reg.inc("repro_x_total", path="b")
        reg.inc("repro_x_total", 2.0, path="a")
        reg.set_gauge("repro_g_ratio", 0.25, topology="chain", m=3)
        reg.observe("repro_lat_seconds", 0.002, stage="s")
        reg.observe("repro_lat_seconds", 0.2, stage="s")
        return reg.snapshot()

    s1, s2 = run(), run()
    assert s1 == s2
    assert list(s1) == sorted(s1)  # keys sorted
    assert s1["repro_x_total{path=a}"] == 2.0
    assert s1["repro_g_ratio{m=3,topology=chain}"] == 0.25  # labels sorted
    assert s1["repro_lat_seconds_count{stage=s}"] == 2
    assert s1["repro_lat_seconds_sum{stage=s}"] == pytest.approx(0.202)


def test_counter_gauge_value_reads():
    reg = om.MetricsRegistry()
    reg.inc("c_total", kind="x")
    reg.inc("c_total", kind="x")
    reg.set_gauge("g", 7.0)
    assert reg.value("c_total", kind="x") == 2.0
    assert reg.value("c_total", kind="y") == 0.0
    assert reg.value("g") == 7.0
    reg.clear()
    assert reg.snapshot() == {}


def test_histogram_buckets_cumulative_in_prometheus_text():
    reg = om.MetricsRegistry()
    reg.register_histogram("h_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        reg.observe("h_seconds", v)
    snap = reg.snapshot()
    assert snap["h_seconds_bucket{le=0.01}"] == 1  # snapshot: per-bucket
    assert snap["h_seconds_bucket{le=+Inf}"] == 4
    text = reg.prometheus_text()
    assert "# TYPE h_seconds histogram" in text
    assert 'h_seconds_bucket{le="0.1"} 2' in text  # exposition: cumulative
    assert 'h_seconds_bucket{le="+Inf"} 4' in text
    assert "h_seconds_count 4" in text


def test_prometheus_text_counters_and_gauges():
    reg = om.MetricsRegistry()
    reg.inc("repro_cache_hits_total", 3)
    reg.set_gauge("repro_waste_ratio", 0.5, topology="star")
    text = reg.prometheus_text()
    assert "# TYPE repro_cache_hits_total counter" in text
    assert "repro_cache_hits_total 3" in text
    assert 'repro_waste_ratio{topology="star"} 0.5' in text


def test_null_registry_drops_everything():
    reg = om.NullRegistry()
    reg.inc("a_total")
    reg.set_gauge("g", 1.0)
    reg.observe("h", 0.1)
    assert reg.snapshot() == {}


def test_metrics_http_server():
    import urllib.request

    reg = om.MetricsRegistry()
    reg.inc("repro_served_total")
    server = om.start_metrics_server(0, registry=reg)  # ephemeral port
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "repro_served_total 1" in body
    finally:
        server.shutdown()


# --------------------------------------------------------------------------
# wiring: engine telemetry, cache counters, stats shims
# --------------------------------------------------------------------------


def test_engine_telemetry_and_metrics(registry):
    from repro.api import Policy, Session

    s = Session(policy=Policy(backend="batched", installments=2))
    art = s.solve(_chain_problem())
    assert art.ok and art.version == 2
    tel = art.telemetry
    assert tel["bucket"]["topology"] == "chain"
    assert tel["lp"]["status"] == "optimal"
    assert tel["lp"]["pivots_phase1"] >= 0 and tel["lp"]["pivots_phase2"] > 0
    for k in ("cache_lookup_s", "pack_s", "lp_build_s", "simplex_s", "replay_s"):
        assert tel["stages"][k] >= 0.0
    snap = registry.snapshot()
    assert snap["repro_engine_bulk_solves_total{path=batched}"] == 1.0
    assert snap["repro_session_submits_total"] == 0.0 if "repro_session_submits_total" in snap else True
    assert registry.value("repro_simplex_status_total", status="optimal", path="batched") == 1.0
    assert registry.value("repro_simplex_pivots_total", phase="2", path="batched") > 0
    # the second identical solve is a cache hit, counted AND marked in telemetry
    art2 = s.solve(_chain_problem())
    assert art2.cache_hit and art2.telemetry["cache_hit"] is True
    assert registry.value("repro_cache_hits_total") == 1.0


def test_cache_evictions_counted(registry):
    from repro.engine.cache import CachedSolution, SolutionCache

    c = SolutionCache(max_entries=2)
    for i in range(4):
        c.put(f"k{i}", CachedSolution(gamma=np.zeros((1, 1)), lp_makespan=1.0,
                                      backend="batched"))
    assert c.evictions == 2
    assert registry.value("repro_cache_evictions_total") == 2.0
    # the historical dict shape is frozen (exact-equality contract elsewhere)
    assert set(c.stats()) == {"entries", "hits", "misses", "hit_rate"}


def test_stats_shims_share_one_schema(registry):
    from repro.api import Policy, Session

    s = Session(policy=Policy(backend="batched", installments=2))
    s.submit(_chain_problem())
    s.flush()
    assert registry.value("repro_session_submits_total") == 1.0
    assert registry.value("repro_session_flushes_total") == 1.0
    # the deprecated dict shims still carry their historical keys
    assert s.stats()["flushes"] == 1
    backend = s.backend("batched")
    bs = backend.stats()
    assert bs["backend"] == "batched" and set(bs["cache"]) >= {"hits", "misses"}


def test_session_metrics_isolation():
    from repro.api import Policy, Session

    mine = om.MetricsRegistry()
    s = Session(policy=Policy(backend="batched", installments=2), metrics=mine)
    s.submit(_chain_problem())
    s.flush()
    assert mine.value("repro_session_submits_total") == 1.0
    assert om.get_registry().value("repro_session_submits_total") == 0.0 or \
        om.get_registry() is not mine  # pinned registry, not the process one


# --------------------------------------------------------------------------
# artifact v2: telemetry round-trip + structured events
# --------------------------------------------------------------------------


def test_artifact_telemetry_roundtrip_bitstable(registry):
    from repro.api import Policy, Session
    from repro.api.artifact import PlanArtifact

    s = Session(policy=Policy(backend="batched", installments=2))
    art = s.solve(_chain_problem())
    assert art.telemetry is not None
    j = art.to_json()
    art2 = PlanArtifact.from_json(j)
    assert art2.to_json() == j  # bit-stable, telemetry included
    assert art2.telemetry == art.telemetry
    assert art2.version == 2


def test_artifact_v1_documents_still_bitstable(registry):
    from repro.api import Policy, Session
    from repro.api.artifact import PlanArtifact

    s = Session(policy=Policy(backend="batched", installments=2))
    d = s.solve(_chain_problem()).to_dict()
    del d["events"], d["telemetry"]
    d["version"] = 1
    j1 = json.dumps(d, sort_keys=True, separators=(",", ":"), allow_nan=True)
    art = PlanArtifact.from_json(j1)
    assert art.version == 1 and art.events == () and art.telemetry is None
    assert art.to_json() == j1  # v1 keys only — the old round-trip holds


def test_artifact_unknown_version_refused():
    from repro.api.artifact import PlanArtifact

    with pytest.raises(ValueError, match="version"):
        PlanArtifact.from_dict({"version": 99})


def test_serial_rescue_structured_event(registry, monkeypatch):
    """Force the batched simplex to fail certification -> the element is
    rescued serially, recorded as a structured serial-rescue event with the
    solver's reason, and counted in the fallback metric."""
    import repro.engine.service as svc
    from repro.api import Policy, Session

    real = svc.solve_simplex_batched

    def sabotaged(c, A_ub, b_ub, A_eq, b_eq, **kw):
        res = real(c, A_ub, b_ub, A_eq, b_eq, **kw)
        res.status = np.full_like(res.status, 3)  # iteration_limit everywhere
        return res

    monkeypatch.setattr(svc, "solve_simplex_batched", sabotaged)
    s = Session(policy=Policy(backend="batched", installments=2))
    art = s.solve(_chain_problem())
    assert art.ok  # rescued — the engine is never a correctness compromise
    (ev,) = art.events
    assert ev["kind"] == "serial-rescue"
    assert ev["reason"] == "iteration_limit"
    assert art.fallback_events == (f"served_by:{ev['backend']}",)
    assert art.telemetry["serial_rescue"]["reason"] == "iteration_limit"
    assert art.telemetry["serial_rescue"]["seconds"] >= 0.0
    assert registry.value("repro_engine_fallback_total", path="batched",
                          reason="iteration_limit") == 1.0
    assert registry.value("repro_session_events_total", kind="serial-rescue") == 1.0


def test_pallas_degrade_structured_event(registry, monkeypatch):
    """Where the fused kernels cannot run, 'pallas' no longer degrades to
    the plain batched path: selecting it raises with the probe's reason,
    and nothing is served or recorded as a degrade."""
    import repro.kernels.ops as kops
    from repro.api import Policy, Session

    monkeypatch.setattr(kops, "scheduling_kernels_error",
                        lambda: "NotImplementedError: refused by the lowering")
    s = Session(policy=Policy(backend="pallas", installments=2))
    with pytest.raises(RuntimeError, match="refused by the lowering"):
        s.solve(_chain_problem())
    assert not any("degrade" in k for k in registry.snapshot())
    assert registry.value("repro_session_events_total", kind="degrade") == 0.0


def test_error_artifact_preserves_class_and_truncates_at_word(registry):
    """The error path keeps the exception class out of the truncation's way
    and never cuts mid-word (the historical [:200] did both)."""
    from repro.core.backends import SolverBackend
    from repro.api import Policy, Session

    long_msg = ("wedged " * 120).strip()  # ~840 chars of word-y detail

    class Exploding(SolverBackend):
        name = "exploding"

        def solve_many(self, requests):
            try:
                raise KeyError("root-cause")
            except KeyError as root:
                raise RuntimeError(long_msg) from root

    s = Session(policy=Policy(installments=2))
    t = s.submit(_chain_problem(), backend=Exploding())
    with pytest.raises(RuntimeError):
        s.flush()
    art = t.result()
    assert art.status == "error" and art.backend == "exploding"
    (ev,) = art.events
    assert ev["kind"] == "error"
    assert ev["error_type"] == "RuntimeError"
    assert ev["error_chain"] == ["RuntimeError", "KeyError"]  # cause preserved
    assert ev["reason"].endswith("...[truncated]")
    body = ev["reason"][: -len(" ...[truncated]")]
    assert set(body.split()) == {"wedged"}  # word-boundary cut: no "wedg"
    # the legacy string shim keeps class + message too
    assert art.fallback_events[0].startswith("error:RuntimeError: wedged")
    assert registry.value("repro_session_errors_total", backend="exploding") == 1.0
    # errors round-trip through the artifact like any other provenance
    from repro.api.artifact import PlanArtifact

    j = art.to_json()
    assert PlanArtifact.from_json(j).to_json() == j


def test_padding_waste_gauge(registry):
    from repro.core.instance import random_instance
    from repro.engine.arena import pack_instances

    inst = random_instance(np.random.default_rng(0), m=3, n_loads=1, q=3)
    pack_instances([inst], pad_shapes=True)  # m=3 -> 4, T=3 -> 4
    waste = registry.value("repro_engine_bucket_padding_waste_ratio",
                           topology="chain", m=3, T=3, m_pad=4, T_pad=4)
    assert waste == pytest.approx(1.0 - 9.0 / 16.0)
    pack_instances([inst], pad_shapes=False)
    assert registry.value("repro_engine_bucket_padding_waste_ratio",
                          topology="chain", m=3, T=3, m_pad=3, T_pad=3) == 0.0


def test_traced_session_run_covers_engine(registry):
    """A traced Session chain run emits the engine-stage spans the flight
    recorder promises (their times on the chip are read by the traced
    benchmark run, ``bench/run.py --trace 1``; this is the structural
    contract)."""
    from repro.api import Policy, Session

    s = Session(policy=Policy(backend="batched", installments=2))
    with s.trace() as tr:
        s.solve_bulk([_chain_problem(i) for i in range(3)])
    names = {e["name"] for e in tr.events()}
    assert {"session.trace", "session.solve_bulk", "session.dispatch",
            "session.materialize", "engine.solve_bulk", "engine.pack",
            "engine.lp_build", "engine.simplex", "simplex.dispatch",
            "simplex.fetch", "simplex.demote", "engine.replay",
            "replay.dispatch", "replay.fetch"} <= names
    assert ot.get_tracer() is None  # trace() restored the previous tracer


def test_simplex_host_spans_nest_inside_the_simplex_span(registry):
    from repro.api import Policy, Session

    s = Session(policy=Policy(backend="batched"))
    with s.trace() as tr:
        s.solve_bulk([_chain_problem(i) for i in range(2)])
    evs = tr.events()
    (simplex,) = [e for e in evs if e["name"] == "engine.simplex"]
    end = simplex["ts_us"] + simplex["dur_us"]
    parts = [e for e in evs if e["name"].startswith("simplex.")]
    assert [e["name"] for e in parts] == ["simplex.dispatch", "simplex.fetch",
                                          "simplex.demote"]
    for e in parts:
        assert simplex["ts_us"] <= e["ts_us"] and e["ts_us"] + e["dur_us"] <= end
        assert e["tid"] == simplex["tid"] and e["args"]["B"] == 2


def test_served_requests_share_ids_across_spans_and_wait_in_a_histogram(registry):
    from repro.api import Policy
    from repro.serve import PlanClient, PlanServer

    tr = ot.Tracer()
    prev = ot.activate(tr)
    try:
        with PlanServer(workers=1, policy=Policy(backend="batched"),
                        port=0) as server:
            client = PlanClient(f"http://localhost:{server.port}")
            for i in range(3):
                assert client.plan(_chain_problem(i)).ok
            server.plan(_chain_problem(9))  # in-process: no HTTP spans
    finally:
        ot.activate(prev)
    evs = tr.events()
    by = {n: [e for e in evs if e["name"] == n]
          for n in ("serve.http_accept", "serve.http_decode",
                    "serve.http_encode", "serve.queue_wait",
                    "serve.request_batch")}
    assert len(by["serve.http_decode"]) == len(by["serve.http_encode"]) == 3
    # the accept span ends where the handler starts the decode, same thread
    for acc, dec in zip(by["serve.http_accept"], by["serve.http_decode"]):
        assert acc["args"] == dec["args"] and acc["tid"] == dec["tid"]
        assert acc["ts_us"] + acc["dur_us"] <= dec["ts_us"]
    assert len(by["serve.queue_wait"]) == 4
    http_ids = [e["args"]["request"] for e in by["serve.http_decode"]]
    assert sorted(http_ids) == sorted(
        e["args"]["request"] for e in by["serve.http_encode"])
    waits = {e["args"]["request"]: e for e in by["serve.queue_wait"]}
    assert len(waits) == 4 and set(http_ids) < set(waits)
    batches = {e["args"]["batch"]: e for e in by["serve.request_batch"]}
    for rid in http_ids:
        decode = next(e for e in by["serve.http_decode"]
                      if e["args"]["request"] == rid)
        wait = waits[rid]
        # the wait starts on the handler thread that decoded the request,
        # after the decode, and ends before the batch that took it
        assert wait["tid"] == decode["tid"]
        assert decode["ts_us"] + decode["dur_us"] <= wait["ts_us"]
        batch = batches[wait["args"]["batch"]]
        assert wait["ts_us"] + wait["dur_us"] <= batch["ts_us"]
        assert batch["tid"] != wait["tid"]
    snap = registry.snapshot()
    assert snap["repro_serve_queue_wait_seconds_count"] == 4
    total = sum(e["dur_us"] for e in by["serve.queue_wait"]) / 1e6
    assert snap["repro_serve_queue_wait_seconds_sum"] == pytest.approx(
        total, rel=1e-6)
    assert snap["repro_serve_request_seconds_count"] == 4
