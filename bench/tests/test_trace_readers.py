"""CPU tests of the readers of the request's path through the program
(front-door queue wait and HTTP time, the session's own time, the host's
share of the simplex call) and of ``hostplane``: the clock shift from the
program's own annotations and the gap labels by the worker thread."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import hostplane  # noqa: E402
import run as bench_run  # noqa: E402
import traffic  # noqa: E402

MS = 1e6  # ns
WORKER, HANDLER = 1, 2  # thread ids


def _span(name, start_ms, end_ms, tid=WORKER, **args):
    """A span as ``bench/run.py`` hands it to a reader: the tracer's clock in
    microseconds, the trace's in nanoseconds (here the same instant)."""
    return {"name": name, "ts_us": start_ms * 1e3,
            "dur_us": (end_ms - start_ms) * 1e3, "tid": tid, "args": args,
            "start": start_ms * MS, "end": end_ms * MS}


def _run(spans=(), counters=None, modules=([],), window_ms=(0.0, 1000.0)):
    return types.SimpleNamespace(
        spans=list(spans), counters=counters or {}, counter=bench_run.counter,
        modules=[list(m) for m in modules],
        window_ns=tuple(t * MS for t in window_ms))


NEW = ("serve.queue_wait_ms", "serve.http_ms_per_request",
       "session.self_ms_per_plan", "simplex.host_share")


# ------------------------------------------------------------------ readers


def test_queue_wait_reads_the_histogram_as_it_grew_in_the_window():
    counters = {"repro_serve_queue_wait_seconds_sum": 0.25,
                "repro_serve_queue_wait_seconds_count": 10.0,
                "repro_serve_queue_wait_seconds_bucket{le=0.1}": 10.0,
                "repro_serve_request_seconds_sum": 9.0}
    read = bench_run._reader("serve.queue_wait_ms")
    assert read(_run(counters=counters)) == pytest.approx(25.0)


def test_http_time_is_decode_and_encode_over_requests_decoded():
    spans = [_span("serve.http_decode", 0, 2, HANDLER, request=0),
             _span("serve.http_decode", 1, 2, HANDLER + 1, request=1),
             _span("serve.queue_wait", 2, 50, HANDLER, request=0, batch=0),
             _span("serve.http_encode", 60, 63, HANDLER, request=0)]
    read = bench_run._reader("serve.http_ms_per_request")
    assert read(_run(spans)) == pytest.approx((2 + 1 + 3) / 2)


def test_session_self_time_leaves_out_the_engine_calls_it_holds():
    spans = [_span("session.solve_bulk", 0, 40, n=4),
             _span("engine.solve_bulk", 5, 35, n=4),
             _span("session.materialize", 36, 39, n=4),
             _span("session.solve_bulk", 50, 60, n=2),
             _span("engine.solve_bulk", 52, 55, n=1),
             _span("engine.solve_bulk", 56, 59, n=1),
             # another thread's engine call inside the interval is not a child
             _span("engine.solve_bulk", 51, 58, tid=HANDLER, n=1)]
    read = bench_run._reader("session.self_ms_per_plan")
    assert read(_run(spans)) == pytest.approx(((40 - 30) + (10 - 6)) / 6)


def test_simplex_host_share_counts_device_time_inside_held_spans():
    spans = [_span("engine.simplex", 10, 40), _span("engine.simplex", 50, 70),
             _span("engine.simplex", 990, 1010)]  # not wholly in the stretch
    runs = [(15 * MS, 30 * MS, "jit__solve_batch"),
            (31 * MS, 33 * MS, "jit__sim_batch"),
            (45 * MS, 55 * MS, "jit__solve_batch"),  # half before its span
            (995 * MS, 1005 * MS, "jit__solve_batch")]
    read = bench_run._reader("simplex.host_share")
    assert read(_run(spans, modules=[runs])) == pytest.approx(
        100.0 * (1 - (15 + 5) / (30 + 20)))
    assert read(_run(spans, modules=[[]])) is None  # no execution traced


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_where_there_is_nothing(name):
    read = bench_run._reader(name)
    assert read(_run()) is None
    # the parent program: the old spans and counters, none of the new ones
    old = [_span("serve.request_batch", 0, 40, n=4),
           _span("engine.solve_bulk", 1, 39, n=4)]
    counters = {"repro_serve_request_seconds_count": 4.0}
    assert read(_run(old, counters)) is None


# ---------------------------------------------------------------- hostplane


def test_annotation_shift_is_the_median_start_difference_by_name():
    shift = 5_000_000.0
    spans = [_span("engine.simplex", 10, 40), _span("engine.simplex", 50, 70),
             _span("serve.http_decode", 3, 4, HANDLER),
             _span("serve.http_decode", 3.5, 5, HANDLER + 1),
             _span("engine.simplex", 900, 990)]  # after the profiler stopped
    jitter = iter([0, 40, -30, 10])
    ann = [(sp["ts_us"] * 1e3 + shift + next(jitter), 0.0, sp["name"])
           for sp in spans[:4]]
    ann.append((0.0, 1.0, "jit__solve_batch"))  # a name no span has
    got = hostplane.annotation_shift(ann, spans)
    assert got["matched"] == 4
    assert got["shift_ns"] == pytest.approx(shift + 5)
    assert got["max_dev_ns"] == pytest.approx(35)
    assert 0 < got["spread_ns"] <= 70
    assert hostplane.annotation_shift([], spans)["matched"] == 0


def test_gap_labels_prefer_the_worker_thread_over_a_handler_span():
    gaps = [(100 * MS, 140 * MS, "jit__solve_batch"),   # worker in lp_build
            (200 * MS, 230 * MS, "jit__sim_batch"),     # worker idle, decoding
            (300 * MS, 320 * MS, "jit__solve_batch"),   # only waits open
            (400 * MS, 401 * MS, "jit__solve_batch")]
    spans = [_span("serve.request_batch", 90, 190, n=3),
             _span("engine.lp_build", 110, 130, B=3),
             # a handler encodes an answer while the worker builds LPs
             _span("serve.http_encode", 115, 125, HANDLER, request=0),
             _span("serve.http_decode", 205, 225, HANDLER, request=1),
             _span("serve.queue_wait", 226, 330, HANDLER, request=1, batch=1)]
    labels = hostplane.label_gaps(gaps, spans, top=3)
    assert labels == [["engine.lp_build (after jit__solve_batch)",
                       pytest.approx(0.04)],
                      ["serve.http_decode (after jit__sim_batch)",
                       pytest.approx(0.03)],
                      [f"{hostplane.WAITING} (after jit__solve_batch)",
                       pytest.approx(0.02)]]


def test_annotations_land_on_the_host_plane_beside_the_spans(tmp_path):
    """A CPU profile of annotated spans: every span is found again, and the
    shift from the tracer's clock to the profile's is one number."""
    import jax

    from repro.obs import trace as obs_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tracer = obs_trace.Tracer("bench", annotate=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    prev = obs_trace.activate(tracer)
    try:
        for _ in range(5):
            with obs_trace.span("engine.simplex"):
                with obs_trace.span("simplex.fetch"):
                    jax.numpy.ones(8).block_until_ready()
    finally:
        obs_trace.activate(prev)
        jax.profiler.stop_trace()
    ann = hostplane.load_annotations(str(tmp_path),
                                     {"engine.simplex", "simplex.fetch"})
    assert len(ann) == 10
    got = hostplane.annotation_shift(ann, tracer.events())
    assert got["matched"] == 10
    assert got["max_dev_ns"] < 5 * MS


# --------------------------------------------------- a traced run on the CPU


def test_traced_run_prints_the_new_front_door_and_session_metrics(
        monkeypatch, capsys, tmp_path):
    """The harness as it stands reads the three metrics that need no device
    trace from a traced run; the fourth needs a TPU's device plane."""
    import jax

    for name, value in (("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache")),
                        ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0"),
                        ("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")):
        monkeypatch.setenv(name, value)
    real_cfg, real_mix = traffic.load_config, traffic.load_mix
    monkeypatch.setattr(traffic, "load_config",
                        lambda name: dict(real_cfg(name), m=3, n_loads=2))
    monkeypatch.setattr(traffic, "load_mix",
                        lambda name: dict(real_mix(name), clients=2))
    monkeypatch.setattr(bench_run, "peaks_for",
                        lambda kind: {"hbm_bytes_per_s": 1.0})
    monkeypatch.setattr(bench_run, "DRAIN_S", 20.0)
    monkeypatch.setattr(bench_run, "TRACE_DIR", str(tmp_path / "trace"))
    args = types.SimpleNamespace(workload="chain-table2-n5.cold",
                                 seed=2**31 + 7, seconds=1.0, trace=1)
    assert bench_run.run(args, require_chip=lambda n: jax.devices()[:n]) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    metrics = result["metrics"]
    for name in NEW[:3]:
        assert metrics[name]["value"] > 0, name
    assert "simplex.host_share" not in metrics  # no device plane on the CPU
