"""Put the program's spans on the profiler's clock from the program's own
annotations, and name each idle gap of the device by what the worker did.

With ``Tracer(annotate=True)`` every span the program opens is also a
``jax.profiler.TraceAnnotation`` of the same name.  A profile taken with a
host tracer level of 1 holds those on its ``/host:CPU`` plane, one line per
thread, on the session clock of the device planes (nanoseconds from the
profile's start).  The k-th annotation of a name is then the k-th span of
that name, and the difference of their starts is the shift from the
tracer's clock to the profile's, with no dispatch lead in it (the least lead
of a program's execution over the span that asked for it, which
``devtrace.clock_shift`` takes, holds one).

These functions read the profile and the spans that ``bench/run.py``
collects; they take effect once the traced run profiles with a host tracer
level of 1 and a tracer that annotates (PERF.md, open questions).
"""

from __future__ import annotations

import glob
import os
import statistics

__all__ = ["load_annotations", "annotation_shift", "label_gaps"]

# what the worker thread does when it holds no span, in the gap labels
WAITING = "no request batch in flight"
# front-door spans that do work on a handler thread (serve.queue_wait is a
# wait, recorded on the handler's thread too, and labels nothing)
FRONT_DOOR = ("serve.http_accept", "serve.http_decode", "serve.http_encode")


def load_annotations(log_dir: str, names) -> list:
    """``(start_ns, end_ns, name)`` of every host event called one of
    ``names`` in the newest profile under ``log_dir``, sorted by start."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    names = set(names)
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events if ev.name in names)
    return sorted(out)


def annotation_shift(annotations: list, spans: list) -> dict:
    """Nanoseconds to add to a span's start on the tracer's clock
    (``ts_us``) to put it on the profile's clock.

    The k-th annotation of each name is paired with the k-th span of that
    name, both in order of start.  The profiler keeps no annotation of a
    span still open when it stopped; such a span is among the last of its
    name to start, so a pair it upsets lies at the end, where it moves the
    median by nothing and shows in ``max_dev_ns``.  Returns the
    median of the paired differences as ``shift_ns``, their interquartile
    range as ``spread_ns``, the largest distance of one from the median as
    ``max_dev_ns``, and the pairs counted as ``matched``; a shift of 0 and
    nothing matched where no name has both."""
    starts: dict = {}
    for sp in spans:
        starts.setdefault(sp["name"], []).append(sp["ts_us"] * 1e3)
    marks: dict = {}
    for s, _, name in annotations:
        marks.setdefault(name, []).append(s)
    diffs = []
    for name, ann in marks.items():
        diffs += [a - h for a, h in zip(sorted(ann), sorted(starts.get(name, [])))]
    if not diffs:
        return {"shift_ns": 0.0, "spread_ns": 0.0, "max_dev_ns": 0.0,
                "matched": 0}
    shift = statistics.median(diffs)
    q = statistics.quantiles(diffs, n=4) if len(diffs) > 1 else [shift] * 3
    return {"shift_ns": shift, "spread_ns": q[2] - q[0],
            "max_dev_ns": max(abs(d - shift) for d in diffs),
            "matched": len(diffs)}


def _innermost(spans: list, at: float):
    inner = None
    for sp in spans:
        if sp["start"] <= at <= sp["end"] and (
                inner is None
                or sp["end"] - sp["start"] < inner["end"] - inner["start"]):
            inner = sp
    return inner


def label_gaps(gaps: list, spans: list, top: int = 10) -> list:
    """The ``top`` longest idle gaps as ``[label, seconds]``.

    ``spans`` are on the profile's clock (``start``/``end`` in ns).  A gap
    is put down to the innermost span open at its middle on a worker thread
    (one that holds ``serve.request_batch`` spans); where the worker holds
    none, to a front-door span doing work then (``serve.http_accept``,
    ``serve.http_decode`` or ``serve.http_encode``); where neither is open,
    the worker was waiting for requests.  The label ends with the program
    that ran before the gap."""
    workers = {sp["tid"] for sp in spans if sp["name"] == "serve.request_batch"}
    on_worker = [sp for sp in spans
                 if sp["tid"] in workers and sp["name"] != "serve.queue_wait"]
    front = [sp for sp in spans if sp["name"] in FRONT_DOOR]
    out = []
    for s, e, prev in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        inner = _innermost(on_worker, mid) or _innermost(front, mid)
        what = inner["name"] if inner else WAITING
        out.append([f"{what} (after {prev})", (e - s) / 1e9])
    return out
