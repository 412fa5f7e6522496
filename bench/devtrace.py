"""Reduce a profiler trace of the window to device busy time, per-program
device time and the longest idle gaps.

The trace is taken with the TPU's per-operation events off (the simplex's
loop runs thousands of trips of dozens of operations each, which fills the
profiler's buffers within seconds), so the unit of device work here is one
execution of one XLA program: an event on a device plane's ``XLA Modules``
line.  Busy time is the union of those intervals; the window is the traced
span on the host's clock.  Event times are nanoseconds from the start of the
profiling session.
"""

from __future__ import annotations

import glob
import os
import re

__all__ = ["load_modules", "clock_shift", "held_until", "reduce", "label_gaps"]

_HASH = re.compile(r"\(\d+\)$")


def load_modules(log_dir: str, n_devices: int) -> list:
    """Per device used, the sorted ``(start_ns, end_ns, program)`` of every
    program execution in the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    per_device: dict = {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            per_device[int(m.group(1))] = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns,
                 _HASH.sub("", ev.name))
                for ev in line.events)
    return [per_device.get(d, []) for d in range(n_devices)]


def clock_shift(intervals: list, program: str, host_starts: list) -> float:
    """Nanoseconds to add to a host time to put it on the trace's clock.

    ``host_starts`` are the host times at which each execution of
    ``program`` was asked for, in order; the k-th execution in the trace
    starts a little after the k-th of them, and the least such lead is
    taken as the shift.  0 where either list is empty."""
    runs = [s for s, _, name in intervals if name == program]
    return min((r - h for r, h in zip(runs, sorted(host_starts))), default=0.0)


def held_until(intervals: list, program: str, expected: int,
               hi_ns: float) -> float:
    """Where the trace of one device stops holding every execution.

    ``expected`` executions of ``program`` ended before ``hi_ns`` by the
    host's own record.  Where the trace holds fewer, the profiler dropped
    the rest, and what it holds whole ends with its last event."""
    if sum(name == program for _, _, name in intervals) >= expected:
        return hi_ns
    return max((e for _, e, _ in intervals), default=0.0)


def _union(intervals: list, lo: float, hi: float) -> tuple:
    """(busy ns inside [lo, hi], idle gaps as (start, end, previous program))."""
    busy, gaps = 0.0, []
    cur_end, prev = lo, "window start"
    for s, e, name in intervals:
        s, e = max(s, lo), min(e, hi)
        if e <= cur_end:
            continue
        if s > cur_end:
            gaps.append((cur_end, s, prev))
            busy += e - s
        else:
            busy += e - cur_end
        cur_end, prev = e, name
    if hi > cur_end:
        gaps.append((cur_end, hi, prev))
    return busy, gaps


def reduce(modules: list, lo_ns: float, hi_ns: float) -> dict:
    """Busy seconds inside the window ``[lo_ns, hi_ns]`` averaged over the
    devices, device seconds per program in it, and every idle gap of device
    0 in it, from ``load_modules``' output."""
    per_program: dict = {}
    busy = []
    gaps: list = []
    for d, intervals in enumerate(modules):
        b, g = _union(intervals, lo_ns, hi_ns)
        busy.append(b / 1e9)
        if d == 0:
            gaps = g
        for s, e, name in intervals:
            s, e = max(s, lo_ns), min(e, hi_ns)
            if e > s:
                per_program[name] = per_program.get(name, 0.0) + (e - s) / 1e9
    return {"window_s": (hi_ns - lo_ns) / 1e9,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "programs": per_program, "gaps": gaps}


def label_gaps(gaps: list, spans: list, top: int = 10) -> list:
    """The ``top`` longest idle gaps as ``[label, seconds]``.

    ``spans`` are the program's host spans, already moved onto the trace's
    clock (nanoseconds from the session start).  A gap is labelled with the
    innermost span open at its middle, or as the worker waiting for requests
    where none is, and with the program that ran before it."""
    out = []
    for s, e, prev in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        inner = None
        for sp in spans:
            if sp["start"] <= mid <= sp["end"] and (
                    inner is None or sp["end"] - sp["start"]
                    < inner["end"] - inner["start"]):
                inner = sp
        what = inner["name"] if inner else "no request batch in flight"
        out.append([f"{what} (after {prev})", (e - s) / 1e9])
    return out
