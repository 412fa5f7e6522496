"""session.self_ms_per_plan: the session's own host time per plan: each
``session.solve_bulk`` span less the ``engine.solve_bulk`` spans it holds
(same thread, inside its interval), over the plans those calls answered."""

import bisect


def read(run):
    outer = [s for s in run.spans if s["name"] == "session.solve_bulk"]
    plans = sum(s["args"]["n"] for s in outer)
    if not plans:
        return None
    inner: dict = {}  # thread -> engine.solve_bulk (start, duration), sorted
    for s in run.spans:
        if s["name"] == "engine.solve_bulk":
            inner.setdefault(s["tid"], []).append((s["ts_us"], s["dur_us"]))
    for calls in inner.values():
        calls.sort()
    own = 0.0
    for s in outer:
        calls = inner.get(s["tid"], [])
        t0, t1 = s["ts_us"], s["ts_us"] + s["dur_us"]
        own += s["dur_us"]
        for start, dur in calls[bisect.bisect_left(calls, (t0,)):]:
            if start > t1:
                break
            if start + dur <= t1:
                own -= dur
    return own / 1e3 / plans
