"""simplex.host_share: the share of the ``engine.simplex`` spans' time in
which the device did not run the simplex program (``jit__solve_batch``):
argument transfer, dispatch, the fetch of its outputs and the host's
feasibility pass.  100 x (1 - the device time of the program's executions
inside the spans / the spans' time), over the spans wholly inside the
stretch the trace holds, on the trace's clock.  Reads nothing where the
stretch holds no such span or no execution."""

PROGRAM = "jit__solve_batch"


def read(run):
    lo, hi = run.window_ns
    spans = [(s["start"], s["end"]) for s in run.spans
             if s["name"] == "engine.simplex" and lo <= s["start"]
             and s["end"] <= hi]
    runs = [(s, e) for s, e, name in run.modules[0] if name == PROGRAM]
    if not spans or not runs:
        return None
    device = sum(max(0.0, min(e, b) - max(s, a))
                 for a, b in spans for s, e in runs)
    return 100.0 * (1.0 - device / sum(b - a for a, b in spans))
