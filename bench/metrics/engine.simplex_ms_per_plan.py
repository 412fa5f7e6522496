"""engine.simplex_ms_per_plan: host time of the ``engine.simplex`` spans (the
span closes once the bucket's results are back on the host) over the LPs
those spans solved on the device."""


def read(run):
    spans = [s for s in run.spans if s["name"] == "engine.simplex"]
    lanes = sum(s["args"]["B"] for s in spans)
    if not lanes:
        return None
    return sum(s["dur_us"] for s in spans) / 1e3 / lanes
