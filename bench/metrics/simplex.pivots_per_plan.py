"""simplex.pivots_per_plan: pivots the device simplex made in the window
(``repro_simplex_pivots_total``, both phases) over the LPs it solved
(``repro_simplex_status_total``, one per LP whatever its status)."""


def read(run):
    lps = run.counter(run.counters, "repro_simplex_status_total")
    if not lps:
        return None
    return run.counter(run.counters, "repro_simplex_pivots_total") / lps
