"""device.idle_share: the share of the traced window in which no program ran
on the device (1 - busy / window, averaged over the chips used).  Reads
nothing where the trace holds no program execution."""


def read(run):
    t = run.trace
    if not t["window_s"] or not t["busy_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
