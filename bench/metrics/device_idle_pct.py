"""Device: share of the traced window, less the loop's waits for an
arrival, in which no operation ran on the chip."""
from bench.stats import share_pct


def read(run):
    if run.trace is None:
        return None
    return share_pct(run.trace.idle_with_work_s, run.trace.work_s)
