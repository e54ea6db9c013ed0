"""Gap between consecutive tokens of a request, p99 over every gap that
ends in the window, in milliseconds."""
from bench.stats import percentile


def read(run):
    p = percentile(run.itl_gaps(), 99)
    return None if p is None else 1e3 * p
