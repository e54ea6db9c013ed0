"""Due time to last token, p90 over every request due in the window; a
request that never finished counts as infinitely late."""
import math

from bench.stats import percentile


def read(run):
    return percentile([(r.handle.finished_at if r.done else math.inf) - r.due
                       for r in run.requests], 90)
