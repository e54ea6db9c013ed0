"""Due time to first token, p90 over every request due in the window; a
request that never got a token counts as infinitely late."""
import math

from bench.stats import percentile


def read(run):
    return percentile([(r.token_t[0] if r.token_t else math.inf) - r.due
                       for r in run.requests], 90)
