"""Scheduler: due time to the start of the ``ServingEngine.step()`` that
admitted the request, p90 over the requests due in the window (benchmark
loop, host clock). The seconds in which the benchmark held the loop itself
(the profiler's stop at the close of a traced run) are not the scheduler's
and are left out of each wait that spans them."""
from bench.stats import percentile


def read(run):
    return percentile([run.unpaused(r.due, r.admit_t) for r in run.requests
                       if r.admit_t is not None], 90)
