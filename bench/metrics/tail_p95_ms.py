"""The tail of the whole request path: the 95th percentile (nearest rank)
over the requests that the traced run's host-clocked readers take (those
due before the profiler started) of answer time minus due time, an
unanswered or failed request counting as infinitely late.  At 0.8 x the
knee it is set by the backlog that admission's bursts, and the machine's
~0.1 s stalls, leave behind; a stall swings it too far from run to run
for an end-to-end bound (PERF.md)."""
from harness import stats


def read(run):
    if not run.sent:
        return None
    return stats.nearest_rank(stats.latencies(run.sent), 0.95) * 1e3
