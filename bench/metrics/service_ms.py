"""Pipeline executor: median over the window's untraced requests of the
time from the start of a request's stage-0 call to the end of its last
stage call (harness spans): the stages' work and the queues between
them."""
import statistics


def read(run):
    if run.spans is None:
        return None
    due = {k for k, _, _, _ in run.sent}
    times = [stages[-1][1] - stages[0][0]
             for k, stages in run.spans.per_request().items() if k in due]
    return statistics.median(times) * 1e3 if times else None
