"""Serving admission: median over the window's untraced requests of the
time from a request's due time to the start of its stage-0 call (harness
spans).  Holds the admission batcher's window and the queue behind it."""
import statistics


def read(run):
    if run.spans is None:
        return None
    due = {k: d for k, d, _, _ in run.sent}
    waits = [stages[0][0] - due[k]
             for k, stages in run.spans.per_request().items() if k in due]
    return statistics.median(waits) * 1e3 if waits else None
