"""Stage program: the busiest stage's busy seconds per item it applied,
from the server's ``snapshot()`` over the window until the profiler
started.  The stage function waits for its outputs, so this is the
host-clocked time of one call: the hop onto the stage's chip, dispatch
and device time."""


def read(run):
    busy = run.snapshot["stage_busy_s"]
    items = run.snapshot["stage_items"]
    if not busy:
        return None
    s = max(range(len(busy)), key=busy.__getitem__)
    return busy[s] / items[s] * 1e3 if items[s] else None
