"""Segmentation: the busiest stage's busy seconds per item over the mean
of that quantity across stages, from the server's ``snapshot()`` over the
window until the profiler started.  1 is a cut whose stages take equal
time per call; the pipeline runs at the pace of the busiest."""


def read(run):
    busy = run.snapshot.get("stage_busy_s")
    items = run.snapshot.get("stage_items")
    if not busy or not items or not all(items):
        return None
    per_item = [b / n for b, n in zip(busy, items)]
    mean = sum(per_item) / len(per_item)
    return max(per_item) / mean if mean > 0 else None
