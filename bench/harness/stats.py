"""Arithmetic of the end-to-end metrics."""
from __future__ import annotations

import math
from typing import Any, List, Sequence


def nearest_rank(xs: Sequence[float], p: float) -> float:
    """The smallest sample with at least ``p`` of the sample at or below
    it (nearest rank, as the serving layer's ``latency_percentiles``).
    An unanswered request enters as ``inf``, so it misses every limit."""
    if not xs:
        raise ValueError("empty sample")
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(p * len(s)) - 1))]



def latencies(sent: Sequence[Any]) -> List[float]:
    """Answer time minus due time of each ``(k, due, sent, request)`` of a
    window, in seconds.  A failed or unanswered request counts as
    infinitely late, and an empty window as one such request."""
    return [(r.t_done - due) if (r.event.is_set() and r.error is None)
            else math.inf for _, due, _, r in sent] or [math.inf]
