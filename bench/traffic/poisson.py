"""Open loop: independent users sending at a fixed mean rate.

Arrivals are Poisson, but every seed gets the same set of gaps, in its own
order: the gaps are the ``n`` midpoint quantiles of the exponential
distribution at ``rate_per_s``, scaled to fill the window exactly, and the
seed only permutes them.  So each seed offers exactly ``rate x seconds``
requests with the same burstiness, and runs with different seeds differ
in arrival order alone.

Mix keys: ``rate_per_s``.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


def schedule(mix: Dict[str, Any], seed: int, seconds: float) -> np.ndarray:
    """Due times of the window's requests, in seconds from its start."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = gaps[np.random.default_rng(seed).permutation(n)]
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def drive(submit: Callable[[int], Any], mix: Dict[str, Any], seed: int,
          seconds: float, t0: float) -> List[Tuple[int, float, float, Any]]:
    """Send request ``k`` at ``t0 + schedule[k]`` whatever the server is
    doing; returns ``(k, due, sent, request)`` per request."""
    sent = []
    for k, offset in enumerate(schedule(mix, seed, seconds)):
        due = t0 + offset
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t = time.perf_counter()
        sent.append((k, due, t, submit(k)))
    return sent
