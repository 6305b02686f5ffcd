"""The program's own spans of each request, as the ``program_span``
readers take them.

The serving path keeps ``(name, start, end)`` spans on each ``Request``
(``Request.spans``): ``admit`` in serving admission, ``queue<s>`` and
``stage<s>`` in the pipeline executor, ``stage<s>.hop``,
``stage<s>.dispatch`` and ``stage<s>.wait`` in the stage program.  A
program without them gives no requests here, and its readers report
nothing.
"""
from __future__ import annotations

import re
import statistics
from collections import defaultdict
from typing import Dict, List, Optional

_STAGE = re.compile(r"^stage(\d+)$")
_QUEUE = re.compile(r"^queue\d+$")


def seconds(run) -> List[Dict[str, float]]:
    """Seconds under each span name, one dictionary per request of
    ``run.sent`` that has spans."""
    out = []
    for _, _, _, request in run.sent:
        spans = getattr(request, "spans", None)
        if spans:
            by_name: Dict[str, float] = defaultdict(float)
            for name, start, end in spans:
                by_name[name] += end - start
            out.append(by_name)
    return out


def median_ms(values: List[float]) -> Optional[float]:
    return statistics.median(values) * 1e3 if values else None


def admit_ms(run) -> Optional[float]:
    return median_ms([r["admit"] for r in seconds(run) if "admit" in r])


def queue_ms(run) -> Optional[float]:
    """Median over requests of the sum of their ``queue<s>`` spans."""
    return median_ms([sum(v for k, v in r.items() if _QUEUE.match(k))
                      for r in seconds(run)
                      if any(_QUEUE.match(k) for k in r)])


def stage_step_ms(run, step: str) -> Optional[float]:
    """The median ``stage<s>.<step>`` of the stage ``s`` whose calls take
    longest at the median."""
    reqs = seconds(run)
    calls: Dict[int, List[float]] = defaultdict(list)
    for r in reqs:
        for name, secs in r.items():
            m = _STAGE.match(name)
            if m:
                calls[int(m.group(1))].append(secs)
    if not calls:
        return None
    s = max(calls, key=lambda k: statistics.median(calls[k]))
    name = f"stage{s}.{step}"
    return median_ms([r[name] for r in reqs if name in r])
