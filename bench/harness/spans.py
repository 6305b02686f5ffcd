"""The harness's own spans around the calls it makes into the program.

In a traced run each stage function handed to ``deploy`` is wrapped: the
wrapper times the call on the host clock and opens a
``jax.profiler.TraceAnnotation`` named ``stage<s>`` so the span sits on
the device trace's clock.  A request is followed from stage to stage by
the identity of the dictionary that carries it: the payload the harness
submits, then each stage's output.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Tuple

import jax


class Spans:
    """Stage-call spans per request: ``calls[s]`` holds
    ``(request index, start, end)`` on ``time.perf_counter``'s clock."""

    def __init__(self, n_stages: int):
        self.calls: List[List[Tuple[int, float, float]]] = [
            [] for _ in range(n_stages)]
        self._owner: Dict[int, int] = {}

    def submitted(self, payload: Any, k: int) -> None:
        """Mark ``payload`` as request ``k`` before it is submitted."""
        self._owner[id(payload)] = k

    def wrap(self, s: int, fn: Callable[[Any], Any]) -> Callable[[Any], Any]:
        last = s == len(self.calls) - 1
        name = f"stage{s}"
        calls = self.calls[s]

        def run(payload: Any) -> Any:
            k = self._owner.pop(id(payload), -1)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(name):
                out = fn(payload)
            calls.append((k, t0, time.perf_counter()))
            if not last:
                self._owner[id(out)] = k
            return out

        return run

    def per_request(self) -> Dict[int, List[Tuple[float, float]]]:
        """``k -> [(start, end) of stage 0, 1, ...]`` for requests seen by
        every stage."""
        by_k: Dict[int, List[Tuple[float, float]]] = {}
        for s, calls in enumerate(self.calls):
            for k, t0, t1 in calls:
                if k >= 0:
                    by_k.setdefault(k, []).append((t0, t1))
        n = len(self.calls)
        return {k: v for k, v in by_k.items() if len(v) == n}
