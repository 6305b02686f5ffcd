"""Percentile and rate arithmetic of the end-to-end metrics."""
import threading

import pytest

import _paths  # noqa: F401
from _paths import BENCH
from harness import stats
from harness.cell import end_to_end
from harness.spec import load_module

TAIL = load_module(f"{BENCH}/metrics/tail_p95_ms.py")


def _p95(sent):
    return TAIL.read(type("Run", (), {"sent": sent}))


def test_nearest_rank():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 0.50) == 50
    assert stats.nearest_rank(xs, 0.95) == 95
    assert stats.nearest_rank([3.0], 0.95) == 3.0
    assert stats.nearest_rank([5, 1, 4, 2, 3], 0.5) == 3
    assert stats.nearest_rank([1, 2, float("inf")], 0.95) == float("inf")
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)


class _Req:
    def __init__(self, t_done, error=None):
        self.t_done = t_done
        self.error = error
        self.event = threading.Event()
        if t_done is not None:
            self.event.set()


def _sent(stall_at=None, stall_s=0.0, n=1000, service=0.002):
    """Requests due every 10 ms over 10 s, served in ``service`` seconds,
    with the server stalled for ``stall_s`` from ``stall_at``."""
    out = []
    free = 0.0
    for k in range(n):
        due = k * 0.01
        start = max(due, free)
        if stall_at is not None and stall_at <= start < stall_at + stall_s:
            start = stall_at + stall_s
        free = start + service
        out.append((k, due, due, _Req(free)))
    return out


def test_latency_from_due_time_and_a_stall_moves_the_tail():
    calm = end_to_end(_sent(), 1.0)
    assert calm["latency_p50_ms"] == pytest.approx(2.0)
    assert _p95(_sent()) == pytest.approx(2.0)
    # a 0.8 s stall at 5 s: the 80 requests due in it wait for its end,
    # so the tail moves and the median does not
    stalled = end_to_end(_sent(5.0, 0.8), 1.0)
    assert stalled["latency_p50_ms"] == pytest.approx(2.0)
    assert _p95(_sent(5.0, 0.8)) > 300.0
    assert stalled["setup_s"] == 1.0


def test_failed_or_unanswered_requests_miss_every_limit():
    sent = _sent(n=100)
    sent[3] = (3, 0.03, 0.03, _Req(0.05, error=RuntimeError("x")))
    sent[4] = (4, 0.04, 0.04, _Req(None))
    assert _p95(sent) == pytest.approx(2.0)
    assert end_to_end(sent, 1.0)["latency_p50_ms"] == pytest.approx(2.0)
    for k in range(10, 14):
        sent[k] = (k, k * 0.01, k * 0.01, _Req(None))
    # 6 of 100 never answered: the 95th percentile is one of them
    assert _p95(sent) == float("inf")
    assert stats.latencies([]) == [float("inf")]
