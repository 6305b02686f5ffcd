"""The closed-loop generator against a fake server that answers one
request at a time."""
import os
import queue
import sys
import threading
import time

import pytest

from _paths import BENCH
from harness.spec import load_module

CLOSED = load_module(f"{BENCH}/traffic/closed.py")


class _Req:
    def __init__(self):
        self.event = threading.Event()


class _Server:
    """Answers its requests in order, ``service_s`` each, or never; keeps
    count of how many are out at once and of who sent what, when."""

    def __init__(self, service_s=0.001, answers=True):
        self.service_s = service_s
        self.answers = answers
        self.lock = threading.Lock()
        self.out = self.most_out = 0
        self.calls = []         # (k, time, client thread)
        self.q = queue.Queue()
        self.worker = threading.Thread(target=self._serve, daemon=True)
        self.worker.start()

    def submit(self, k):
        r = _Req()
        with self.lock:
            self.out += 1
            self.most_out = max(self.most_out, self.out)
            self.calls.append((k, time.perf_counter(),
                               threading.current_thread().name))
        if self.answers:
            self.q.put(r)
        return r

    def _serve(self):
        while True:
            r = self.q.get()
            if r is None:
                return
            time.sleep(self.service_s)
            with self.lock:
                self.out -= 1
            r.event.set()

    def close(self):
        self.q.put(None)
        self.worker.join()


def _drive(seed, clients=8, seconds=0.3, **kw):
    srv = _Server(**kw)
    t0 = time.perf_counter() + 0.02
    sent = CLOSED.drive(srv.submit, {"clients": clients, "pool": 16}, seed,
                        seconds, t0)
    t_back = time.perf_counter()
    srv.close()
    return srv, sent, t0, t_back


def test_keeps_clients_out_and_sends_nothing_after_the_window():
    srv, sent, t0, t_back = _drive(2**31 + 7)
    assert 2 <= srv.most_out <= 8
    assert len(sent) > 20
    ks = [k for k, *_ in sent]
    assert ks == list(range(len(sent)))
    assert [k for k, _, _ in sorted(srv.calls)] == ks
    for _, due, t, _ in sent:
        assert due == t and t0 <= t < t0 + 0.3
    assert max(t for _, t, _ in srv.calls) < t0 + 0.3
    assert t_back < t0 + 0.3 + 0.5


def test_unanswered_clients_stop_when_the_window_closes():
    srv, sent, t0, t_back = _drive(3, answers=False)
    assert sorted(k for k, *_ in sent) == list(range(8))
    assert t0 + 0.3 <= t_back < t0 + 0.3 + 0.5


def _first_images(seed):
    srv, _, _, _ = _drive(seed, seconds=0.05)
    return {name: k for k, _, name in srv.calls if k < 8}


@pytest.mark.parametrize("a, b", [(1, 2), (2**40 + 3, 5)])
def test_seeds_differ_only_in_which_client_starts_on_which_image(a, b):
    first_a, first_b = _first_images(a), _first_images(b)
    assert first_a == _first_images(a)
    assert first_a != first_b
    assert sorted(first_a.values()) == sorted(first_b.values()) == list(
        range(8))


def test_many_clients_on_a_short_switch_interval_lose_no_request():
    """More clients than cores, threads switched every microsecond: every
    request number is handed out once, and none is lost."""
    clients = min(64, 2 * (os.cpu_count() or 1) + 1)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        srv, sent, _, _ = _drive(9, clients=clients, seconds=0.3,
                                 service_s=0.0)
    finally:
        sys.setswitchinterval(interval)
    assert not srv.worker.is_alive()
    assert [k for k, *_ in sent] == list(range(len(sent)))
    assert sorted(k for k, _, _ in srv.calls) == list(range(len(sent)))
    assert len(sent) > clients and srv.most_out <= clients


def test_a_failed_submit_is_raised_after_the_clients_stop():
    def submit(k):
        if k == 3:
            raise RuntimeError("server gone")
        return _Req()                   # never answered

    # request 3 is one of the four first ones, sent as each client starts;
    # a window of a second leaves the threads time to start on a busy host
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="server gone"):
        CLOSED.drive(submit, {"clients": 4, "pool": 8}, 1, 1.0, t0)
    assert time.perf_counter() >= t0 + 1.0
