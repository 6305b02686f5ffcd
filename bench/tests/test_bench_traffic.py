"""The traffic generators replay exactly from a seed."""
import threading
import time

import numpy as np
import pytest

from _paths import BENCH
from harness.spec import load_module

POISSON = load_module(f"{BENCH}/traffic/poisson.py")


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_poisson_schedule_replays_from_seed(seed):
    mix = {"rate_per_s": 250}
    a = POISSON.schedule(mix, seed, 12.0)
    b = POISSON.schedule(mix, seed, 12.0)
    np.testing.assert_array_equal(a, b)
    assert len(a) == 3000
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 12.0


def test_poisson_seeds_share_their_gaps_in_another_order():
    mix = {"rate_per_s": 100}
    a = np.diff(POISSON.schedule(mix, 1, 10.0))
    b = np.diff(POISSON.schedule(mix, 2, 10.0))
    assert not np.allclose(a, b)
    # the same gaps but the last, which each seed leaves out of its diffs
    sa, sb = np.sort(a), np.sort(b)
    assert np.mean(np.abs(sa[:-1] - sb[:-1]) <= sa[1:] - sa[:-1] + 1e-12) > 0.99
    # exponential: the coefficient of variation of the gaps is about 1
    assert 0.9 < a.std() / a.mean() < 1.1


class _Req:
    def __init__(self, delay):
        self.event = threading.Event()
        threading.Timer(delay, self.event.set).start()


def test_poisson_drive_sends_on_schedule():
    got = []

    def submit(k):
        got.append(k)
        return _Req(0.0)

    t0 = time.perf_counter() + 0.01
    sent = POISSON.drive(submit, {"rate_per_s": 200}, 5, 0.5, t0)
    due = t0 + POISSON.schedule({"rate_per_s": 200}, 5, 0.5)
    assert got == list(range(100)) == [k for k, *_ in sent]
    np.testing.assert_allclose([d for _, d, _, _ in sent], due)
    assert all(t >= d for _, d, t, _ in sent)

