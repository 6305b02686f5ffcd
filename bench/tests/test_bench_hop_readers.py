"""The readers of the cut and its chip-to-chip hops (``stage_imbalance``,
``chip_hop_ms``) on runs made by hand, and on runs of a program that has
no such spans."""
import json
import os
from types import SimpleNamespace

import pytest

from _paths import BENCH, ROOT
from harness.spec import load_module

MS = 1e-3


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def _request(hops_ms):
    """A request whose stage ``s`` hop takes ``hops_ms[s]`` ms, with the
    stage call around it."""
    spans, t = [], 50.0
    for s, ms in enumerate(hops_ms):
        spans += [(f"stage{s}", t, t + 2 * ms * MS),
                  (f"stage{s}.hop", t, t + ms * MS)]
        t += 2 * ms * MS
    return SimpleNamespace(spans=spans)


def _run(snapshot, requests):
    return SimpleNamespace(snapshot=snapshot,
                           sent=[(k, 0.0, 0.0, r)
                                 for k, r in enumerate(requests)])


# four stages, 10 items each: per item 2, 1, 1 and 0.4 ms of busy time
SNAPSHOT = {"stage_busy_s": [0.020, 0.010, 0.010, 0.004],
            "stage_items": [10, 10, 10, 10]}
REQUESTS = [_request([0.3, 1.0, 0.5, 0.25]),
            _request([0.3, 2.0, 1.0, 0.5]),
            _request([0.3, 0.5, 0.25, 0.25]),
            SimpleNamespace()]          # a request without spans


@pytest.mark.parametrize("name, want", [
    ("stage_imbalance", 2.0 / 1.1),
    ("chip_hop_ms", 1.75),              # of 1.75, 3.5 and 1.0
])
def test_reader_on_a_hand_made_run(name, want):
    assert _reader(name).read(_run(SNAPSHOT, REQUESTS)) == pytest.approx(
        want)


def test_one_stage_has_no_chip_hop_and_no_imbalance():
    run = _run({"stage_busy_s": [0.02], "stage_items": [10]},
               [_request([0.3])] * 3)
    assert _reader("stage_imbalance").read(run) == pytest.approx(1.0)
    assert _reader("chip_hop_ms").read(run) is None


@pytest.mark.parametrize("name", ["stage_imbalance", "chip_hop_ms"])
def test_reader_reports_nothing_without_its_counter_or_spans(name):
    reader = _reader(name)
    bare = _run({}, [SimpleNamespace(), SimpleNamespace(spans=[])])
    assert reader.read(bare) is None
    assert reader.read(_run({"stage_busy_s": [], "stage_items": []},
                            [])) is None
    if name == "stage_imbalance":
        # a stage that applied nothing in the window
        idle = dict(SNAPSHOT, stage_items=[10, 10, 0, 10])
        assert reader.read(_run(idle, REQUESTS)) is None


def test_chip_hop_ms_is_listed_only_where_a_hop_crosses_chips():
    """On one chip every hop is a same-device ``device_put``: nothing
    crosses, so the reader has no chip-to-chip hop to read there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    (hop,) = [m for m in bench["per_layer"] if m["name"] == "chip_hop_ms"]
    assert hop["workloads"] and all(chips[w] > 1 for w in hop["workloads"])
