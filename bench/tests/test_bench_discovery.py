"""Cells, configurations, traffic mixes and metrics are found by name:
a later change adds files and entries, and edits nothing that is there."""
import json
import os
import shutil

from _paths import BENCH, ROOT
from harness.spec import load_cell

METRIC = '''def read(run):
    return float(len(run.sent))
'''


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_every_cell_of_the_benchmark_loads():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = load_cell(ROOT, w["name"])
        assert cell.config["name"] == w["config"]
        assert callable(cell.generator.drive)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer and set(cell.readers) == {
            m["name"] for m in cell.per_layer}
        for m in cell.per_layer:
            assert m["moves"] in names


def test_added_files_and_entries_are_found(tmp_path):
    root = _copy(tmp_path)
    before = _digest(root / "bench")
    cfg = json.loads((root / "bench/configs/resnet50.json").read_text())
    cfg["name"] = "resnet50-two"
    cfg["stages"] = 2
    (root / "bench/configs/resnet50-two.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/trickle.json").write_text(json.dumps(
        {"kind": "poisson", "rate_per_s": 5, "pool": 4}))
    (root / "bench/metrics/sent_count.py").write_text(METRIC)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "resnet50-two", "source": "x",
                             "file": "bench/configs/resnet50-two.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "resnet50.two.trickle",
                               "config": "resnet50-two",
                               "traffic": "trickle", "chips": 1, "why": "x"})
    # no workloads list: applies wherever latency_p50_ms is reported
    bench["per_layer"].append({"name": "sent_count", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "latency_p50_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = load_cell(str(root), "resnet50.two.trickle")
    assert cell.config["stages"] == 2
    assert cell.traffic["rate_per_s"] == 5
    assert cell.generator.schedule(cell.traffic, 0, 2.0).shape == (10,)
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    # the new cell does not report latency_p50_ms: the new metric is not
    # its to report
    assert cell.readers == {}
    # the cell already there reports the new metric too, and nothing that
    # was there was edited
    steady = load_cell(str(root), "resnet50.c1.steady")
    assert "sent_count" in steady.readers
    assert steady.readers["sent_count"].read(
        type("R", (), {"sent": [1, 2, 3]})) == 3.0
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
