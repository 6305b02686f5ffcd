"""The per-stage hop-bytes counter: each stage call built by
``build_stage_fns`` counts the bytes of the boundary it moves onto its
device, and ``snapshot()["stage_hop_bytes"]`` reports them as deltas."""
import jax
import numpy as np
import pytest

from conftest import api_plan as plan
from repro.core.pipeline import PipelineExecutor, count_hop_bytes
from repro.models.cnn import REAL_CNNS, synthetic_cnn
from repro.models.layers import GraphModel, build_stage_fns
from repro.serving import PipelinedModelServer


def _serve(srv, payloads):
    reqs = [srv.submit(p) for p in payloads]
    for r in reqs:
        assert r.event.wait(30) and r.error is None, r.error


def _boundary_bytes(fns, x):
    """Bytes of each stage's input boundary, from direct calls (which
    count nothing)."""
    out, sizes = {GraphModel.INPUT: x}, []
    for fn in fns:
        sizes.append(sum(v.nbytes for v in out.values()))
        out = fn(out)
    return sizes


def test_four_stage_counter_is_boundary_bytes_times_items_as_deltas():
    model = synthetic_cnn(8, L=6, hw=16)
    pl = plan(model.to_layer_graph(), 4, "balanced_norefine")
    params = model.init(jax.random.PRNGKey(0))
    fns = build_stage_fns(model, params, pl)
    x = np.ones((1,) + model.input_shape, np.float32)
    sizes = _boundary_bytes(fns, x)
    assert sizes[0] == x.nbytes         # stage 0 counts the image
    assert all(n > 0 for n in sizes)
    srv = PipelinedModelServer(pl, fns)
    srv.start()
    try:
        _serve(srv, [{GraphModel.INPUT: x} for _ in range(3)])
        snap = srv.snapshot()
        assert snap["stage_items"] == [3] * 4
        assert snap["stage_hop_bytes"] == [3 * n for n in sizes]
        # the next snapshot counts from the last one, not from the start
        _serve(srv, [{GraphModel.INPUT: x} for _ in range(2)])
        assert srv.snapshot()["stage_hop_bytes"] == [2 * n for n in sizes]
        assert srv.snapshot()["stage_hop_bytes"] == [0] * 4
    finally:
        srv.stop()


def test_reconfigure_rebases_the_counter():
    model = synthetic_cnn(8, L=6, hw=16)
    g = model.to_layer_graph()
    params = model.init(jax.random.PRNGKey(0))
    pl2, pl3 = (plan(g, n, "balanced_norefine") for n in (2, 3))
    x = np.ones((1,) + model.input_shape, np.float32)
    srv = PipelinedModelServer(pl2, build_stage_fns(model, params, pl2))
    with srv:
        srv.serve_batch([{GraphModel.INPUT: x}])
        srv.snapshot()
        fns = build_stage_fns(model, params, pl3)
        srv.reconfigure(pl3, fns)
        assert srv.snapshot()["stage_hop_bytes"] == [0, 0, 0]
        srv.serve_batch([{GraphModel.INPUT: x}] * 2)
        assert srv.snapshot()["stage_hop_bytes"] == [
            2 * n for n in _boundary_bytes(fns, x)]


def test_resnet50_single_stage_reports_one_entry():
    model = REAL_CNNS["ResNet50"]()
    pl = plan(model.to_layer_graph(), 1)
    params = model.init(jax.random.PRNGKey(0))
    x = np.zeros((1,) + model.input_shape, np.float32)
    srv = PipelinedModelServer(pl, build_stage_fns(model, params, pl))
    srv.start()
    try:
        _serve(srv, [{GraphModel.INPUT: x}])
        assert srv.snapshot()["stage_hop_bytes"] == [224 * 224 * 3 * 4]
    finally:
        srv.stop()


@pytest.mark.parametrize("replicas", [1, 3])
def test_executor_sums_replicas_and_ignores_calls_outside_it(replicas):
    def fn(x):
        count_hop_bytes(x)
        return x

    count_hop_bytes(5)          # not on a worker thread: counts nothing
    ex = PipelineExecutor([fn, fn], replicas=[replicas, 1])
    outs, _ = ex.run_batch([10, 20, 30])
    assert outs == [10, 20, 30]
    assert ex.hop_bytes_snapshot() == [60, 60]
    ex.stop()
    fn(7)
    assert ex.hop_bytes_snapshot() == [60, 60]
