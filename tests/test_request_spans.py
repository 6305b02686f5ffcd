"""Per-request spans inside the serving path: the batcher's ``admit``, the
executor's ``queue<s>`` and ``stage<s>``, and the stage program's
``stage<s>.hop``/``.dispatch``/``.wait``, kept on ``Request.spans``."""
import os
import re
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from conftest import api_plan as plan
from repro.core import pipeline
from repro.core.pipeline import PipelineExecutor, StageLost, span
from repro.models.cnn import synthetic_cnn
from repro.models.layers import GraphModel, build_stage_fns
from repro.serving import PipelinedModelServer

HARNESS_NAMES = re.compile(r"^stage(\d+)$|^bench_window$")


def _plan(n_stages):
    return plan(synthetic_cnn(600).to_layer_graph(), n_stages,
                "balanced_norefine")


def _wait(reqs):
    for r in reqs:
        assert r.event.wait(10), "request never completed"
        assert r.error is None, r.error


def _in_order(spans):
    for (_, a, b), (_, c, d) in zip(spans, spans[1:]):
        assert a <= b <= c <= d


def test_server_spans_tile_admission_queues_and_stages():
    """Two stages that sleep known times: every request gets admit,
    queue0, stage0, queue1, stage1, in order and without overlap, and
    each sleep lies inside its stage's span."""
    slept = {0: {}, 1: {}}

    def sleeper(s, secs):
        def fn(x):
            t0 = time.perf_counter()
            time.sleep(secs)
            slept[s][x] = (t0, time.perf_counter())
            return x
        return fn

    srv = PipelinedModelServer(_plan(2), [sleeper(0, 0.003),
                                          sleeper(1, 0.002)],
                               max_batch=4, max_wait_s=0.005)
    srv.start()
    reqs = [srv.submit(i) for i in range(8)]
    _wait(reqs)
    srv.stop()
    for i, r in enumerate(reqs):
        assert [n for n, _, _ in r.spans] == [
            "admit", "queue0", "stage0", "queue1", "stage1"]
        assert r.spans[0][1] == r.t_submit
        assert r.spans[-1][2] <= r.t_done
        _in_order(r.spans)
        for s in (0, 1):
            _, a, b = r.spans[2 + 2 * s]
            t0, t1 = slept[s][i]
            assert a <= t0 < t1 <= b


def test_stage_program_spans_lie_inside_the_stage_call():
    model = synthetic_cnn(6, hw=16)
    pl = plan(model.to_layer_graph(), 1, "balanced_norefine")
    params = model.init(jax.random.PRNGKey(0))
    srv = PipelinedModelServer(pl, build_stage_fns(model, params, pl),
                               max_batch=2, max_wait_s=0.002)
    srv.start()
    x = np.ones((1,) + model.input_shape, np.float32)
    reqs = [srv.submit({GraphModel.INPUT: x}) for _ in range(3)]
    _wait(reqs)
    srv.stop()
    np.testing.assert_allclose(np.asarray(reqs[0].result[model.output]),
                               np.asarray(model.apply(params, x)),
                               rtol=2e-4, atol=2e-4)
    for r in reqs:
        spans = {n: (a, b) for n, a, b in r.spans}
        assert list(spans) == ["admit", "queue0", "stage0", "stage0.hop",
                               "stage0.dispatch", "stage0.wait"]
        steps = [(n, *spans[n]) for n in list(spans)[3:]]
        _in_order(steps)
        a, b = spans["stage0"]
        assert a <= steps[0][1] and steps[-1][2] <= b


def test_stage_program_is_named_for_its_model_and_stage():
    """A profile shows ``jit_<model>_stage<s>``, and no name of the
    program's matches the benchmark harness's own annotations."""
    model = synthetic_cnn(6, hw=16)
    pl = plan(model.to_layer_graph(), 2, "balanced_norefine")
    params = model.init(jax.random.PRNGKey(0))
    layers = pl.stage_layers[1]
    boundary = {n: jax.ShapeDtypeStruct((1,) + model.shape_of(n),
                                        np.float32)
                for n in model.nodes[layers[0]].inputs}
    text = model.stage_program(layers, 1).lower(
        {n: params[n] for n in layers if n in params}, boundary).as_text()
    assert "module @jit_synthetic_f6_stage1 " in text
    names = ["synthetic_f6_stage1", "jit_synthetic_f6_stage1"] + [
        f"repro.{n}" for s in (0, 1)
        for n in (f"stage{s}", f"stage{s}.hop", f"stage{s}.dispatch",
                  f"stage{s}.wait")]
    assert not [n for n in names if HARNESS_NAMES.match(n)]


def test_stage_function_receives_the_submitted_object():
    seen = []

    def fn(x):
        seen.append(x)
        return x

    srv = PipelinedModelServer(_plan(1), [fn], max_batch=2,
                               max_wait_s=0.002)
    srv.start()
    payloads = [{"image": i} for i in range(4)]
    reqs = [srv.submit(p) for p in payloads]
    _wait(reqs)
    srv.stop()
    assert len(seen) == 4 and all(a is b for a, b in zip(seen, payloads))
    assert all(r.result is p for r, p in zip(reqs, payloads))


@pytest.mark.parametrize("entry", ["run_batch", "serve_batch"])
def test_batch_entry_points_record_nothing(entry):
    current = []

    def fn(x):
        with span("stage0.hop"):
            current.append(getattr(pipeline._current, "spans", None))
        return x * 2

    if entry == "run_batch":
        ex = PipelineExecutor([fn, lambda x: x + 1])
        outs, _ = ex.run_batch(list(range(5)))
        traced = ex._traced
        ex.stop()
    else:
        srv = PipelinedModelServer(_plan(2), [fn, lambda x: x + 1])
        outs = srv.serve_batch(list(range(5)))
        traced = srv.executor._traced
        srv.stop()
    assert outs == [2 * i + 1 for i in range(5)]
    assert current == [None] * 5 and traced == {}


def test_readmitted_request_spans_continue_where_they_ended():
    """A request that crossed a lost stage is admitted again: its second
    ``admit`` starts where its first ended."""
    srv = PipelinedModelServer(_plan(1), [lambda x: x], max_wait_s=0.002,
                               stage_loss_retries=1)
    srv.executor.start()
    srv.executor.kill_stage(0)
    srv.start()
    r = srv.submit(1)
    assert r.event.wait(10)
    srv.stop()
    assert isinstance(r.error, StageLost) and r.retries == 1
    assert [n for n, _, _ in r.spans] == ["admit", "admit"]
    assert r.spans[0][1] == r.t_submit and r.spans[1][1] == r.spans[0][2]


def test_hedged_stage_records_each_span_once():
    """Replica 0 stalls on item 0 until a hedged twin on replica 1 has
    answered: only the twin's call, the first to finish, is recorded."""
    calls = []
    released = threading.Event()

    def fn(x):
        first = x == 0 and 0 not in calls
        calls.append(x)
        with span("stage0.hop"):
            if first:
                released.wait(5)
        return x

    ex = PipelineExecutor([fn], replicas=[2], hedge_after=0.01)
    spans = [[] for _ in range(3)]
    futs = [ex.submit(i, spans=spans[i]) for i in range(3)]
    assert [f.result(5) for f in futs] == [0, 1, 2]
    t_release = time.perf_counter()
    released.set()
    ex.stop()
    assert calls.count(0) == 2
    for s in spans:
        assert [n for n, _, _ in s] == ["queue0", "stage0", "stage0.hop"]
    assert spans[0][1][2] < t_release


def test_microbatched_call_gives_its_spans_to_every_item():
    def fn(x):
        with span("stage0.hop"):
            return x * 2

    ex = PipelineExecutor([fn], microbatch=4, microbatch_wait_s=0.05)
    spans = [[] for _ in range(4)]
    futs = [ex.submit(np.full((1, 2), i, np.float32), spans=spans[i])
            for i in range(4)]
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(5), np.full((1, 2), 2 * i))
    stacked = ex.microbatch_snapshot()
    ex.stop()
    assert stacked["items"][0] >= 2
    for s in spans:
        assert [n for n, _, _ in s] == ["queue0", "stage0", "stage0.hop"]
    # the items of one stacked call share its stage and step spans
    calls = {s[1] for s in spans}
    assert len(calls) == 4 - stacked["items"][0] + stacked["calls"][0]


def test_pipeline_module_imports_without_jax():
    path = os.path.abspath(pipeline.__file__)
    code = f"""
import importlib.util, sys
sys.modules["jax"] = None
spec = importlib.util.spec_from_file_location("pipeline", {path!r})
m = importlib.util.module_from_spec(spec)
spec.loader.exec_module(m)
def fn(x):
    with m.span("stage0.hop"):
        return x + 1
ex = m.PipelineExecutor([fn])
spans = []
assert ex.submit(1, spans=spans).result(5) == 2
ex.stop()
print(",".join(n for n, _, _ in spans))
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "queue0,stage0,stage0.hop"
