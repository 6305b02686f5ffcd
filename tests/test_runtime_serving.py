"""Runtime (fault tolerance, stragglers, elastic) and serving tests."""
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from conftest import api_plan as plan
from repro.core import EdgeTPUModel
from repro.core.pipeline import (PipelineExecutor, simulated_stage,
                                 stage_balance_metrics)
from repro.models.cnn import synthetic_cnn
from repro.runtime import (ElasticPlanner, FailureInjector, SpeculativeExecutor,
                           TrainSupervisor)
from repro.serving import MicroBatcher, PipelinedModelServer


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def _counting_step():
    seen = []

    def step_fn(state, step):
        seen.append(step)
        return {"x": state["x"] + 1}, {"loss": float(state["x"])}

    return step_fn, seen


def test_supervisor_restarts_from_checkpoint(tmp_path):
    step_fn, seen = _counting_step()
    store = CheckpointStore(str(tmp_path), keep=3)
    sup = TrainSupervisor(store, step_fn, ckpt_every=5, async_ckpt=False,
                          injector=FailureInjector(fail_at_steps=[12]))
    state, report = sup.run({"x": jnp.array(0)}, 20)
    assert report.restarts == 1
    assert report.final_step == 20
    # replayed steps 10..12 after restoring the step-10 checkpoint
    assert seen.count(11) == 2
    # state reflects exactly 20 effective steps (replay is idempotent
    # because state was restored)
    assert int(state["x"]) == 20


def test_supervisor_restart_budget(tmp_path):
    step_fn, _ = _counting_step()
    store = CheckpointStore(str(tmp_path))
    inj = FailureInjector(fail_at_steps=[])

    def always_fail(state, step):
        raise RuntimeError("boom")

    sup = TrainSupervisor(store, always_fail, ckpt_every=5, max_restarts=2,
                          async_ckpt=False)
    with pytest.raises(RuntimeError, match="restart budget"):
        sup.run({"x": jnp.array(0)}, 10)


def test_supervisor_resumes_across_runs(tmp_path):
    step_fn, _ = _counting_step()
    store = CheckpointStore(str(tmp_path), keep=3)
    sup = TrainSupervisor(store, step_fn, ckpt_every=5, async_ckpt=False)
    state, _ = sup.run({"x": jnp.array(0)}, 10)
    # a "new process" picks up from the latest checkpoint
    step_fn2, seen2 = _counting_step()
    sup2 = TrainSupervisor(store, step_fn2, ckpt_every=5, async_ckpt=False)
    state2, report2 = sup2.run({"x": jnp.array(0)}, 20)
    assert min(seen2) == 10               # did not replay from scratch
    assert int(state2["x"]) == 20


# ---------------------------------------------------------------------------
# stragglers
# ---------------------------------------------------------------------------
def test_speculative_executor_hedges_stragglers():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.2)               # first call straggles
        return x * 2

    ex = SpeculativeExecutor(flaky, hedge_after=0.03)
    assert ex.submit(21) == 42
    assert ex.hedged == 1
    ex.shutdown()


def test_speculative_executor_fast_path():
    ex = SpeculativeExecutor(lambda x: x + 1, hedge_after=0.5)
    assert ex.map([1, 2, 3]) == [2, 3, 4]
    assert ex.hedged == 0
    ex.shutdown()


# ---------------------------------------------------------------------------
# elastic replanning
# ---------------------------------------------------------------------------
def test_elastic_replan_is_fast_and_cached():
    g = synthetic_cnn(600).to_layer_graph()
    ep = ElasticPlanner(g, "balanced")
    p4 = ep.on_resize(4)
    p3 = ep.on_resize(3)                  # a device died
    assert p4.n_stages == 4 and p3.n_stages == 3
    assert ep.replan_times[3] < 1.0       # paper §2.2: fast partitioning
    assert ep.on_resize(4) is p4          # cached


# ---------------------------------------------------------------------------
# pipeline executor + analytical time model
# ---------------------------------------------------------------------------
def test_pipeline_order_and_errors():
    ex = PipelineExecutor([lambda x: x + 1, lambda x: x * 2])
    outs, busy = ex.run_batch(list(range(10)), collect_stage_times=True)
    assert outs == [(i + 1) * 2 for i in range(10)]
    assert len(busy) == 2

    def boom(x):
        raise ValueError("stage died")

    ex2 = PipelineExecutor([lambda x: x, boom])
    with pytest.raises(ValueError, match="stage died"):
        ex2.run_batch([1, 2])


def test_pipeline_time_matches_model():
    """Wall-clock of simulated stages ~= fill + (B-1)*max_stage."""
    lat = [0.01, 0.03, 0.01]
    ex = PipelineExecutor([simulated_stage(l) for l in lat])
    n = 10
    _, dt, busy = ex.timed_run(list(range(n)))
    model = sum(lat) + (n - 1) * max(lat)
    assert dt == pytest.approx(model, rel=0.35)
    m = stage_balance_metrics(busy)
    assert m["max_stage_s"] >= m["mean_stage_s"]


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def test_microbatcher_gathers_up_to_max():
    mb = MicroBatcher(max_batch=4)
    for i in range(6):
        mb.submit(i)
    b1 = mb.next_ready()
    b2 = mb.next_ready()
    assert [r.payload for r in b1] == [0, 1, 2, 3]
    assert [r.payload for r in b2] == [4, 5]


def test_pipelined_server_end_to_end():
    g = synthetic_cnn(600).to_layer_graph()
    pl = plan(g, 3, "balanced_norefine")
    fns = [lambda x: x + 1, lambda x: x * 2, lambda x: x - 3]
    srv = PipelinedModelServer(pl, fns, max_batch=8, max_wait_s=0.02)
    outs = srv.serve_batch([1, 2, 3])
    assert outs == [(x + 1) * 2 - 3 for x in (1, 2, 3)]
    srv.start()
    reqs = [srv.submit(i) for i in range(5)]
    for i, r in enumerate(reqs):
        assert r.event.wait(5)
        assert r.result == (i + 1) * 2 - 3
    srv.stop()
    assert srv.stats["requests"] >= 8
