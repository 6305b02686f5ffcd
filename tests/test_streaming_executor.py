"""Streaming executor tests: submit()/Future semantics, equivalence with
run_batch (ordering, failure forwarding, replicated stages) under a
randomized concurrent-submitter stress, stop() completing in-flight
futures, monotonic busy accounting, and shape-bucketed dynamic
micro-batching."""
import random
import threading
import time

import numpy as np
import pytest

from repro.core.pipeline import (PipelineExecutor, PipelineStopped,
                                 simulated_stage, stage_balance_metrics)
from repro.runtime import ElasticPlanner
from repro.serving import (MicroBatcher, PipelinedModelServer, Request,
                           latency_percentiles)
from conftest import api_plan as plan
from repro.models.cnn import synthetic_cnn


# ---------------------------------------------------------------------------
# submit() semantics
# ---------------------------------------------------------------------------
def test_submit_returns_future_with_result():
    with PipelineExecutor([lambda x: x + 1, lambda x: x * 2]) as ex:
        futs = [ex.submit(i) for i in range(10)]
        assert [f.result(timeout=5) for f in futs] == \
            [(i + 1) * 2 for i in range(10)]
        assert ex.in_flight == 0


def test_submit_failure_resolves_future_with_original_error():
    def boom(x):
        if x == 3:
            raise ValueError("item three")
        return x * 10

    with PipelineExecutor([boom]) as ex:
        futs = [ex.submit(i) for i in range(6)]
        for i, f in enumerate(futs):
            if i == 3:
                with pytest.raises(ValueError, match="item three"):
                    f.result(timeout=5)
            else:
                assert f.result(timeout=5) == i * 10


def test_submit_after_stop_raises():
    ex = PipelineExecutor([lambda x: x])
    ex.run_batch([1])
    ex.stop()
    # a stopped executor restarts on submit (same contract as run_batch)
    assert ex.submit(2).result(timeout=5) == 2
    ex.stop()


def test_streams_interleave_without_barrier():
    """Two callers' items overlap in flight; each gets its own results."""
    with PipelineExecutor([simulated_stage(0.002), lambda x: x * 2]) as ex:
        a = [ex.submit(("a", i)) for i in range(8)]
        b = [ex.submit(("b", i)) for i in range(8)]
        assert [f.result(timeout=5) for f in a] == \
            [("a", i, "a", i) for i in range(8)]
        assert [f.result(timeout=5) for f in b] == \
            [("b", i, "b", i) for i in range(8)]


# ---------------------------------------------------------------------------
# streaming vs run_batch equivalence (ordering, failures, replicas)
# ---------------------------------------------------------------------------
def _jittered_fns(seed):
    rng = random.Random(seed)

    def jitter(x):
        time.sleep(rng.random() * 0.002)
        return x * 2.0 + 1.0

    return [lambda x: x + 0.5, jitter, lambda x: x - 0.25]


@pytest.mark.parametrize("replicas", [None, [1, 4, 1]])
def test_streaming_matches_run_batch_bit_identical(replicas):
    fns = _jittered_fns(0)
    inputs = [i * 0.1 for i in range(40)]
    with PipelineExecutor(fns) as base:
        expect, _ = base.run_batch(inputs)
    with PipelineExecutor(fns, replicas=replicas) as ex:
        futs = [ex.submit(x) for x in inputs]
        streamed = [f.result(timeout=10) for f in futs]
        assert streamed == expect          # same floats, same order
        batched, _ = ex.run_batch(inputs)  # run_batch over the same stream
        assert batched == expect


@pytest.mark.parametrize("replicas", [None, [2, 3]])
def test_concurrent_submitters_randomized_stress(replicas):
    """Several threads submit interleaved items (some failing) through a
    jittery, optionally replicated pipeline; every thread sees its own
    results, in its own order, with failures attributed per item."""
    rng = random.Random(42)

    def jitter(x):
        time.sleep(rng.random() * 0.001)
        return x

    def boom(x):
        if x[1] % 7 == 3:
            raise ValueError(f"bad {x}")
        return (x[0], x[1] * 2)

    n_threads, n_items = 4, 30
    results = [None] * n_threads

    with PipelineExecutor([jitter, boom], queue_size=8,
                          replicas=replicas) as ex:
        def submitter(t):
            futs = [ex.submit((t, i)) for i in range(n_items)]
            out = []
            for i, f in enumerate(futs):
                try:
                    out.append(f.result(timeout=30))
                except ValueError:
                    out.append("failed")
            results[t] = out

        threads = [threading.Thread(target=submitter, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive(), "submitter hung"

    for t in range(n_threads):
        expect = ["failed" if i % 7 == 3 else (t, i * 2)
                  for i in range(n_items)]
        assert results[t] == expect


def test_run_batch_first_error_in_submission_order_after_drain():
    def boom(x):
        if x % 3 == 0:
            raise RuntimeError(f"item {x}")
        return x

    ex = PipelineExecutor([boom])
    with pytest.raises(RuntimeError, match="item 0"):
        ex.run_batch(list(range(7)))
    outs, _ = ex.run_batch([1, 2, 4])      # drained, still usable
    assert outs == [1, 2, 4]
    ex.stop()


# ---------------------------------------------------------------------------
# stop() with in-flight futures
# ---------------------------------------------------------------------------
def test_stop_completes_inflight_futures_not_hang():
    ex = PipelineExecutor([simulated_stage(0.25)])
    futs = [ex.submit(i) for i in range(6)]
    time.sleep(0.05)
    t0 = time.perf_counter()
    ex.stop(timeout=0.2)                   # too short to drain 1.5s of work
    assert time.perf_counter() - t0 < 2.0
    for f in futs:
        try:
            f.result(timeout=0.5)          # completed normally before stop
        except PipelineStopped:
            pass                           # or cancelled by stop — never hangs


def test_clean_stop_drains_inflight_normally():
    ex = PipelineExecutor([simulated_stage(0.02)])
    futs = [ex.submit(i) for i in range(5)]
    ex.stop()                              # default timeout: full drain
    assert [f.result(timeout=0.1) for f in futs] == list(range(5))


# ---------------------------------------------------------------------------
# monotonic busy accounting
# ---------------------------------------------------------------------------
def test_busy_counters_are_monotonic_with_snapshot_deltas():
    ex = PipelineExecutor([simulated_stage(0.01), simulated_stage(0.002)])
    _, busy1 = ex.run_batch([0] * 5, collect_stage_times=True)
    _, busy2 = ex.run_batch([0] * 5, collect_stage_times=True)
    # per-batch deltas, not cumulative (loose bounds: sleeps overshoot
    # under load; the monotonicity property below is the real assertion)
    assert 0.02 < busy1[0] < 0.3
    assert 0.02 < busy2[0] < 0.3
    # ...while the raw snapshot keeps growing
    total = ex.busy_snapshot()
    assert total[0] == pytest.approx(busy1[0] + busy2[0], rel=0.01)
    ex.stop()


def test_stage_balance_metrics_empty_is_neutral():
    m = stage_balance_metrics([])
    assert m == {"max_stage_s": 0.0, "mean_stage_s": 0.0,
                 "max_minus_mean_s": 0.0, "balance": 1.0}
    # and a snapshot interval with traffic still works end to end
    m2 = stage_balance_metrics([0.5, 0.25, 0.25])
    assert m2["balance"] == pytest.approx(1 / 1.5)


# ---------------------------------------------------------------------------
# dynamic micro-batching
# ---------------------------------------------------------------------------
def test_microbatch_stacks_same_shape_prefix_and_preserves_order():
    sizes = []

    def fn(x):
        sizes.append(int(x.shape[0]))
        return x * 2.0

    with PipelineExecutor([fn], microbatch=4,
                          microbatch_wait_s=0.02) as ex:
        payloads = [np.full((1, 3), float(i)) for i in range(12)]
        outs, _ = ex.run_batch(payloads)
    for i, o in enumerate(outs):
        assert o.shape == (1, 3) and float(o[0, 0]) == 2.0 * i
    assert any(s > 1 for s in sizes)       # something actually stacked
    snap = ex.microbatch_snapshot()
    assert snap["items"][0] >= snap["calls"][0]


def test_microbatch_mixed_shapes_bucket_breaks_keep_fifo():
    def fn(x):
        return x + 1.0

    with PipelineExecutor([fn], microbatch=8,
                          microbatch_wait_s=0.01) as ex:
        ps = [np.full((1, 2), float(i)) if i % 3 else
              np.full((1, 5), float(i)) for i in range(10)]
        outs, _ = ex.run_batch(ps)
    for p, o in zip(ps, outs):
        assert o.shape == p.shape and np.allclose(o, p + 1.0)


def test_microbatch_non_array_payloads_run_singly():
    with PipelineExecutor([lambda x: x * 2], microbatch=4) as ex:
        outs, _ = ex.run_batch([1, 2, 3])
    assert outs == [2, 4, 6]
    assert ex.microbatch_snapshot()["calls"] == [0]


def test_microbatch_unstackable_output_falls_back_per_item():
    probes = []

    def reduces(x):                        # (rows,3)->(1,3): wrong leading
        probes.append(int(x.shape[0]))
        return x.sum(axis=0, keepdims=True)

    with PipelineExecutor([reduces], microbatch=4,
                          microbatch_wait_s=0.02) as ex:
        ps = [np.full((2, 3), float(i)) for i in range(6)]
        outs, _ = ex.run_batch(ps)
        outs2, _ = ex.run_batch(ps)
    for o_list in (outs, outs2):
        for i, o in enumerate(o_list):
            assert o.shape == (1, 3) and float(o[0, 0]) == 2.0 * i
    # the stage is marked unstackable after at most one wasted probe:
    # no stacked call is ever counted, and later traffic runs per-item
    # without further stacked probes
    assert ex.microbatch_snapshot()["calls"] == [0]
    assert sum(1 for r in probes if r > 2) <= 1


def test_microbatch_failure_attributed_to_the_right_item():
    def maybe_boom(x):
        if np.any(x == 3.0):               # fails batched and singly
            raise ValueError("bad three")
        return x

    with PipelineExecutor([maybe_boom], microbatch=4,
                          microbatch_wait_s=0.02) as ex:
        futs = [ex.submit(np.full((1, 2), float(i))) for i in range(6)]
        for i, f in enumerate(futs):
            if i == 3:
                with pytest.raises(ValueError, match="bad three"):
                    f.result(timeout=5)
            else:
                assert float(f.result(timeout=5)[0, 0]) == float(i)


def test_microbatch_validation():
    with pytest.raises(ValueError):
        PipelineExecutor([lambda x: x], microbatch=[1, 2])
    with pytest.raises(ValueError):
        PipelineExecutor([lambda x: x], microbatch=0)


# ---------------------------------------------------------------------------
# streaming server
# ---------------------------------------------------------------------------
def _toy_server(n_stages=3, **kw):
    g = synthetic_cnn(600).to_layer_graph()
    pl = plan(g, n_stages, "balanced_norefine")
    fns = [lambda x: x + 1, lambda x: x * 2, lambda x: x - 3][:n_stages]
    return PipelinedModelServer(pl, fns, **kw), pl


def test_server_streaming_per_request_futures_and_snapshot():
    srv, _ = _toy_server(max_batch=4, max_wait_s=0.005)
    srv.start()
    reqs = [srv.submit(i) for i in range(9)]
    for i, r in enumerate(reqs):
        assert r.event.wait(5)
        assert r.error is None and r.result == (i + 1) * 2 - 3
        assert r.latency >= 0.0
    snap = srv.snapshot()
    assert snap["requests"] == 9 and snap["failed"] == 0
    assert snap["latency"]["n"] == 9
    assert snap["latency"]["p50_s"] <= snap["latency"]["p99_s"]
    assert len(snap["stage_busy_s"]) == 3
    # the window resets: an immediate snapshot sees nothing new
    assert srv.snapshot()["requests"] == 0
    srv.stop()


def test_server_stop_completes_unserved_requests_with_error():
    srv, _ = _toy_server(max_batch=2, max_wait_s=0.01)
    # never started: requests sit in the batcher until stop()
    reqs = [srv.submit(i) for i in range(3)]
    srv.stop()
    for r in reqs:
        assert r.event.wait(2), "request hung through stop()"
        assert r.error is not None
    assert srv.stats["failed"] == 3


def test_server_reconfigure_hot_swaps_plan_and_fns():
    srv, _ = _toy_server(max_batch=4, max_wait_s=0.005)
    srv.start()
    r = srv.submit(1)
    assert r.event.wait(5) and r.result == 1
    g = synthetic_cnn(600).to_layer_graph()
    pl2 = plan(g, 2, "balanced_norefine")
    srv.reconfigure(pl2, [lambda x: x + 10, lambda x: x * 3])
    assert srv.plan is pl2 and srv.executor.n_stages == 2
    r2 = srv.submit(1)
    assert r2.event.wait(5) and r2.result == 33
    srv.stop()


def test_elastic_planner_resize_server_hook():
    g = synthetic_cnn(600).to_layer_graph()
    pl = plan(g, 3, "balanced_norefine")
    srv = PipelinedModelServer(pl, [lambda x: x] * 3, max_batch=4,
                               max_wait_s=0.005)
    srv.start()
    ep = ElasticPlanner(g, "balanced_norefine")

    def builder(p):
        return [lambda x: x + 1] * p.n_stages

    pl2 = ep.resize_server(srv, builder, 2)   # a device left
    assert pl2.n_stages == 2 and srv.plan is pl2
    r = srv.submit(5)
    assert r.event.wait(5) and r.result == 7   # two +1 stages
    srv.stop()


# ---------------------------------------------------------------------------
# MicroBatcher + Request satellites
# ---------------------------------------------------------------------------
def test_microbatcher_empty_wait_is_bounded():
    mb = MicroBatcher(max_batch=4)
    t0 = time.perf_counter()
    assert mb.next_ready() == []
    assert time.perf_counter() - t0 < 0.2


def test_microbatcher_next_ready_hands_on_first_arrival_at_once():
    """A request that arrives while next_ready blocks is returned at once,
    alone: nothing waits for a second one."""
    mb = MicroBatcher(max_batch=8)

    def late_puts():
        time.sleep(0.03)
        mb.submit(1)
        time.sleep(0.3)
        mb.submit(2)

    threading.Thread(target=late_puts, daemon=True).start()
    batch = []
    while not batch:                      # an idle poll may come first
        batch = mb.next_ready()
    assert time.perf_counter() - batch[0].t_submit < 0.1
    assert [r.payload for r in batch] == [1]


def _admit_s(req):
    return sum(b - a for name, a, b in req.spans if name == "admit")


def test_per_item_admission_hands_on_without_window():
    """A lone request is handed on at once: the deprecated max_wait_s of
    0.5 s holds nothing back."""
    srv, _ = _toy_server(max_batch=15, max_wait_s=0.5)
    srv.start()
    try:
        for i in range(3):
            r = srv.submit(i)
            assert r.event.wait(5) and r.error is None
            assert r.result == (i + 1) * 2 - 3
            assert _admit_s(r) < 0.1
    finally:
        srv.stop()
    assert srv.stats["admitted"] == 3


def test_stacking_server_admits_at_once_and_executor_stacks():
    """With stage 0 stacking (microbatch=4), admission still holds
    nothing back; the executor's microbatch_wait_s forms the bucket."""
    srv, _ = _toy_server(max_batch=4, max_wait_s=0.5, microbatch=4,
                         microbatch_wait_s=0.2)
    assert srv.executor.microbatch[0] == 4
    srv.start()
    try:
        reqs = [srv.submit(np.full((1, 3), float(i))) for i in range(4)]
        for i, r in enumerate(reqs):
            assert r.event.wait(5) and r.error is None
            assert r.result.shape == (1, 3)
            assert float(r.result[0, 0]) == (i + 1) * 2 - 3
            assert _admit_s(r) < 0.1
    finally:
        srv.stop()
    snap = srv.executor.microbatch_snapshot()
    assert snap["calls"][0] >= 1 and snap["items"][0] >= 2


def test_admission_hands_on_at_once_across_reconfigure():
    srv, _ = _toy_server(max_batch=4, max_wait_s=0.5)
    srv.start()
    try:
        r = srv.submit(1)
        assert r.event.wait(5) and r.result == 1
        assert _admit_s(r) < 0.1
        g = synthetic_cnn(600).to_layer_graph()
        srv.reconfigure(plan(g, 2, "balanced_norefine"),
                        [lambda x: x + 10, lambda x: x * 3])
        for i in range(2):
            r = srv.submit(i)
            assert r.event.wait(5) and r.error is None
            assert r.result == (i + 10) * 3
            assert _admit_s(r) < 0.1
    finally:
        srv.stop()
    assert srv.stats["admitted"] == 3


def test_request_ids_unique_across_reused_payloads():
    mb = MicroBatcher()
    payload = object()                     # same object every time
    rids = {mb.submit(payload).rid for _ in range(50)}
    assert len(rids) == 50
    # ids also survive payload GC / address reuse
    rids |= {mb.submit(tuple([i])).rid for i in range(50)}
    assert len(rids) == 100


def test_latency_percentiles_shapes():
    assert latency_percentiles([])["n"] == 0
    p = latency_percentiles([0.001 * i for i in range(1, 101)])
    assert p["p50_s"] <= p["p95_s"] <= p["p99_s"] <= p["max_s"]
    assert p["n"] == 100
