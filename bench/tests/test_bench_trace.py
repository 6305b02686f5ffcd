"""The reduction of profiler traces to device busy time, costliest
operations and named idle gaps, exactly, on a hand-made trace laid out as
the chip's traces are: a ``/device:TPU:<id>`` plane per chip with its
``XLA Ops`` line, and the harness's annotations on a host plane."""
import pytest
from jax.profiler import ProfileData

import _paths  # noqa: F401
from harness import trace

HAND_MADE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 7500000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_apply_subset" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
}
planes {
  id: 3 name: "/device:TPU:2"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } }
}
planes {
  id: 4 name: "/host:CPU"
  lines { id: 1 name: "trace-window" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } }
  lines { id: 2 name: "serve-stage0" timestamp_ns: 5000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 } }
  lines { id: 3 name: "serve-stage1" timestamp_ns: 9000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 21000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "stage0" } }
  event_metadata { key: 3 value { id: 3 name: "stage1" } }
}
'''


def test_reduce_hand_made_trace():
    r = trace.reduce(ProfileData.from_text_proto(HAND_MADE), [0, 1])
    assert r.window_s == pytest.approx(20e-6)
    # chip 0: [1000, 6000] and [8000, 9500] overlapping ops; chip 1's op
    # starts before the window and is clipped to it; chip 2 is not ours
    assert r.busy_by_chip == pytest.approx({0: 6.5e-6, 1: 1e-6})
    assert r.busy_s == pytest.approx(3.75e-6)
    assert r.top_ops == [["chip0 fusion.1", pytest.approx(6e-6)],
                         ["chip0 convolution.2", pytest.approx(1e-6)],
                         ["chip1 fusion.9", pytest.approx(1e-6)]]
    assert dict((k, pytest.approx(v)) for k, v in r.idle_gaps) == {
        "chip0 idle: in stage0 call": 2e-6,
        "chip0 idle: in stage1 call": 11.5e-6,
        "chip1 idle: no stage call open": 3e-6,
        "chip1 idle: in stage0 call": 4e-6,
        "chip1 idle: in stage1 call": 12e-6}
    # stage 1's call ends after the window: not counted in it
    assert r.stage_calls == {0: [pytest.approx((5e-6, 9e-6))], 1: []}


def test_reduce_refuses_a_trace_without_its_window_or_chips():
    no_window = HAND_MADE.replace('"bench_window"', '"other"')
    with pytest.raises(ValueError, match="bench_window"):
        trace.reduce(ProfileData.from_text_proto(no_window), [0])
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce(ProfileData.from_text_proto(HAND_MADE), [0, 5])


def test_op_name():
    assert trace.op_name(
        "%fusion.35 = bf16[112,1,8]{2,1,0:T(8,128)} fusion(bf16[3] %a)") \
        == "fusion.35 = bf16[112,1,8]"
    assert trace.op_name("convolution.2") == "convolution.2"


def test_union():
    assert trace.union([(0, 2), (1, 3), (5, 6), (7, 9)], 0.5, 8) == [
        (0.5, 3), (5, 6), (7, 8)]
    assert trace.union([], 0, 1) == []
