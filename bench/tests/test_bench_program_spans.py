"""The ``program_span`` readers, on requests whose spans are made by hand,
and the trace reduction beside the program's own annotations."""
import os
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from _paths import BENCH
from harness import trace
from harness.spec import load_module
from test_bench_trace import HAND_MADE

MS = 1e-3


def _request(admit, queues, stages):
    """A request whose spans tile its life: ``admit``, then per stage its
    queue wait and its call, the call cut into hop, dispatch and wait
    (each given in ms)."""
    t = 100.0
    spans = [("admit", t, t + admit * MS)]
    t += admit * MS
    for s, (queue, steps) in enumerate(zip(queues, stages)):
        spans.append((f"queue{s}", t, t + queue * MS))
        t += queue * MS
        spans.append((f"stage{s}", t, t + sum(steps) * MS))
        for step, ms in zip(("hop", "dispatch", "wait"), steps):
            spans.append((f"stage{s}.{step}", t, t + ms * MS))
            t += ms * MS
    return SimpleNamespace(spans=spans)


def _run(requests):
    return SimpleNamespace(sent=[(k, 0.0, 0.0, r)
                                 for k, r in enumerate(requests)])


# stage 1 takes longest at the median (5 ms against 3.5), though
# stage 0's slowest call (9 ms) is the longest of all
RUN = _run([
    _request(10.0, [2.0, 1.0], [(1.0, 0.5, 2.0), (0.5, 0.25, 4.25)]),
    _request(20.0, [4.0, 2.0], [(2.0, 0.5, 6.5), (1.0, 0.5, 3.5)]),
    _request(5.0, [1.0, 0.5], [(0.5, 0.5, 2.5), (2.0, 1.0, 2.0)]),
    SimpleNamespace(),      # a request of a program without spans
])


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.mark.parametrize("name, want", [
    ("batch_wait_ms", 10.0),
    ("queue_wait_ms", 3.0),       # of 3, 6 and 1.5
    ("stage_hop_ms", 1.0),        # stage 1's 0.5, 1 and 2
    ("stage_dispatch_ms", 0.5),
    ("stage_wait_ms", 3.5),
])
def test_reader_on_hand_made_spans(name, want):
    assert _reader(name).read(RUN) == pytest.approx(want)


@pytest.mark.parametrize("name", ["batch_wait_ms", "queue_wait_ms",
                                  "stage_hop_ms", "stage_dispatch_ms",
                                  "stage_wait_ms"])
def test_reader_reports_nothing_without_spans(name):
    assert _reader(name).read(_run([SimpleNamespace(),
                                    SimpleNamespace(spans=[])])) is None
    assert _reader(name).read(_run([])) is None


# the program's annotations on the thread of the harness's stage-0 call:
# repro.stage0 around it, hop, dispatch and wait inside it
PROGRAM = HAND_MADE.replace(
    'events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 } }',
    'events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }\n'
    '    events { metadata_id: 4 offset_ps: 0 duration_ps: 4100000 }\n'
    '    events { metadata_id: 5 offset_ps: 0 duration_ps: 1000000 }\n'
    '    events { metadata_id: 6 offset_ps: 1000000 duration_ps: 500000 }\n'
    '    events { metadata_id: 7 offset_ps: 1500000 duration_ps: 2500000 }'
    ' }').replace(
    'event_metadata { key: 1 value { id: 1 name: "bench_window" } }',
    'event_metadata { key: 1 value { id: 1 name: "bench_window" } }\n'
    '  event_metadata { key: 4 value { id: 4 name: "repro.stage0" } }\n'
    '  event_metadata { key: 5 value { id: 5 name: "repro.stage0.hop" } }\n'
    '  event_metadata { key: 6 value { id: 6 name: "repro.stage0.dispatch"'
    ' } }\n'
    '  event_metadata { key: 7 value { id: 7 name: "repro.stage0.wait" } }')


def test_program_annotations_leave_the_reduction_as_it_was():
    assert PROGRAM.count("repro.stage0") == 4
    plain = trace.reduce(ProfileData.from_text_proto(HAND_MADE), [0, 1])
    beside = trace.reduce(ProfileData.from_text_proto(PROGRAM), [0, 1])
    assert beside.stage_calls == plain.stage_calls
    assert beside.idle_gaps == plain.idle_gaps
    assert beside.busy_by_chip == plain.busy_by_chip
