"""Profiler traces: recording a few seconds of the window, and reducing
them to device busy time, the costliest device operations and the idle
gaps named by what the host was doing.

The trace is read with ``jax.profiler.ProfileData`` alone.  A chip is the
plane ``/device:TPU:<id>``; its operations are the events of its
``XLA Ops`` line, and busy time is the union of their intervals inside
the traced window.  The window is the harness's own ``bench_window``
annotation; the harness's ``stage<s>`` annotations, on the host plane,
name each stretch of an idle gap by the stage calls open on the host.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import threading
import time
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import jax

WINDOW = "bench_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_STAGE = re.compile(r"^stage(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10

Interval = Tuple[float, float]


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off and the host tracer at
    its first level: on the host, only annotations such as the harness's
    are recorded, not the runtime's own events, which cost the host
    about half its rate at level 2."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def record_until(log_dir: str, start_at: float, end_at: float,
                 before=None) -> threading.Thread:
    """A thread that, at ``start_at``, calls ``before`` and starts the
    profiler, and marks ``[start_at, end_at]`` as the traced window.  The
    caller stops the profiler (``jax.profiler.stop_trace``) once the work
    it wants traced has ended, since the stage calls stand still while it
    stops."""

    def run() -> None:
        wait = start_at - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if before is not None:
            before()
        start(log_dir)
        with jax.profiler.TraceAnnotation(WINDOW):
            time.sleep(max(0.0, end_at - time.perf_counter()))

    t = threading.Thread(target=run, name="trace-window")
    t.start()
    return t


def load(log_dir: str) -> "jax.profiler.ProfileData":
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def union(intervals: Sequence[Interval], lo: float,
          hi: float) -> List[Interval]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, sorted."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


@dataclasses.dataclass
class Reduced:
    """A trace reduced to what the metrics read; times in seconds."""
    window_s: float
    busy_by_chip: Dict[int, float]
    top_ops: List[List]
    idle_gaps: List[List]
    # stage index -> [(start, end)] of the harness's stage calls that
    # ended inside the window, on the trace's clock
    stage_calls: Dict[int, List[Interval]]

    @property
    def busy_s(self) -> float:
        """Device-busy seconds, averaged over the chips."""
        return sum(self.busy_by_chip.values()) / len(self.busy_by_chip)


def reduce(data, chip_ids: Sequence[int]) -> Reduced:
    """Reduce a loaded trace for the chips ``chip_ids``."""
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    window = None
    stage_spans: Dict[int, List[Interval]] = defaultdict(list)
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            if int(m.group(1)) in chip_ids:
                ops[int(m.group(1))] = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for line in plane.lines if line.name == OPS_LINE
                    for e in line.events]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                else:
                    s = _STAGE.match(e.name)
                    if s:
                        stage_spans[int(s.group(1))].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    missing = set(chip_ids) - set(ops)
    if missing:
        raise ValueError(f"trace has no device plane for chips {missing}")
    lo, hi = window
    busy: Dict[int, float] = {}
    per_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    host = host_segments(stage_spans, lo, hi)
    for chip, evs in ops.items():
        u = union([(a, b) for _, a, b in evs], lo, hi)
        busy[chip] = sum(b - a for a, b in u) * 1e-9
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                per_op[f"chip{chip} {op_name(name)}"] += (b - a) * 1e-9
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for label, secs in overlap(idle, host).items():
            gaps[f"chip{chip} idle: {label}"] += secs
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:TOP]
    in_window = {s: sorted((a * 1e-9, b * 1e-9) for a, b in v
                           if lo <= b <= hi)
                 for s, v in stage_spans.items()}
    return Reduced(window_s=(hi - lo) * 1e-9, busy_by_chip=busy,
                   top_ops=[[n, v] for n, v in top],
                   idle_gaps=[[n, v] for n, v in idle],
                   stage_calls=in_window)


def op_name(text: str) -> str:
    """An operation's name and result type from its HLO text:
    ``%fusion.35 = bf16[112,1,8,16,64]{...} fusion(...)`` gives
    ``fusion.35 = bf16[112,1,8,16,64]``."""
    return text.split("{", 1)[0].lstrip("%")[:100]


def host_segments(spans: Dict[int, List[Interval]], lo: float,
                  hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut into segments labelled by the stage calls open on
    the host in each."""
    marks = sorted([(a, 1, s) for s, v in spans.items() for a, _ in v]
                   + [(b, -1, s) for s, v in spans.items() for _, b in v])
    out: List[Tuple[float, float, str]] = []
    open_: Dict[int, int] = defaultdict(int)
    t = lo
    for x, step, s in marks + [(hi, 0, -1)]:
        x = min(max(x, lo), hi)
        if x > t:
            names = sorted(k for k, n in open_.items() if n > 0)
            label = ("in " + "+".join(f"stage{k}" for k in names) + " call"
                     if names else "no stage call open")
            if out and out[-1][2] == label and out[-1][1] == t:
                out[-1] = (out[-1][0], x, label)
            else:
                out.append((t, x, label))
            t = x
        if step:
            open_[s] += step
    return out


def overlap(gaps: List[Interval],
            segments: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Seconds of ``gaps`` (sorted, disjoint) under each segment label."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            x, y, label = segments[k]
            d = min(b, y) - max(a, x)
            if d > 0:
                out[label] += d * 1e-9
            k += 1
    return out
