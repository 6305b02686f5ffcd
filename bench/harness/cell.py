"""One run of one cell: set-up, the measured window, and its readings.

The program is driven through its front door as its users drive it:
``deploy(spec, graph=..., stage_fn_builder=...)`` with
``build_stage_fns`` placing stage ``s`` on its chip, then
``Deployment.serve()`` and ``PipelinedModelServer.submit`` for every
request.  Everything else here (weights, images, traffic, timing, the
reference) belongs to the benchmark.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compare, reference, stats, trace
from .spans import Spans
from .spec import Cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.api import DeploymentSpec, deploy  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.cnn import REAL_CNNS  # noqa: E402
from repro.models.layers import GraphModel, build_stage_fns  # noqa: E402

# a request not answered this long after the window closed never comes
ANSWER_WAIT_S = 60.0
# requests served before the window, through the admission loop
WARM_REQUESTS = 32
# seconds at the end of the window that the profiler records in a traced
# run; it slows every stage call by about a fifth, so the host-clocked
# readers take the requests due before it started, less a margin
TRACE_S = 2.0
HOST_MARGIN_S = 0.5
TRACE_DIR = os.path.join(ROOT, ".bench_out", "trace")


def seed_words(seed: int, n: int) -> List[jax.Array]:
    """``n`` independent PRNG keys from any whole number."""
    words = np.random.SeedSequence(seed).generate_state(2 * n)
    return [jnp.asarray(words[2 * i:2 * i + 2], jnp.uint32)
            for i in range(n)]


def use_compile_cache() -> None:
    """The program's persistent compilation cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), for every
    program however fast it compiles, so that only a checkout's first run
    compiles."""
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


class CollectorPauses:
    """Python's garbage-collector passes while ``on``: ``(generation,
    seconds)`` each.  Every thread, the generator's among them, stops for
    as long as a pass lasts."""

    def __init__(self) -> None:
        self.on = False
        self.pauses: List[tuple] = []
        self._t: Optional[float] = None
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on and self._t is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._event)

    def describe(self) -> str:
        longest = max((s for _, s in self.pauses), default=0.0)
        full = sum(1 for g, _ in self.pauses if g == 2)
        return (f"{len(self.pauses)} ({full} of the oldest generation), "
                f"longest {longest * 1e3:.4f} ms")


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, XLA compilation and
    compile-cache lookups) while ``on``."""

    PREFIXES = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self) -> None:
        self.on = False
        self.count = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, *_: Any, **__: Any) -> None:
        if self.on and name.startswith(self.PREFIXES):
            self.count += 1


@dataclasses.dataclass
class Served:
    """A cell deployed and warmed up, ready for its window."""
    model: Any
    params: Any
    pool: np.ndarray
    server: Any
    submit: Callable[[int], Any]
    stage_chips: List[int]
    spans: Optional[Spans]


def make_inputs(arch: Dict[str, Any], seed: int, n_pool: int, chip):
    """The weights (on ``chip``) and the image pool (on the host) of
    ``seed``, each made in one program on the chip.  Pixels are N(0, 1)
    on the bfloat16 grid, as the weights are (``reference.make_params``)."""
    k_params, k_images = seed_words(seed, 2)
    with jax.default_device(chip):
        params = jax.jit(lambda k: reference.make_params(arch, k))(k_params)
        pool = np.asarray(jax.jit(lambda k: reference.on_bf16_grid(
            jax.random.normal(k, (n_pool,) + tuple(arch["input_shape"]))))(
                k_images))
    return params, pool


@contextlib.contextmanager
def serving(cell: Cell, seed: int, chips: Sequence[Any], traced: bool,
            log=print) -> Iterator[Served]:
    """Deploy ``cell`` through the front door with the weights and images
    of ``seed``, warm it up, and stop it on exit."""
    arch = cell.config
    model = REAL_CNNS[arch["model"]]()
    want = jax.tree.map(lambda a: a.shape,
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    if want != reference.param_shapes(arch):
        raise SystemExit(f"{arch['name']}: the program's {arch['model']} "
                         f"does not have the layers the configuration "
                         f"states")
    n_pool = cell.traffic["pool"]
    t = time.perf_counter()
    params, pool = make_inputs(arch, seed, n_pool, chips[0])
    log(f"set-up: weights and images {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()

    spec = DeploymentSpec(model=f"cnn:{arch['model']}",
                          stages=arch["stages"])
    spans = Spans(arch["stages"]) if traced else None

    def place(n: int) -> List[int]:
        # stage s on chip s across chips; every stage on the one chip
        return [s if len(chips) > 1 else 0 for s in range(n)]

    def builder(plan):
        devs = [chips[c] for c in place(plan.n_stages)]
        fns = build_stage_fns(model, params, plan, devices=devs)
        return [spans.wrap(s, f) for s, f in enumerate(fns)] if spans \
            else fns

    dep = deploy(spec, graph=model.to_layer_graph(), stage_fn_builder=builder)
    plan = dep.plan
    stage_chips = place(plan.n_stages)
    log(f"plan: strategy {spec.strategy}, cost source {spec.cost_source}, "
        f"max_batch {spec.max_batch}, max_wait_s {spec.max_wait_s}, "
        f"microbatch {spec.microbatch}; {plan.describe()}")
    log("cuts: " + "; ".join(
        f"stage {s} on chip {stage_chips[s]}: {len(ls)} layers "
        f"{ls[0]}..{ls[-1]}" for s, ls in enumerate(plan.stage_layers)))

    def payload(k: int) -> Dict[str, np.ndarray]:
        i = k % n_pool
        return {GraphModel.INPUT: pool[i:i + 1]}

    server = dep.serve()
    server.executor.start()
    try:
        # every stage program, the hops and the admission path
        server.serve_batch([payload(k) for k in range(2)])
        server.start()
        warm = [server.submit(payload(k)) for k in range(WARM_REQUESTS)]
        for r in warm:
            if not r.event.wait(ANSWER_WAIT_S) or r.error is not None:
                raise RuntimeError(f"warm-up request failed: {r.error}")

        log(f"set-up: plan, stage build and warm-up "
            f"{time.perf_counter() - t:.3f} s")
        # what set-up made is kept out of the collector's scans, so that a
        # collection inside the window does not walk the whole heap
        gc.collect()
        gc.freeze()

        def submit(k: int):
            p = payload(k)
            if spans is None:
                return server.submit(p)
            spans.submitted(p, k)
            with jax.profiler.TraceAnnotation("submit"):
                return server.submit(p)

        yield Served(model=model, params=params, pool=pool, server=server,
                     submit=submit, stage_chips=stage_chips, spans=spans)
    finally:
        dep.close()
        gc.unfreeze()


def window(served: Served, generator, mix: Dict[str, Any], seed: int,
           seconds: float, t0: float):
    """Drive ``generator`` from ``t0`` for ``seconds``; returns what was
    sent.  The server's ``snapshot()`` counts from here on.  Waits until
    every request sent is answered, or ``ANSWER_WAIT_S`` past the close."""
    served.server.snapshot()
    sent = generator.drive(served.submit, mix, seed, seconds, t0)
    t_close = t0 + seconds
    wait = t_close - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    for _, _, _, r in sent:
        r.event.wait(max(0.0, t_close + ANSWER_WAIT_S - time.perf_counter()))
    return sent


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read from one traced run: the requests
    due in the window before the profiler started (less
    ``HOST_MARGIN_S``), the server's snapshot up to its start, and the
    reduced trace of the window's last ``TRACE_S`` seconds."""
    cell: Cell
    chips: Sequence[Any]
    stage_chips: List[int]
    t0: float
    seconds: float
    sent: List[Any]
    spans: Optional[Spans]
    snapshot: Dict[str, Any]
    trace: Optional[trace.Reduced]


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             chips: Sequence[Any], t_start: float,
             log=print) -> Dict[str, Any]:
    """Set up ``cell`` on ``chips``, measure ``seconds``, check, and
    return the result object (without printing it)."""
    arch = cell.config
    use_compile_cache()
    compiles = CompileCounter()
    pauses = CollectorPauses()
    with serving(cell, seed, chips, traced, log) as sv:
        t0 = time.perf_counter() + 0.01
        setup_s = t0 - t_start
        t_trace = t0 + max(0.0, seconds - TRACE_S)
        host_snap: List[Dict[str, Any]] = []
        if traced:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            tracer = trace.record_until(
                TRACE_DIR, t_trace, t0 + seconds,
                before=lambda: host_snap.append(sv.server.snapshot()))
        compiles.on = pauses.on = True
        sent = window(sv, cell.generator, cell.traffic, seed, seconds, t0)
        compiles.on = pauses.on = False
        pauses.close()
        if traced:
            tracer.join()
            t = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"profiler stopped in {time.perf_counter() - t:.3f} s")
        used = set(sv.stage_chips)
        peak = max((chips[c].memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for c in used)
        out = sv.model.output
        answered = [(k, r) for k, _, _, r in sent
                    if r.event.is_set() and r.error is None
                    and out in r.result]
        got = jax.device_get([r.result[out] for _, r in answered])
        params, pool = sv.params, sv.pool
        spans, stage_chips = sv.spans, sv.stage_chips
    del sv
    gc.collect()

    attempted = len(sent)
    failed = attempted - len(answered)
    late = [t - due for _, due, t, _ in sent]
    log(f"device_kind: {chips[0].device_kind}; devices: "
        f"{len(jax.devices())}; chips used: {len(used)}")
    if sent:
        log(f"generator lateness (sent - due): median "
            f"{np.median(late) * 1e3:.4f} ms, max {max(late) * 1e3:.4f} ms "
            f"over {attempted} requests")
    log(f"compile events inside the window: {compiles.count}")
    log(f"collector passes inside the window: {pauses.describe()}")
    log(f"setup_s {setup_s:.4f}; attempted {attempted}, failed {failed}")

    # the reference, once the program's state is freed
    rows = [k % len(pool) for k, _ in answered]
    with jax.default_device(chips[0]):
        ref = compare.reference_logits(arch, params, pool, sorted(set(rows)))
    checks = compare.check(arch, rows,
                           np.concatenate(got) if got else np.zeros((0, 1)),
                           ref, attempted, failed)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    metrics: Dict[str, Dict[str, Any]] = {}
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed, "metrics": metrics,
                              "device": device}
    if not traced:
        e2e = end_to_end(sent, setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        reduced = trace.reduce(trace.load(TRACE_DIR),
                               [chips[c].id for c in sorted(used)])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        host = [x for x in sent if x[1] < t_trace - HOST_MARGIN_S]
        run = Run(cell=cell, chips=chips, stage_chips=stage_chips, t0=t0,
                  seconds=seconds, sent=host, spans=spans,
                  snapshot=host_snap[0], trace=reduced)
        for m in cell.per_layer:
            v = cell.readers[m["name"]].read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    return result


def end_to_end(sent, setup_s: float) -> Dict[str, float]:
    """Every end-to-end metric this harness knows, from the requests sent
    in the window: latency from each request's due time (an unanswered or
    failed request counts as infinitely late)."""
    return {"latency_p50_ms": stats.nearest_rank(stats.latencies(sent),
                                                 0.50) * 1e3,
            "setup_s": setup_s}
