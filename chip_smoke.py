"""Chip smoke test: the paper's CNN serving path, end to end, on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the path that exists only across chips

One chip: plan ResNet50 (Table-1 widths, 25.6 M parameters, random weights
from a fixed seed) into 4 balanced stages through ``repro.api.deploy``,
build the stages with ``build_stage_fns`` (all on the one chip), and serve
8 seeded 224x224x3 images through ``Deployment.serve()``.  The served
logits must match ``jax.jit(model.apply)`` on the same chip, and that
direct forward must match the same forward on the host CPU at ``highest``
matmul precision.

Four chips: only the cross-chip path.  (a) The host executor with stage
``s`` on ``jax.devices()[s]``; each stage's output must live on its own
device.  (b) ``SpmdPipelineExecutor`` on a 4-stage mesh.  Both must match
the direct forward on chip 0.

Exits non-zero, printing no result, unless JAX's first device is a TPU;
there is no CPU fallback.  Any failed check raises.  The last line of
standard output is ``{"ok": true, "device": {...}}``.  Rates printed here
are smoke figures, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SEED = 0
N_IMAGES = 8
N_STAGES = 4
WAIT_S = 300.0
# served vs direct forward: same chip, same matmul precision and the same
# batch shape per call, so only fusion across the cuts differs
SERVED_BOUND = 1e-2
# chip forward (default precision: one bf16 pass per f32 matmul/conv) vs
# the host CPU in f32 at "highest": bf16 rounding over ~50 conv layers;
# a wiring fault (wrong weights, a dropped layer) is O(1)
CPU_BOUND = 5e-2


def compare(what: str, got, ref, bound: float) -> None:
    """Raise unless ``got`` is finite, top-1 agrees with ``ref`` on every
    row, and ``max|got - ref| / max|ref|`` is within ``bound``."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {ref.shape}")
    got, ref = got.reshape(len(got), -1), ref.reshape(len(ref), -1)
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite values")
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(got - ref))) / scale
    agree = int(np.sum(got.argmax(-1) == ref.argmax(-1)))
    top2 = np.sort(ref, axis=-1)[:, -2:]
    margin = float(np.min(top2[:, 1] - top2[:, 0])) / scale
    print(f"{what}: max|d|/max|ref| = {err:.3e} (bound {bound:.0e}); "
          f"top-1 agrees on {agree}/{len(ref)} "
          f"(smallest top-2 margin / max|ref| = {margin:.3e})", flush=True)
    if err > bound or agree != len(ref):
        raise AssertionError(f"{what}: outside bound or top-1 differs")


def one_chip(model, params, spec, images) -> None:
    """Serve ``images`` one request each through ``deploy(spec).serve()``
    on the default device and check them against the direct forward."""
    import jax
    import numpy as np
    from repro.api import deploy
    from repro.models.layers import GraphModel, build_stage_fns

    dep = deploy(spec, graph=model.to_layer_graph(),
                 stage_fn_builder=lambda p: build_stage_fns(model, params, p))
    print(f"plan: {dep.plan.describe()}", flush=True)
    payloads = [{GraphModel.INPUT: images[i:i + 1]}
                for i in range(len(images))]
    with dep.serve() as server:
        t0 = time.perf_counter()
        server.serve_batch(payloads[:1])            # compiles every stage
        stage_compile_s = time.perf_counter() - t0
        server.start()
        t0 = time.perf_counter()
        reqs = [server.submit(p) for p in payloads]
        for r in reqs:
            if not r.event.wait(WAIT_S):
                raise TimeoutError(f"request {r.rid} not served in {WAIT_S} s")
        wall_s = time.perf_counter() - t0
        failed = [(r.rid, r.error) for r in reqs if r.error is not None]
        if failed:
            raise RuntimeError(f"requests failed: {failed}")
        served = np.concatenate([np.asarray(r.result[model.output])
                                 for r in reqs])

    t0 = time.perf_counter()
    direct = jax.jit(model.apply).lower(params, images[:1]).compile()
    direct_compile_s = time.perf_counter() - t0
    ref = np.concatenate([np.asarray(direct(params, images[i:i + 1]))
                          for i in range(len(images))])
    print(f"compile_s: stages (first request) {stage_compile_s:.2f}, "
          f"direct forward {direct_compile_s:.2f}", flush=True)
    print(f"smoke rate, not a benchmark: {len(reqs) / wall_s:.2f} images/s "
          f"({len(reqs)} requests in {wall_s:.3f} s)", flush=True)
    compare("served vs direct (same chip)", served, ref, SERVED_BOUND)

    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref_cpu = jax.jit(model.apply)(jax.device_put(params, cpu),
                                       jax.device_put(images, cpu))
        ref_cpu = np.asarray(ref_cpu)
    print(f"cpu reference: {time.perf_counter() - t0:.2f} s", flush=True)
    compare("direct chip vs cpu highest", ref, ref_cpu, CPU_BOUND)


def four_chips(model, params, spec, images, devices) -> None:
    """Stage ``s`` on ``devices[s]`` through the host executor, and the
    same plan on the SPMD mesh; both against the direct forward on
    ``devices[0]``."""
    import jax
    import numpy as np
    from repro.api import deploy
    from repro.launch.pipeline_spmd import (SpmdPipelineExecutor,
                                            default_stage_mesh)
    from repro.models.layers import GraphModel, build_stage_fns

    dep = deploy(spec, graph=model.to_layer_graph(),
                 stage_fn_builder=lambda p: build_stage_fns(
                     model, params, p, devices=devices))
    print(f"plan: {dep.plan.describe()}", flush=True)
    direct = jax.jit(model.apply)

    # (a) host executor: check where every stage's output lives
    fns = dep.stage_functions()
    h = {GraphModel.INPUT: images[:1]}
    t0 = time.perf_counter()
    for s, fn in enumerate(fns):
        h = fn(h)
        where = {d for a in h.values() for d in a.devices()}
        if where != {devices[s]}:
            raise AssertionError(f"stage {s} output on {where}, "
                                 f"expected {devices[s]}")
    print(f"stage outputs on {[str(d) for d in devices]}; compile + first "
          f"pass {time.perf_counter() - t0:.2f} s", flush=True)
    payloads = [{GraphModel.INPUT: images[i:i + 1]}
                for i in range(len(images))]
    with dep.executor() as ex:      # builds its own stages: warm them up
        ex.run_batch(payloads[:1])
        t0 = time.perf_counter()
        outs, _ = ex.run_batch(payloads)
        wall_s = time.perf_counter() - t0
    host = np.concatenate([np.asarray(o[model.output]) for o in outs])
    print(f"host executor smoke rate, not a benchmark: "
          f"{len(outs) / wall_s:.2f} images/s", flush=True)
    ref1 = np.concatenate([np.asarray(direct(params, images[i:i + 1]))
                           for i in range(len(images))])
    compare("host executor, stage s on device s, vs direct", host, ref1,
            SERVED_BOUND)

    # (b) the plan lowered onto a 4-stage mesh
    m = N_STAGES
    mb = len(images) // m
    t0 = time.perf_counter()
    spmd = SpmdPipelineExecutor.for_cnn(
        model, params, dep.plan, mesh=default_stage_mesh(N_STAGES),
        n_microbatches=m, batch_size=len(images))
    print(f"spmd bring-up (weights + compile) {time.perf_counter() - t0:.2f}"
          f" s", flush=True)
    with spmd:
        got = np.asarray(spmd(images))
    ref_mb = np.concatenate([np.asarray(direct(params,
                                               images[i * mb:(i + 1) * mb]))
                             for i in range(m)])
    compare("spmd pipeline vs direct", got, ref_mb, SERVED_BOUND)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: only the cross-chip "
                         "path (host executor over 4 devices + SPMD mesh)")
    args = ap.parse_args()
    # the one-chip check compares with the host CPU, so keep its backend
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is on platform {dev.platform!r} ({dev})")
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} devices")

    from repro.api import DeploymentSpec
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.cnn import REAL_CNNS

    cache = enable_compile_cache()
    print(f"device_kind: {dev.device_kind}; jax {jax.__version__}; "
          f"{len(devices)} devices; compile cache {cache}", flush=True)
    model = REAL_CNNS["ResNet50"]()
    params = model.init(jax.random.PRNGKey(SEED))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"ResNet50: {n_params:,} parameters", flush=True)
    images = jax.random.normal(jax.random.PRNGKey(SEED + 1),
                               (N_IMAGES,) + model.input_shape)
    spec = DeploymentSpec(model="cnn:ResNet50", stages=N_STAGES,
                          strategy="balanced", cost_source="analytic")
    if args.chips == 1:
        one_chip(model, params, spec, images)
    else:
        four_chips(model, params, spec, images, devices[:args.chips])
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']:,}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
