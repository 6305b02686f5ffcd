"""Serving driver: batched requests through the balanced-segmented pipeline.

Demonstrates the paper's full deployment story at LM scale, on CPU:

1. build the arch's LayerGraph and run SEGM_BALANCED (vs SEGM_COMP) for
   ``--stages`` devices;
2. split the stacked block params by the plan; one host thread per stage
   with queues between (paper Fig. 5 executor) — or the SPMD
   shard_map/ppermute pipeline with ``--backend spmd`` (needs >=stages
   devices, e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
3. serve a *stream* of requests: each request is admitted into the
   pipeline as it arrives (no inter-batch barrier) and completes its own
   future; report throughput, per-request latency percentiles, and
   per-stage busy times (paper Fig. 10 metric) from the server's
   monotonic-counter snapshot() deltas.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --stages 4 --requests 15
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.api import DeploymentSpec, deploy
from repro.configs.common import concrete_batch
from repro.core.pipeline import (PipelineExecutor, ShapeKeyedStageCache,
                                 stage_balance_metrics)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api, lm, lm_graph


def make_stage_fns(cfg, params, counts, stage_cache=None):
    """Per-stage callables applying a contiguous block range (+ embed on
    stage 0, unembed on the last stage).

    Stage bodies are built lazily through a :class:`ShapeKeyedStageCache`:
    the jitted closure for a stage is constructed once per input
    shape/dtype and reused for every subsequent batch (pass a shared
    ``stage_cache`` to also reuse across executor/server restarts)."""
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(int)
    cache = stage_cache if stage_cache is not None else ShapeKeyedStageCache()

    def block_range_fn(lo, hi, first, last):
        def build():
            blocks = jax.tree.map(lambda a: a[lo:hi], params["blocks"])

            @jax.jit
            def run(x_or_tokens):
                if first:
                    x = lm.embed_tokens(cfg, params, x_or_tokens)
                else:
                    x = x_or_tokens
                s = x.shape[1]
                positions = jnp.arange(s)[None, :]
                fn = lm._block_fn(cfg)

                def body(x, bp):
                    return fn(x, bp, positions), None

                if hi > lo:
                    x, _ = jax.lax.scan(body, x, blocks)
                if last:
                    return lm.unembed(cfg, params, x[:, -1:])
                return x

            return run

        # first/last must be part of the key: two empty block ranges (e.g.
        # a final_norm-only stage vs the head stage) share lo == hi
        return cache.wrap(f"blocks[{lo}:{hi}]:f{int(first)}l{int(last)}",
                          build)

    fns = []
    for i, c in enumerate(counts):
        fns.append(block_range_fn(offsets[i], offsets[i + 1],
                                  i == 0, i == len(counts) - 1))
    return fns


def spec_from_args(args) -> DeploymentSpec:
    """CLI flags -> declarative DeploymentSpec (the repro.api front door).

    ``--device-budget`` switches to the joint cuts+replicas placement
    strategy over that many devices; otherwise ``--stages`` identical
    devices, one per stage, with the requested split strategy."""
    common = dict(
        model=f"lm:{args.arch}:seq={args.seq}",
        backend=getattr(args, "backend", "host"),
        microbatch=args.microbatch,
        microbatch_wait_s=args.microbatch_wait_ms / 1e3,
        max_batch=args.requests, max_wait_s=0.005,
        cost_source=args.cost_source,
        hedge_after=(getattr(args, "hedge_after_ms", 0.0) / 1e3
                     or None),
        stage_loss_retries=getattr(args, "stage_loss_retries", 0),
        deadline_ms=(getattr(args, "deadline_ms", 0.0) or None),
        shed_policy=getattr(args, "shed_policy", "none"),
        drift_threshold=getattr(args, "drift_threshold", 0.0),
        canary_requests=getattr(args, "canary_requests", 4))
    if getattr(args, "workload", "batch") == "decode":
        # decode plans at the (concurrency, max_context) operating point
        # with the per-token cost regime; see repro.decode
        return DeploymentSpec(
            strategy="decode_placement", stages=args.stages,
            workload="decode",
            max_context=getattr(args, "max_context", None) or None,
            decode_concurrency=(getattr(args, "decode_concurrency", None)
                                or None),
            **common)
    if args.device_budget:
        # joint cuts+replicas search: a bottleneck stage may get k devices
        # (round-robin fan-out in the executor, order-restoring fan-in)
        return DeploymentSpec(strategy="placement",
                              device_budget=args.device_budget, **common)
    return DeploymentSpec(strategy=args.strategy, stages=args.stages,
                          **common)


def run_decode(args) -> None:
    """``--workload decode``: KV-aware placement + continuous batching.

    Plans with the ``decode_placement`` strategy (per-token costs, KV cap
    at the operating point — works for *every* family, recurrent ones as
    O(1)-state blocks), then serves token streams through the
    :class:`~repro.decode.engine.DecodeServer` for the scan-block
    families."""
    from repro.decode import DECODE_FAMILIES

    cfg = configs.get(args.arch).smoke_config()
    g = lm_graph.lm_layer_graph(cfg, seq_len=args.seq)
    spec = spec_from_args(args)
    dep = deploy(spec, graph=g)
    pl = dep.plan
    print("plan:", pl.describe())
    print("report:", pl.report.describe())
    if cfg.family not in DECODE_FAMILIES:
        print(f"note: family {cfg.family!r} ({args.arch}) plans decode "
              f"placement (above) but the continuous-batching runtime "
              f"binds the scan-block families {DECODE_FAMILIES}; pick one "
              f"of those archs to stream tokens")
        return

    params = api.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=8).astype(np.int32)
               for _ in range(args.requests)]
    with dep.serve(start=True, params=params) as srv:
        srv.submit(prompts[0], max_new_tokens=2).result(600)   # jit warmup
        srv.snapshot()                          # reset the delta window
        t0 = time.perf_counter()
        reqs = [srv.submit(p, max_new_tokens=args.max_new_tokens)
                for p in prompts]
        outs = [r.result(600) for r in reqs]
        dt = time.perf_counter() - t0
        snap = srv.snapshot()
    assert all(len(o) == args.max_new_tokens for o in outs), \
        [len(o) for o in outs]
    print(f"{len(outs)} streams x {args.max_new_tokens} tokens in "
          f"{dt*1e3:.1f} ms ({snap['tokens']/dt:.1f} tok/s, "
          f"{snap['steps']} batched steps)")
    print(f"inter-token p50/p95 (ms): "
          f"{snap['inter_token_p50_s']*1e3:.2f} / "
          f"{snap['inter_token_p95_s']*1e3:.2f}")
    print(f"modeled decode: {pl.report.decode_tokens_per_s:.1f} tok/s, "
          f"KV headroom {pl.report.kv_headroom_pct:.0f}%")


def run_fleet(args) -> None:
    """``--fleet fleet.json``: bring up a multi-tenant fleet from a spec
    document and drive the synthetic traffic scenario against it —
    weighted-fair routing, per-member SLOs, and a mid-run traffic shift
    the autoscaler chases (see EXPERIMENTS.md §Multi-tenant fleet)."""
    from repro.fleet import FleetSpec
    from repro.fleet.scenario import (FleetScenario, TrafficPhase,
                                      summarize_member)

    with open(args.fleet) as f:
        fspec = FleetSpec.from_json(f.read())
    names = list(fspec.member_names)
    print(f"fleet: {len(names)} members over "
          f"{fspec.pool().n_devices} devices: {names}")

    svc = args.fleet_service_ms / 1e3
    sc = FleetScenario(fspec, {n: svc for n in names})
    fleet = sc.deploy()
    counts0 = fleet.device_counts()
    print(f"pool split: {counts0} (mode={fleet.placement.mode}, "
          f"worst modeled norm "
          f"{fleet.placement.worst_norm:.2f})")

    # phase 1: share-proportional traffic; phase 2: the first member's
    # load triples (the shift the autoscaler must chase)
    base = {m.name: max(1, round(2 * m.share)) for m in fspec.members}
    shifted = dict(base)
    shifted[names[0]] = 3 * base[names[0]]
    with fleet:
        metrics = sc.drive(fleet, [
            TrafficPhase(windows=args.fleet_windows, rates=base),
            TrafficPhase(windows=args.fleet_windows, rates=shifted),
        ])
        counts1 = fleet.device_counts()
        events = ([] if fleet.autoscaler is None
                  else list(fleet.autoscaler.events))
    att = sc.attainment(metrics)
    for n in names:
        print(f"  {n}: {summarize_member(metrics[n])} "
              f"attainment={att[n]:.2f}")
    audit = sc.audit()
    moves = [e for e in events if e["event"] in ("commit", "rollback")]
    print(f"audit: {audit}")
    print(f"device split {counts0} -> {counts1}; "
          f"{sum(1 for e in moves if e['event'] == 'commit')} committed "
          f"moves, {sum(1 for e in moves if e['event'] == 'rollback')} "
          f"rollbacks")
    assert all(a["lost"] == 0 and a["misordered"] == 0
               for a in audit.values()), audit


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--requests", type=int, default=15)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--strategy", default="balanced",
                    choices=["balanced", "balanced_norefine", "comp"])
    ap.add_argument("--backend", default="host",
                    choices=["host", "spmd"],
                    help="execution tier: 'host' (threaded stage workers, "
                         "streaming admission) or 'spmd' (the plan lowered "
                         "onto a device mesh: shard_map + ppermute with "
                         "overlapped weight streaming; needs >= --stages "
                         "devices — set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--microbatch", type=int, default=1,
                    help="stage-level dynamic micro-batching bucket size "
                         "(stack up to k same-shape in-flight requests "
                         "into one jitted call; 1 = off)")
    ap.add_argument("--microbatch-wait-ms", type=float, default=2.0,
                    help="max hold time for a micro-batch bucket to fill")
    ap.add_argument("--device-budget", type=int, default=0,
                    help="plan over this many devices with replicated "
                         "bottleneck stages (the 'placement' strategy; "
                         "0 = off, use --stages identical devices, one "
                         "per stage)")
    ap.add_argument("--hedge-after-ms", type=float, default=0.0,
                    help="speculatively re-dispatch an item stuck on a "
                         "replicated stage for this long to another "
                         "replica (first result wins; 0 = off)")
    ap.add_argument("--stage-loss-retries", type=int, default=0,
                    help="re-admit a request that crossed a dead stage "
                         "this many times (survives degraded-mode "
                         "replans; 0 = fail fast)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request latency budget: a request past it "
                         "completes with DeadlineExceeded at admission or "
                         "merge exit instead of waiting unbounded (0 = "
                         "off)")
    ap.add_argument("--shed-policy", default="none",
                    choices=["none", "deadline"],
                    help="'deadline': shed requests at admission when the "
                         "queue-delay estimate (in_flight x service pace) "
                         "would outlive their deadline budget; callers "
                         "get Overloaded + a jittered retry_after_s hint")
    ap.add_argument("--drift-threshold", type=float, default=0.0,
                    help="relative modeled-vs-observed per-stage drift "
                         "past which the self-healing controller replans "
                         "from live telemetry (0 = loop off; see "
                         "EXPERIMENTS.md §Self-healing serving)")
    ap.add_argument("--canary-requests", type=int, default=4,
                    help="held-aside requests validating a candidate "
                         "executor before a guarded reconfigure commits")
    ap.add_argument("--workload", default="batch",
                    choices=["batch", "decode"],
                    help="'batch': prefill request/response serving "
                         "(default).  'decode': KV-cache-aware placement "
                         "(decode_placement strategy) + continuous-"
                         "batching token streaming; see EXPERIMENTS.md "
                         "§Decode serving")
    ap.add_argument("--max-context", type=int, default=128,
                    help="decode operating point: per-sequence KV budget "
                         "(prompt + generated tokens)")
    ap.add_argument("--decode-concurrency", type=int, default=4,
                    help="decode operating point: concurrent sequences in "
                         "the running batch")
    ap.add_argument("--max-new-tokens", type=int, default=16,
                    help="tokens generated per decode request")
    ap.add_argument("--cost-source", default="analytic",
                    help="where the planner's per-depth costs come from: "
                         "'analytic' (closed-form device model), "
                         "'trace:<path>' (a repro.profiling ProfileTrace "
                         "artifact), or 'calibrated:<path>' (analytic "
                         "model least-squares-fit to that trace); see "
                         "EXPERIMENTS.md §Profiling & calibration")
    ap.add_argument("--fleet", default="",
                    help="path to a FleetSpec JSON document: serve N "
                         "models on one shared device pool (SLO-driven "
                         "pool split, weighted-fair admission, "
                         "autoscaling) and drive the synthetic traffic "
                         "scenario against it; ignores the single-model "
                         "flags above")
    ap.add_argument("--fleet-windows", type=int, default=10,
                    help="traffic windows per fleet scenario phase")
    ap.add_argument("--fleet-service-ms", type=float, default=6.0,
                    help="synthetic whole-model service time per fleet "
                         "member (sleep-based stage fns)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.fleet:
        run_fleet(args)
        return
    if args.workload == "decode":
        run_decode(args)
        return

    mod = configs.get(args.arch)
    cfg = mod.smoke_config()
    if cfg.family not in ("dense", "moe", "vlm"):
        # every family plans via lm_graph; only the batch-serving runtime
        # binds scan-block stage functions.  Plan, report, and say so.
        g = lm_graph.lm_layer_graph(cfg, seq_len=args.seq)
        pl = deploy(spec_from_args(args), graph=g).plan
        print("plan:", pl.describe())
        print("report:", pl.report.describe())
        print(f"note: family {cfg.family!r} ({args.arch}) plans via "
              f"lm_graph (above) but the pipeline serving runtime binds "
              f"the scan-block families ('dense', 'moe', 'vlm'); pick one "
              f"of those archs to serve, or use --workload decode for "
              f"KV-aware decode planning")
        return
    params = api.init(cfg, jax.random.PRNGKey(0))

    g = lm_graph.lm_layer_graph(cfg, seq_len=args.seq)
    spec = spec_from_args(args)

    from repro.launch.pipeline_spmd import stage_block_counts

    def fns_for(p):
        counts = stage_block_counts(p, cfg.n_layers)
        return make_stage_fns(cfg, params, counts)

    dep = deploy(spec, graph=g, stage_fn_builder=fns_for)
    pl = dep.plan
    print("plan:", pl.describe())
    print("report:", pl.report.describe())
    print("blocks per stage:", stage_block_counts(pl, cfg.n_layers))

    reqs = [concrete_batch(cfg, args.seq, 1,
                           key=jax.random.PRNGKey(i),
                           kind="prefill")["tokens"]
            for i in range(args.requests)]

    if args.backend == "spmd":
        # batch path: the whole request set rides one mesh dispatch (the
        # SPMD tier has no streaming admission loop — that is the host
        # executor's job; see EXPERIMENTS.md §SPMD execution)
        ex = dep.executor(backend="spmd", model=cfg, params=params,
                          n_microbatches=max(1, args.microbatch),
                          batch_size=args.requests, seq_len=args.seq)
        if isinstance(ex, PipelineExecutor):     # replicated-plan fallback
            raise SystemExit("plan has replicated stages; rerun without "
                             "--device-budget or use --backend host")
        rows = [r[0] for r in reqs]              # (seq,) token rows
        with ex:
            ex.run_batch(rows[:1])               # warmup (compile)
            t0 = time.perf_counter()
            outs, stats = ex.run_batch(rows)
            dt = time.perf_counter() - t0
            print(f"{len(outs)} requests in {dt*1e3:.1f} ms "
                  f"({stats['items_per_s']:.1f} req/s, "
                  f"m={stats['n_microbatches']}, "
                  f"weight-stream fill {stats['fill_s']*1e3:.0f} ms)")
            print("predicted stage times (s):",
                  [round(t, 4) for t in ex.predicted_stage_times()])
            print("achieved stage times (s): ",
                  [round(t, 4) for t in ex.achieved_stage_times()])
        ref = api.forward(cfg, params, {"tokens": reqs[0]},
                          last_token_only=True)
        err = float(jnp.max(jnp.abs(outs[0][-1:] - ref[0])))
        print(f"pipeline vs direct max err: {err:.2e}")
        assert err < 2e-2
        return

    # persistent streaming executor: stage workers live for the whole
    # serving session; requests are admitted continuously (no barrier).
    # The Deployment handle owns the server wiring (spec's serving policy).
    with dep.serve() as server:
        server.serve_batch(reqs[:1])           # warmup (jit)
        server.start()                          # admission loop
        healer = None
        if args.drift_threshold > 0:
            # closed-loop calibration: live telemetry -> rolling trace ->
            # guarded (canary + rollback) replans; see runtime.selfheal
            healer = dep.self_heal(reqs[:args.canary_requests]).start()
        server.snapshot()                       # reset the delta window
        t0 = time.perf_counter()
        pending = [server.submit(r) for r in reqs]
        for req in pending:
            assert req.event.wait(300), f"request {req.rid} timed out"
        dt = time.perf_counter() - t0
        snap = server.snapshot()
        assert all(r.error is None for r in pending)
        outs = [r.result for r in pending]
        busy = snap["stage_busy_s"]
        metrics = stage_balance_metrics(busy)
        lat = snap["latency"]
        print(f"{len(outs)} requests in {dt*1e3:.1f} ms "
              f"({snap['throughput_rps']:.1f} req/s)")
        print(f"latency p50/p95/p99 (ms): {lat['p50_s']*1e3:.1f} / "
              f"{lat['p95_s']*1e3:.1f} / {lat['p99_s']*1e3:.1f}")
        print(f"stage busy (s): {[round(b,4) for b in busy]}")
        print(f"balance (mean/max): {metrics['balance']:.3f}")
        if healer is not None:
            healer.stop()
            print(f"self-heal: {healer.windows} windows, "
                  f"{healer.commits} commits, "
                  f"{healer.rollbacks} rollbacks "
                  f"(state={healer.state})")

        # reference check
        ref = api.forward(cfg, params, {"tokens": reqs[0]},
                          last_token_only=True)
        err = float(jnp.max(jnp.abs(outs[0] - ref)))
        print(f"pipeline vs direct max err: {err:.2e}")
        assert err < 2e-2


if __name__ == "__main__":
    main()
