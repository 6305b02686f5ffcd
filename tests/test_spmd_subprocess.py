"""Distribution tests that need multiple devices: run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count set locally (the main test
process must keep the real 1-device topology)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n_devices: int = 4, timeout: int = 560,
                     args=()):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                        + env.get("XLA_FLAGS", ""))
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code), *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nERR:\n{proc.stderr}"
    return proc.stdout


def test_spmd_pipeline_matches_direct():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.configs.common import concrete_batch
        from repro.models import api, lm_graph
        from repro.api import DeploymentSpec
        from repro.api import plan as api_plan
        from repro.launch.pipeline_spmd import pipeline_logits
        from repro.launch.mesh import make_mesh

        cfg = configs.get("qwen3-1.7b").smoke_config()
        mesh = make_mesh((1, 4), ("data", "model"))
        params = api.init(cfg, jax.random.PRNGKey(0))
        batch = concrete_batch(cfg, 16, 8, kind="prefill")
        g = lm_graph.lm_layer_graph(cfg, seq_len=16)
        pl = api_plan(DeploymentSpec(stages=4,
                                     strategy="balanced_norefine"), graph=g)
        ref = api.forward(cfg, params, batch)
        with mesh:
            out = pipeline_logits(cfg, mesh, pl, params, batch,
                                  n_microbatches=4)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 2e-2, err
        print("OK", err)
    """)
    assert "OK" in out


def test_spmd_pipeline_unequal_stage_counts():
    """Force an unbalanced plan (counts differ per stage) — identity
    masking must keep the result exact."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, dataclasses
        from repro import configs
        from repro.configs.common import concrete_batch
        from repro.models import api, lm_graph
        from repro.api import DeploymentSpec
        from repro.api import plan as api_plan
        from repro.launch.pipeline_spmd import (pipeline_logits,
                                                stage_block_counts)
        from repro.launch.mesh import make_mesh

        cfg = dataclasses.replace(configs.get("qwen3-1.7b").smoke_config(),
                                  n_layers=6)
        mesh = make_mesh((1, 4), ("data", "model"))
        params = api.init(cfg, jax.random.PRNGKey(0))
        batch = concrete_batch(cfg, 16, 8, kind="prefill")
        g = lm_graph.lm_layer_graph(cfg, seq_len=16)
        pl = api_plan(DeploymentSpec(stages=4, strategy="comp"),
                      graph=g)            # comp: unequal block counts
        counts = stage_block_counts(pl, cfg.n_layers)
        assert len(set(counts)) > 1, counts
        ref = api.forward(cfg, params, batch)
        with mesh:
            out = pipeline_logits(cfg, mesh, pl, params, batch,
                                  n_microbatches=4)
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 2e-2, (err, counts)
        print("OK", counts)
    """)
    assert "OK" in out


@pytest.mark.parametrize("executor", ["spmd", "host"])
def test_spmd_cnn_executor_matches_direct(executor):
    """One 4-stage CNN plan on four devices must reproduce model.apply.
    spmd: lowered via apply_subset ranges onto a 4-stage mesh (fused
    per-stage branches + ppermute hops).  host: build_stage_fns stages
    through the host executor, stage s on device s; each stage's output
    must sit on its own device."""
    out = run_with_devices("""
        import sys
        import jax, jax.numpy as jnp
        from repro.models.cnn import synthetic_cnn
        from repro.models.layers import GraphModel, build_stage_fns
        from repro.api import DeploymentSpec, deploy
        from repro.launch.pipeline_spmd import SpmdPipelineExecutor

        model = synthetic_cnn(8, L=6, hw=32)
        params = model.init(jax.random.PRNGKey(0))
        devs = jax.devices()[:4]
        dep = deploy(DeploymentSpec(stages=4, strategy="balanced_norefine"),
                     graph=model.to_layer_graph(),
                     stage_fn_builder=lambda p: build_stage_fns(
                         model, params, p, devices=devs))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        ref = model.apply(params, x)
        if sys.argv[1] == "spmd":
            with SpmdPipelineExecutor.for_model(model, params, dep.plan,
                                                n_microbatches=4,
                                                batch_size=8) as ex:
                got = ex(x)
        else:
            h = {GraphModel.INPUT: x}
            for s, fn in enumerate(dep.stage_functions()):
                h = fn(h)
                assert {d for a in h.values() for d in a.devices()} \
                    == {devs[s]}, s
            with dep.executor() as ex:
                outs, _ = ex.run_batch([{GraphModel.INPUT: x[i:i + 1]}
                                        for i in range(len(x))])
            got = jnp.concatenate([o[model.output] for o in outs])
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-4, err
        print("OK", err)
    """, args=(executor,))
    assert "OK" in out


def test_spmd_cnn_2stage_indivisible_batch():
    """2-stage mesh with a batch the microbatch count does not divide:
    the pad-and-slice path must stay exact."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.models.cnn import synthetic_cnn
        from repro.api import DeploymentSpec
        from repro.api import plan as api_plan
        from repro.launch.pipeline_spmd import SpmdPipelineExecutor

        model = synthetic_cnn(4, L=5, hw=16)
        params = model.init(jax.random.PRNGKey(0))
        pl = api_plan(DeploymentSpec(stages=2,
                                     strategy="balanced_norefine"),
                      graph=model.to_layer_graph())
        x = jax.random.normal(jax.random.PRNGKey(1), (7, 16, 16, 3))
        ref = model.apply(params, x)
        with SpmdPipelineExecutor.for_model(model, params, pl,
                                            n_microbatches=4) as ex:
            outs, stats = ex.run_batch(list(x))
        got = jnp.stack(outs)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-4, err
        assert stats["items_per_s"] > 0
        print("OK", err)
    """, n_devices=2)
    assert "OK" in out


def test_spmd_cnn_skip_dag_uneven_plan():
    """Skip connection crossing every cut of an uneven (comp) plan: the
    boundary value must ride through intermediate stages untouched."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.models.layers import Builder
        from repro.api import DeploymentSpec
        from repro.api import plan as api_plan
        from repro.launch.pipeline_spmd import (SpmdPipelineExecutor,
                                                cnn_boundary_specs)

        b = Builder("skipnet", (16, 16), 3)
        s = b.act(b.conv(b.model.INPUT, 8, 3, name="c1"), name="c1_relu")
        x = s
        for i in range(6):
            x = b.conv(x, 8, 3, name=f"mid{i}")
        x = b.add([x, s], name="skip_add")
        x = b.dense(b.gap(x, name="pool"), 10, name="head")
        model = b.build()

        params = model.init(jax.random.PRNGKey(0))
        pl = api_plan(DeploymentSpec(stages=4, strategy="comp"),
                      graph=model.to_layer_graph())
        bounds, _ = cnn_boundary_specs(model, pl)
        assert any("c1_relu" in dict(bs) for bs in bounds[2:]), bounds
        xin = jax.random.normal(jax.random.PRNGKey(1), (7, 16, 16, 3))
        ref = model.apply(params, xin)
        with SpmdPipelineExecutor.for_model(model, params, pl,
                                            n_microbatches=3,
                                            overlap_streaming=False) as ex:
            got = ex(xin)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-4, err
        print("OK", err)
    """)
    assert "OK" in out


def test_spmd_lm_executor_pad_and_probes():
    """LM executor front-to-back: token batch the microbatch count does
    not divide, plus the predicted/achieved probe surface."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.configs.common import concrete_batch
        from repro.models import api, lm_graph
        from repro.api import DeploymentSpec
        from repro.api import plan as api_plan
        from repro.launch.pipeline_spmd import SpmdPipelineExecutor

        cfg = configs.get("qwen3-1.7b").smoke_config()
        params = api.init(cfg, jax.random.PRNGKey(0))
        batch = concrete_batch(cfg, 16, 7, kind="prefill")
        g = lm_graph.lm_layer_graph(cfg, seq_len=16)
        pl = api_plan(DeploymentSpec(stages=4,
                                     strategy="balanced_norefine"), graph=g)
        ref = api.forward(cfg, params, batch)
        with SpmdPipelineExecutor.for_model(cfg, params, pl,
                                            n_microbatches=4,
                                            batch_size=7,
                                            seq_len=16) as ex:
            got = ex(batch["tokens"])
            pred = ex.predicted_stage_times()
            ach = ex.achieved_stage_times(reps=2, warmup=1)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 2e-2, err
        assert len(pred) == len(ach) == 4
        assert all(t > 0 for t in ach)
        assert ex.fill_s > 0
        print("OK", err)
    """)
    assert "OK" in out


def test_stream_stage_weights_overlap_matches_serial():
    """Overlapped and serial streaming must assemble identical global
    arrays (the overlap only reorders transfers against compilation)."""
    out = run_with_devices("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.launch.pipeline_spmd import stream_stage_weights

        mesh = make_mesh((1, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        stacked = {"w": rng.standard_normal((4, 64)).astype(np.float32),
                   "b": rng.standard_normal((4, 8)).astype(np.float32)}
        g1, _, r1 = stream_stage_weights(mesh, stacked, "model",
                                         overlap=True)
        g2, _, r2 = stream_stage_weights(mesh, stacked, "model",
                                         overlap=False)
        for k in stacked:
            np.testing.assert_array_equal(np.asarray(g1[k]),
                                          np.asarray(g2[k]))
            assert g1[k].sharding.spec == g2[k].sharding.spec
        assert r1.fill_s > 0 and r2.fill_s > 0
        assert 0 <= r1.blocked_s <= r1.fill_s
        assert 0 <= r2.blocked_s <= r2.fill_s
        print("OK", r1, r2)
    """)
    assert "OK" in out


def test_sharded_train_step_matches_single_device():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.configs.common import concrete_batch
        from repro.launch import sharding as shd, steps as steps_lib
        from repro.launch.mesh import make_mesh
        from repro.optim import AdamWConfig

        cfg = configs.get("qwen3-1.7b").smoke_config()
        params, opt = steps_lib.init_train_state(cfg, jax.random.PRNGKey(0))
        batch = concrete_batch(cfg, 16, 4, kind="train")
        step = steps_lib.make_train_step(cfg, AdamWConfig(lr=1e-3),
                                         loss_chunk=16)
        # single-device reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch)
        # sharded
        mesh = make_mesh((2, 2), ("data", "model"))
        with mesh:
            in_sh = (shd.param_shardings(mesh, params, fsdp=True),
                     shd.opt_state_shardings(mesh, opt),
                     shd.batch_shardings(mesh, batch))
            p2, o2, m2 = jax.jit(step, in_shardings=in_sh)(params, opt,
                                                           batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        d = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
                for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        assert d < 2e-2, d
        print("OK", float(m1["loss"]), d)
    """)
    assert "OK" in out


def test_mini_dryrun_cell_includes_roofline():
    """End-to-end dryrun_cell on the production mesh for the smallest arch
    (the full sweep runs via python -m repro.launch.dryrun --all)."""
    out = run_with_devices("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import dryrun_cell
        rec = dryrun_cell("whisper-tiny", "decode_32k", multi_pod=False,
                          verbose=False)
        assert rec["status"] == "ok", rec
        assert rec["fits_hbm"]
        assert set(rec["roofline"]) == {"compute_s", "memory_s",
                                        "collective_s", "dominant"}
        assert rec["hlo_flops_per_device"] > 0
        print("OK")
    """, n_devices=512)
    assert "OK" in out


# ---------------------------------------------------------------------------
# backend routing (in-process: the fallback decision never builds a mesh)
# ---------------------------------------------------------------------------
def _replicated_plan():
    import dataclasses

    from repro.api import DeploymentSpec
    from repro.api import plan as api_plan
    from repro.models.cnn import synthetic_cnn

    pl = api_plan(DeploymentSpec(stages=2, strategy="balanced_norefine"),
                  graph=synthetic_cnn(4, L=4, hw=16).to_layer_graph())
    stages = [dataclasses.replace(pl.stages[0], replicas=2), pl.stages[1]]
    return dataclasses.replace(pl, stages=stages)


def test_spmd_backend_replicated_plan_falls_back_to_host(caplog):
    """Front door: a replicated plan cannot map one-stage-one-mesh-slice;
    executor(backend='spmd') must fall back to the host executor with a
    logged notice, not die."""
    import logging

    from repro.api.deploy import Deployment
    from repro.core.pipeline import PipelineExecutor

    pl = _replicated_plan()
    dep = Deployment.from_plan(pl, stage_fns=[lambda x: x, lambda x: x])
    with caplog.at_level(logging.WARNING, logger="repro.api.deploy"):
        ex = dep.executor(backend="spmd")
    try:
        assert isinstance(ex, PipelineExecutor)
        assert any("falling back" in r.message for r in caplog.records)
    finally:
        ex.stop()


def test_spmd_backend_requires_model_and_params():
    from repro.api import DeploymentSpec
    from repro.api import plan as api_plan
    from repro.api.deploy import Deployment
    from repro.models.cnn import synthetic_cnn

    model = synthetic_cnn(4, L=4, hw=16)
    pl = api_plan(DeploymentSpec(stages=2, strategy="balanced_norefine"),
                  graph=model.to_layer_graph())
    dep = Deployment.from_plan(pl)
    with pytest.raises(ValueError, match="model"):
        dep.executor(backend="spmd")
    with pytest.raises(ValueError, match="'host' or 'spmd'"):
        dep.executor(backend="tpu")


def test_require_unreplicated_direct_raises():
    """The low-level SPMD entry points keep the hard error (only the
    Deployment front door downgrades it to a fallback)."""
    from repro.launch.pipeline_spmd import (_require_unreplicated,
                                            plan_supports_spmd)

    pl = _replicated_plan()
    assert not plan_supports_spmd(pl)
    with pytest.raises(NotImplementedError, match="replicated"):
        _require_unreplicated(pl)


def test_spec_backend_field_round_trips():
    from repro.api import DeploymentSpec

    spec = DeploymentSpec(stages=2, backend="spmd")
    assert DeploymentSpec.from_json(spec.to_json()) == spec
    with pytest.raises(ValueError, match="backend"):
        DeploymentSpec(stages=2, backend="mesh")


def test_collectives_appear_in_sharded_hlo():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_analysis import analyze

        mesh = make_mesh((4,), ("model",))
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        with mesh:
            f = jax.jit(lambda a, b: a @ b,
                        in_shardings=(NamedSharding(mesh, P(None, "model")),
                                      NamedSharding(mesh, P("model", None))),
                        out_shardings=NamedSharding(mesh, P()))
            compiled = f.lower(x, w).compile()
        tot = analyze(compiled.as_text())
        assert tot.coll_bytes > 0
        assert sum(tot.coll_counts.values()) >= 1
        print("OK", tot.coll_counts)
    """)
    assert "OK" in out
