"""Quickstart: the paper's pipeline in five minutes, on CPU — through the
``repro.api`` front door.

1. Describe the deployment declaratively (a DeploymentSpec naming a real
   CNN), let the strategy registry plan it, and read the PlanReport.
2. Compare the paper's strategies by swapping one spec field.
3. Really run a pipelined forward (threads + queues, paper Fig. 5) via a
   Deployment handle and check it matches the direct forward.

    PYTHONPATH=src python examples/quickstart.py
    PYTHONPATH=src python examples/quickstart.py --smoke   # CI-sized
"""
import argparse

import jax
import jax.numpy as jnp

from repro.api import DeploymentSpec, deploy, plan
from repro.core import EdgeTPUModel
from repro.models.cnn import REAL_CNNS, synthetic_cnn
from repro.models.layers import GraphModel, build_stage_fns

MIB = 2 ** 20


def main(smoke: bool = False) -> None:
    # --- 1. one declarative spec; stages=None means the paper's §5.2.2
    # auto rule (fewest TPUs whose refined plan avoids host memory) -------
    graph = REAL_CNNS["ResNet50"]().to_layer_graph()
    model = EdgeTPUModel(graph)
    pl = plan(DeploymentSpec(model="cnn:ResNet50", strategy="balanced"),
              graph=graph, tpu_model=model)
    n = pl.n_stages
    print(f"ResNet50: {graph.summary()}")
    print(f"min TPUs to avoid host memory: {n} (paper Table 5: 4)")
    print(f"report: {pl.report.describe()}\n")

    # --- 2. strategy comparison = one spec field ------------------------
    for strat in ("comp", "balanced_norefine", "balanced"):
        p = plan(DeploymentSpec(stages=n, strategy=strat), graph=graph,
                 tpu_model=model)
        host = p.report.spill_bytes / MIB
        sp = model.speedup(p.cuts, batch=15)
        print(f"{strat:18s} host={host:5.2f} MiB  speedup vs 1 TPU: "
              f"{sp:4.2f}x   {p.describe()}")

    # --- 3. really run a pipelined model (small synthetic CNN) ----------
    print("\npipelined execution check (synthetic CNN, 3 stages):")
    m = synthetic_cnn(6 if smoke else 12, hw=16 if smoke else 32)
    g = m.to_layer_graph()
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1,) + m.input_shape)
    direct = m.apply(params, x)

    dep = deploy(
        DeploymentSpec(stages=3, strategy="balanced_norefine"), graph=g,
        stage_fn_builder=lambda p: build_stage_fns(m, params, p))
    with dep.executor() as ex:
        outs, _ = ex.run_batch([{GraphModel.INPUT: x}])
    err = float(jnp.max(jnp.abs(outs[0][m.output] - direct)))
    print(f"pipeline vs direct max err: {err:.2e} (stages: "
          f"{[len(ls) for ls in dep.plan.stage_layers]} layers)")
    assert err < 1e-4
    print("OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: smaller synthetic CNN")
    main(smoke=ap.parse_args().smoke)
