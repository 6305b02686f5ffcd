"""ResNet152 at its published widths, served as the benchmark's cells
serve it: four balanced stages through ``deploy`` and ``serve()``, one
224x224 image submitted, every stage on the CPU device, against the plain
reference, and the configuration's limit against the bfloat16 control."""
import json
import os

import jax
import numpy as np

from _paths import BENCH
from harness import compare, reference
from repro.api import DeploymentSpec, deploy
from repro.models.cnn import REAL_CNNS
from repro.models.layers import GraphModel, build_stage_fns


def test_served_four_stages_agree_with_the_reference():
    with open(os.path.join(BENCH, "configs", "resnet152.json")) as f:
        arch = json.load(f)
    model = REAL_CNNS[arch["model"]]()
    params = jax.jit(lambda k: reference.make_params(arch, k))(
        jax.random.PRNGKey(21))
    image = np.asarray(reference.on_bf16_grid(jax.random.normal(
        jax.random.PRNGKey(22), (1,) + tuple(arch["input_shape"]))))
    dev = jax.devices("cpu")[0]
    dep = deploy(DeploymentSpec(model="cnn:ResNet152", stages=4),
                 graph=model.to_layer_graph(),
                 stage_fn_builder=lambda plan: build_stage_fns(
                     model, params, plan, devices=[dev] * plan.n_stages))
    try:
        assert dep.plan.n_stages == 4
        server = dep.serve()
        server.start()
        request = server.submit({GraphModel.INPUT: image})
        assert request.event.wait(120) and request.error is None, \
            request.error
        got = np.asarray(request.result[model.output])
    finally:
        dep.close()
    ref = np.asarray(jax.jit(lambda p, x: reference.forward(arch, p, x))(
        params, image))
    # float32 on both sides: the CPU computes the program's convolutions
    # in float32 at default precision, so at most the order of summation
    # differs (here nothing does, and the error reads 0); 1e-4 leaves
    # that order room over 152 layers and is still 50 times below what
    # computing in bfloat16 gives (PERF.md: the control reads ~5e-3)
    err = compare.row_errors(got, ref)
    assert np.abs(ref).max() > 1.0
    assert err.max() < 1e-4, err
    # the configuration's limit passes the served answer and refuses the
    # reference computed in bfloat16 in its place
    ctl = compare.reference_logits(arch, params, image, [0], control=True)

    def judged(logits):
        checks = compare.check(arch, [0], logits, {0: ref[0]}, 1, 0)
        return all(c["value"] <= c["limit"] for c in checks.values())

    assert judged(got)
    assert not judged(ctl[0][None])
