"""The control, the plain reference computed in bfloat16 and put in the
program's place, comes out not ``correct`` under each configuration's
limit, and the program (float32 on the CPU) comes out correct.  Small
size: two images."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _paths import BENCH
from harness import compare, reference
from repro.models.cnn import REAL_CNNS


@pytest.mark.parametrize("name", ["resnet50"])
def test_control_fails_and_program_passes(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        arch = json.load(f)
    params = jax.jit(lambda k: reference.make_params(arch, k))(
        jax.random.PRNGKey(11))
    pool = np.asarray(reference.on_bf16_grid(jax.random.normal(
        jax.random.PRNGKey(12), (2,) + tuple(arch["input_shape"]))))
    rows = [0, 1]
    ref = compare.reference_logits(arch, params, pool, rows)
    ctl = compare.reference_logits(arch, params, pool, rows, control=True)
    model = REAL_CNNS[arch["model"]]()
    prog = jax.jit(model.apply)(params, jnp.asarray(pool))

    def judged(got):
        checks = compare.check(arch, rows, np.asarray(got), ref, 2, 0)
        return all(c["value"] <= c["limit"] for c in checks.values())

    assert judged(prog)
    assert not judged(np.stack([ctl[r] for r in rows]))
