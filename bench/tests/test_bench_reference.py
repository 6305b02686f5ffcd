"""The plain reference and the configurations against the program."""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _paths import BENCH, ROOT
from harness import reference
from repro.models.cnn import REAL_CNNS

CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "configs")))


def _arch(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_matches_the_program_and_its_published_size(name):
    arch = _arch(name)
    model = REAL_CNNS[arch["model"]]()
    want = jax.tree.map(lambda a: a.shape,
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert reference.param_shapes(arch) == want
    assert reference.macs(arch) == model.total_macs
    published = arch["published"]["macs_m"] * 1e6
    assert abs(model.total_macs - published) / published < 0.01
    n_params = sum(np.prod(s) for leaves in want.values()
                   for s in leaves.values())
    assert abs(n_params / 1e6 - arch["published"]["params_m"]) \
        / arch["published"]["params_m"] < 0.01


def test_on_bf16_grid_rounds_to_the_nearest_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(5), (4096,)) * 10.0
    g = np.asarray(reference.on_bf16_grid(x))
    # every value is a bfloat16 value, and the nearest one, ties to even
    np.testing.assert_array_equal(
        g, np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    assert not np.array_equal(g, np.asarray(x))
    ties = np.array([1 + 2**-8, 1 + 3 * 2**-8], np.float32)
    np.testing.assert_array_equal(np.asarray(reference.on_bf16_grid(ties)),
                                  [1.0, 1 + 2**-6])


def test_weights_are_on_the_bf16_grid():
    arch = _arch("resnet50")
    params = jax.jit(lambda k: reference.make_params(arch, k))(
        jax.random.PRNGKey(8))
    for layer, leaves in params.items():
        for leaf, a in leaves.items():
            a = np.asarray(a)
            on_grid = np.array_equal(
                a, np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                              .astype(jnp.float32)))
            assert on_grid == (leaf == "w"), (layer, leaf)


def test_reference_agrees_with_the_program_on_cpu():
    arch = _arch("resnet50")
    model = REAL_CNNS["ResNet50"]()
    params = jax.jit(lambda k: reference.make_params(arch, k))(
        jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 224, 224, 3))
    ref = np.asarray(jax.jit(lambda p, x: reference.forward(arch, p, x))(
        params, x))
    got = np.asarray(jax.jit(model.apply)(params, x))
    assert np.abs(ref).max() > 1.0
    assert np.abs(got - ref).max() / np.abs(ref).max() < 1e-5


@pytest.mark.parametrize("where", ["checkout", "bare"])
def test_run_prints_no_result_without_a_tpu_or_the_program(where, tmp_path):
    """In the checkout on a host without a TPU, and in a directory that
    holds only ``BENCHMARK.json`` and ``bench/``, a run exits non-zero
    and prints no result."""
    cwd = ROOT
    if where == "bare":
        cwd = str(tmp_path)
        shutil.copytree(BENCH, tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50.c1.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert '"correct"' not in p.stdout
