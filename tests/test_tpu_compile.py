"""Compile the chip's programs for a described TPU v5e; no chip needed.

The TPU compiler ships with JAX.  It compiles for a topology that is
described, not attached, and refuses what the chip would refuse: a Pallas
block not aligned to the tiling, too much VMEM, a program that does not fit
HBM.  Nothing runs here, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.api import DeploymentSpec, plan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.matmul_qi8 import matmul_qi8
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.launch.pipeline_spmd import _CnnLowering, cnn_boundary_specs
from repro.models.cnn import REAL_CNNS

HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:         # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # what is compiled for a described chip cannot be read back from
        # the persistent cache without one: keep it out of the cache
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def resnet50():
    model = REAL_CNNS["ResNet50"]()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pl = plan(DeploymentSpec(model="cnn:ResNet50", stages=4,
                             strategy="balanced"),
              graph=model.to_layer_graph())
    return model, shapes, pl


def _placed(shapes, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def test_resnet50_direct_forward_compiles(resnet50, one_chip):
    model, shapes, _ = resnet50
    x = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(model.apply).lower(_placed(shapes, one_chip),
                                          x).compile()
    _fits_one_chip(compiled)


def test_resnet50_stage_program_compiles(resnet50, one_chip):
    """A middle stage of the 4-stage plan, as ``build_stage_fns`` runs it."""
    model, shapes, pl = resnet50
    bounds, _ = cnn_boundary_specs(model, pl)
    layers = pl.stage_layers[1]
    stage_params = _placed({n: shapes[n] for n in layers if n in shapes},
                           one_chip)
    boundary = {name: jax.ShapeDtypeStruct((1,) + shape, jnp.float32,
                                           sharding=one_chip)
                for name, shape in bounds[1]}
    compiled = model.stage_program(layers, 1).lower(
        stage_params, boundary).compile()
    _fits_one_chip(compiled)


def test_resnet50_spmd_pipeline_compiles(resnet50, topo):
    """The 4-stage SPMD CNN pipeline on a mesh of the four described
    chips: stage hops must lower to collective-permutes."""
    model, shapes, pl = resnet50
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    params = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    m, mb = 4, 2
    low = _CnnLowering(model, params, pl, mesh, m, "model", donate=True)
    w = jax.ShapeDtypeStruct(low.stacked_host.shape, jnp.float32,
                             sharding=NamedSharding(mesh, P("model")))
    x = jax.ShapeDtypeStruct((m, mb, low.flat), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    compiled = low.pipe_jit.lower(w, x).compile()
    assert "collective-permute" in compiled.as_text()
    _fits_one_chip(compiled)


@pytest.fixture(scope="module")
def resnet152():
    model = REAL_CNNS["ResNet152"]()
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pl = plan(DeploymentSpec(model="cnn:ResNet152", stages=4),
              graph=model.to_layer_graph())
    return model, shapes, pl


@pytest.mark.parametrize("stage", range(4))
def test_resnet152_stage_program_compiles_on_its_chip(resnet152, topo,
                                                      stage):
    """Each stage of the benchmark's four-stage ResNet152 plan, as
    ``build_stage_fns`` runs it with stage s on chip s; every cut after
    the first image falls mid-block, so two tensors cross it."""
    model, shapes, pl = resnet152
    bounds, _ = cnn_boundary_specs(model, pl)
    assert len(bounds[stage]) == (1 if stage == 0 else 2)
    chip = SingleDeviceSharding(topo.devices[stage])
    layers = pl.stage_layers[stage]
    stage_params = _placed({n: shapes[n] for n in layers if n in shapes},
                           chip)
    boundary = {name: jax.ShapeDtypeStruct((1,) + shape, jnp.float32,
                                           sharding=chip)
                for name, shape in bounds[stage]}
    compiled = model.stage_program(layers, stage).lower(
        stage_params, boundary).compile()
    _fits_one_chip(compiled)


BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8

# one call per Pallas kernel at a real width: qwen3-1.7b attention
# (16 q / 8 kv heads, head_dim 128), an MLP-sized int8 matmul,
# recurrentgemma-9b's RG-LRU width (4096), rwkv6-1.6b's heads (32 x 64)
KERNEL_CASES = {
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        [((1, 16, 4096, 128), BF16), ((1, 8, 4096, 128), BF16),
         ((1, 8, 4096, 128), BF16)]),
    "flash_decode": (
        flash_decode,
        [((8, 16, 128), BF16), ((8, 8, 4096, 128), BF16),
         ((8, 8, 4096, 128), BF16), ((), jnp.int32)]),
    "matmul_qi8": (
        matmul_qi8, [((512, 2048), I8), ((2048, 6144), I8)]),
    "rglru_scan": (
        rglru_scan,
        [((1, 2048, 4096), BF16), ((1, 2048, 4096), BF16),
         ((1, 4096), F32)]),
    "rwkv6_scan": (
        rwkv6_scan,
        [((1, 32, 2048, 64), BF16)] * 4
        + [((32, 64), BF16), ((1, 32, 64, 64), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_pallas_kernel_compiles(name, one_chip):
    fn, args = KERNEL_CASES[name]
    structs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
               for shape, dtype in args]
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
