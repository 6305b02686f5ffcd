"""CNN zoo: Table 1 fidelity (params/MACs) + runnable forwards + pipelined
subset execution == direct forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import api_plan as plan
from repro.core import EdgeTPUModel
from repro.core.pipeline import PipelineExecutor
from repro.models.cnn import REAL_CNNS, TABLE1, synthetic_cnn
from repro.models.layers import Builder, GraphModel, build_stage_fns

# NASNetMobile is a flagged structural approximation (params match, MACs
# deviate); V2 ResNets share V1 MAC structure in our builders.
MACS_EXEMPT = {"NASNetMobile", "ResNet50V2", "ResNet101V2", "ResNet152V2"}


@pytest.mark.parametrize("name", sorted(REAL_CNNS))
def test_table1_params(name):
    m = REAL_CNNS[name]()
    ref_p, _ = TABLE1[name]
    rel = abs(m.total_params / 1e6 - ref_p) / ref_p
    assert rel < 0.08, f"{name}: {m.total_params/1e6:.2f}M vs {ref_p}M"


@pytest.mark.parametrize("name", sorted(set(REAL_CNNS) - MACS_EXEMPT))
def test_table1_macs(name):
    m = REAL_CNNS[name]()
    _, ref_m = TABLE1[name]
    rel = abs(m.total_macs / 1e6 - ref_m) / ref_m
    assert rel < 0.12, f"{name}: {m.total_macs/1e6:.0f} vs {ref_m} MMACs"


def test_synthetic_param_formula():
    for f, L in ((32, 5), (100, 5), (64, 3)):
        m = synthetic_cnn(f, L=L)
        assert m.total_params == 9 * f * (3 + f * (L - 1))


def test_synthetic_forward_shapes_and_finite():
    m = synthetic_cnn(16)
    params = m.init(jax.random.PRNGKey(0))
    x = jnp.ones((2, 64, 64, 3))
    y = m.apply(params, x)
    assert y.shape == (2, 64, 64, 16)
    assert np.isfinite(np.asarray(y)).all()


def test_mobilenet_forward():
    m = REAL_CNNS["MobileNetV2"]()
    params = m.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 224, 224, 3))
    y = m.apply(params, x)
    assert y.shape == (1, 1000)
    assert np.isfinite(np.asarray(y)).all()


def _pipeline_vs_direct(model: GraphModel, n_stages: int, pl=None):
    if pl is None:
        pl = plan(model.to_layer_graph(), n_stages, "balanced_norefine")
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1,) + model.input_shape)
    direct = model.apply(params, x)

    execu = PipelineExecutor(build_stage_fns(model, params, pl))
    outs, _ = execu.run_batch([{GraphModel.INPUT: x}])
    np.testing.assert_allclose(np.asarray(outs[0][model.output]),
                               np.asarray(direct), rtol=2e-4, atol=2e-4)


def test_pipelined_chain_model_equals_direct():
    _pipeline_vs_direct(synthetic_cnn(12, hw=32), 3)


def test_pipelined_branchy_model_equals_direct():
    """Horizontal cuts must be correct across open paths (paper Fig. 8):
    use a small inception-style builder with 4-way branches."""
    from repro.models.layers import Builder
    b = Builder("mini_inception", (32, 32), 3)
    x = b.conv_bn(b.model.INPUT, 8, 3, 1, "same", "relu", "stem")
    for i in range(3):
        b1 = b.conv_bn(x, 8, 1, 1, "same", "relu", f"m{i}_b1")
        b2 = b.conv_bn(x, 6, 1, 1, "same", "relu", f"m{i}_b2a")
        b2 = b.conv_bn(b2, 8, 3, 1, "same", "relu", f"m{i}_b2b")
        b3 = b.pool(x, "avg", 3, 1, "same", f"m{i}_b3p")
        b3 = b.conv_bn(b3, 8, 1, 1, "same", "relu", f"m{i}_b3")
        x = b.concat([b1, b2, b3], f"m{i}_cat")
    x = b.gap(x, "gap")
    b.dense(x, 10, name="head")
    _pipeline_vs_direct(b.build(), 4)


def test_stage_fns_pass_skip_through_whole_stage():
    """A skip connection spanning whole stages of an uneven (comp) plan:
    the stages between must hand the tensor on unchanged."""
    b = Builder("skipnet", (16, 16), 3)
    s = b.act(b.conv(b.model.INPUT, 8, 3, name="c1"), name="c1_relu")
    x = s
    for i in range(6):
        x = b.conv(x, 8, 3, name=f"mid{i}")
    x = b.add([x, s], name="skip_add")
    b.dense(b.gap(x, name="pool"), 10, name="head")
    model = b.build()
    pl = plan(model.to_layer_graph(), 4, "comp")
    assert not any("c1_relu" in ls or "skip_add" in ls
                   for ls in pl.stage_layers[1:-1])
    _pipeline_vs_direct(model, 4, pl)


def test_stage_fns_place_on_device_and_wait():
    model = synthetic_cnn(6, hw=16)
    pl = plan(model.to_layer_graph(), 2, "balanced_norefine")
    params = model.init(jax.random.PRNGKey(0))
    dev = jax.devices()[0]
    x = np.ones((2,) + model.input_shape, np.float32)    # host input
    out = {GraphModel.INPUT: x}
    for fn in build_stage_fns(model, params, pl, devices=[dev, dev]):
        out = fn(out)
        assert all(a.devices() == {dev} and a.is_fully_replicated
                   for a in out.values())
    np.testing.assert_allclose(np.asarray(out[model.output]),
                               np.asarray(model.apply(params, x)),
                               rtol=2e-4, atol=2e-4)
    with pytest.raises(ValueError, match="1 devices for 2 stages"):
        build_stage_fns(model, params, pl, devices=[dev])


def test_min_stages_matches_paper_table5():
    """Paper Table 5: ceil(size/8MiB) — e.g. ResNet101 -> 6, ResNet152 -> 8,
    InceptionV4 -> 7, Xception -> 4 (int8 bytes == param count)."""
    from repro.core.placement import min_stages_to_fit
    expect = {"ResNet101": 6, "ResNet152": 8, "InceptionV4": 7,
              "Xception": 3, "DenseNet121": 2}
    for name, n in expect.items():
        g = REAL_CNNS[name]().to_layer_graph()
        got = min_stages_to_fit(g, 8 * 2 ** 20)
        assert abs(got - n) <= 1, (name, got, n)
