"""Plain float32 ResNet v1 forward and the benchmark's own weights.

The architecture comes from a configuration file (``bench/configs``):
stem convolution, stage block counts, base width and bottleneck
expansion.  The forward is written with ``jax.lax`` primitives and shares
no code with the program; it reads the weights the benchmark made from
the seed, keyed by the layer names that the program's ResNet uses (so
the same dictionary can be handed to both).

Layer by layer, as the configuration states:

* stem: 7x7/2 convolution with bias, batch norm, ReLU, 3x3/2 max pool,
  all with SAME padding;
* bottleneck block (v1): 1x1 conv (stride s) -> BN -> ReLU -> 3x3 conv ->
  BN -> ReLU -> 1x1 conv (4x width) -> BN, added to the shortcut (a 1x1
  conv with stride s and BN on the first block of each stage, else the
  identity), then ReLU;
* global average pool and a dense classifier with bias.

Batch norm is in inference form with running statistics:
``(x - mean) * rsqrt(var + eps) * gamma + beta``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Dict[str, jax.Array]]


def _blocks(arch: Dict[str, Any]) -> Iterator[Tuple[str, int, int, int]]:
    """(prefix, input channels, width, stride) of every bottleneck block."""
    width, exp = arch["width"], arch["expansion"]
    cin = arch["stem"]["filters"]
    for si, n in enumerate(arch["blocks"]):
        w = width * 2 ** si
        for bi in range(n):
            yield f"s{si}b{bi}", cin, w, (2 if bi == 0 and si > 0 else 1)
            cin = w * exp


def param_shapes(arch: Dict[str, Any]) -> Dict[str, Dict[str, Tuple]]:
    """Every weight the forward reads, with its shape."""
    stem = arch["stem"]
    k, c0 = stem["kernel"], stem["filters"]
    cin = arch["input_shape"][-1]
    exp = arch["expansion"]

    def bn(c):
        return {"gamma": (c,), "beta": (c,), "mean": (c,), "var": (c,)}

    shapes = {"stem_conv": {"w": (k, k, cin, c0), "b": (c0,)},
              "stem_bn": bn(c0)}
    for pfx, ci, w, _ in _blocks(arch):
        if pfx.endswith("b0"):
            shapes[f"{pfx}_scconv"] = {"w": (1, 1, ci, w * exp)}
            shapes[f"{pfx}_scbn"] = bn(w * exp)
        shapes[f"{pfx}_a_conv"] = {"w": (1, 1, ci, w)}
        shapes[f"{pfx}_a_bn"] = bn(w)
        shapes[f"{pfx}_b_conv"] = {"w": (3, 3, w, w)}
        shapes[f"{pfx}_b_bn"] = bn(w)
        shapes[f"{pfx}_c_conv"] = {"w": (1, 1, w, w * exp)}
        shapes[f"{pfx}_c_bn"] = bn(w * exp)
    feat = arch["width"] * 2 ** (len(arch["blocks"]) - 1) * exp
    shapes["predictions"] = {"w": (feat, arch["classes"]),
                             "b": (arch["classes"],)}
    return shapes


def make_params(arch: Dict[str, Any], key: jax.Array) -> Params:
    """Random weights from ``key``, float32.  Call under ``jax.jit`` so
    they are made on the device in one program.

    Convolutions and the classifier draw N(0, 1/fan_in), rounded to the
    nearest bfloat16 value, as a checkpoint trained in bfloat16 holds
    them; batch norm draws its scale and variance from U(0.5, 1.5) and
    its shift and mean from N(0, 0.1^2), so that every BN term is
    exercised; biases draw N(0, 0.1^2).  Each kind is one draw, cut into
    the leaves, so the program stays small however many layers there
    are.

    Why the bfloat16 grid: at the configuration's default precision the
    chip rounds each convolution's operands to bfloat16.  Weights that
    bfloat16 holds exactly leave that rounding to the activations alone,
    so the served logits sit about four times closer to the float32
    reference than a model computed in bfloat16 does (``PERF.md``), and
    the comparison can tell the two apart."""
    shapes = param_shapes(arch)
    kinds = {"w": [], "u": [], "n": []}
    for layer, leaves in shapes.items():
        for leaf, shape in leaves.items():
            kind = "w" if leaf == "w" else "u" if leaf in ("gamma", "var") \
                else "n"
            kinds[kind].append((layer, leaf, shape))
    k_w, k_u, k_n = jax.random.split(key, 3)
    draws = {
        "w": jax.random.normal(k_w, (_count(kinds["w"]),)),
        "u": jax.random.uniform(k_u, (_count(kinds["u"]),), minval=0.5,
                                maxval=1.5),
        "n": 0.1 * jax.random.normal(k_n, (_count(kinds["n"]),))}
    params: Params = {layer: {} for layer in shapes}
    for kind, leaves in kinds.items():
        at = 0
        for layer, leaf, shape in leaves:
            size = math.prod(shape)
            a = draws[kind][at:at + size].reshape(shape)
            if kind == "w":
                a = on_bf16_grid(a / math.sqrt(math.prod(shape[:-1])))
            params[layer][leaf] = a
            at += size
    return params


def on_bf16_grid(a: jax.Array) -> jax.Array:
    """Float32 ``a`` rounded to the nearest bfloat16 value (ties to even),
    kept in float32.  Done on the bits, so that no compiler pass can fold
    a round trip through bfloat16 away."""
    b = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _count(leaves) -> int:
    return sum(math.prod(shape) for _, _, shape in leaves)


def forward(arch: Dict[str, Any], params: Params, x: jax.Array,
            dtype=jnp.float32) -> jax.Array:
    """Logits of a batch of NHWC images, with weights, activations and
    results held in ``dtype`` and every convolution and matrix product at
    ``highest`` precision."""
    eps = arch["bn_epsilon"]
    hi = jax.lax.Precision.HIGHEST
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = x.astype(dtype)

    def conv(name, h, stride):
        return jax.lax.conv_general_dilated(
            h, p[name]["w"], (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi)

    def bn(name, h):
        q = p[name]
        return (h - q["mean"]) * jax.lax.rsqrt(q["var"] + eps) * q["gamma"] \
            + q["beta"]

    stem = arch["stem"]
    h = conv("stem_conv", x, stem["stride"]) + p["stem_conv"]["b"]
    h = jax.nn.relu(bn("stem_bn", h))
    ps, pst = stem["pool"], stem["pool_stride"]
    h = jax.lax.reduce_window(h, jnp.array(-jnp.inf, dtype), jax.lax.max,
                              (1, ps, ps, 1), (1, pst, pst, 1), "SAME")
    for pfx, _, _, stride in _blocks(arch):
        if pfx.endswith("b0"):
            sc = bn(f"{pfx}_scbn", conv(f"{pfx}_scconv", h, stride))
        else:
            sc = h
        y = jax.nn.relu(bn(f"{pfx}_a_bn", conv(f"{pfx}_a_conv", h, stride)))
        y = jax.nn.relu(bn(f"{pfx}_b_bn", conv(f"{pfx}_b_conv", y, 1)))
        y = bn(f"{pfx}_c_bn", conv(f"{pfx}_c_conv", y, 1))
        h = jax.nn.relu(sc + y)
    h = jnp.mean(h, axis=(1, 2))
    q = p["predictions"]
    logits = jnp.dot(h, q["w"], precision=hi) + q["b"]
    return logits.astype(jnp.float32)


def macs(arch: Dict[str, Any]) -> int:
    """Multiply-accumulates of one image through the forward above."""
    hw = arch["input_shape"][0]
    stem = arch["stem"]
    hw = math.ceil(hw / stem["stride"])
    shapes = param_shapes(arch)
    total = hw * hw * math.prod(shapes["stem_conv"]["w"])
    hw = math.ceil(hw / stem["pool_stride"])
    for pfx, _, _, stride in _blocks(arch):
        out = math.ceil(hw / stride)
        for conv in ("scconv", "a_conv", "b_conv", "c_conv"):
            w = shapes.get(f"{pfx}_{conv}")
            if w is not None:
                total += out * out * math.prod(w["w"])
        hw = out
    return total + math.prod(shapes["predictions"]["w"])


def logits_in_blocks(fn, images: jax.Array, block: int) -> List[jax.Array]:
    """``fn`` over ``images`` in blocks of ``block`` rows (the last block
    padded to full size so one program serves all)."""
    out = []
    n = images.shape[0]
    for i in range(0, n, block):
        x = images[i:i + block]
        pad = block - x.shape[0]
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        out.append(fn(x)[:block - pad])
    return out
