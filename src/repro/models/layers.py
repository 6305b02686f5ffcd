"""Functional graph-model framework for the CNN zoo.

Every model is a :class:`GraphModel`: a DAG of :class:`OpNode`, each with a
parameter initializer and a pure-JAX apply function.  From a GraphModel we
derive:

* a runnable forward pass (``init`` / ``apply``), NHWC layout;
* partial execution of any layer subset (``apply_subset``) — this is what the
  pipelined executor runs per stage, with cut-crossing activations passed
  through the stage boundary exactly like the paper's host queues;
* a :class:`repro.core.graph.LayerGraph` with per-layer params/MACs/activation
  bytes (``to_layer_graph``) — the input to the segmentation strategies.

BatchNorm follows inference semantics (running stats folded in); parameter
counts include the 4 per-channel BN tensors, matching Keras' "params" metric
used by the paper's Table 1.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import LayerGraph
from ..core.pipeline import count_hop_bytes, span

Params = Dict[str, Any]


@dataclasses.dataclass
class OpNode:
    name: str
    inputs: List[str]
    init: Callable[[jax.Array], Params]          # key -> params
    apply: Callable[[Params, List[jax.Array]], jax.Array]
    params_count: int
    macs: int
    out_shape: Tuple[int, ...]                   # per-single-input (no batch)
    kind: str = "generic"
    act_dtype_bytes: int = 1                     # int8 CNN path by default

    @property
    def out_bytes(self) -> int:
        return int(np.prod(self.out_shape)) * self.act_dtype_bytes


class GraphModel:
    """A DAG of OpNodes with one input placeholder and one output node."""

    def __init__(self, name: str, input_shape: Tuple[int, ...]):
        self.name = name
        self.input_shape = input_shape
        self.nodes: Dict[str, OpNode] = {}
        self._order: List[str] = []
        self.output: Optional[str] = None

    INPUT = "__input__"

    def add(self, node: OpNode) -> str:
        if node.name in self.nodes or node.name == self.INPUT:
            raise ValueError(f"duplicate node {node.name}")
        for i in node.inputs:
            if i != self.INPUT and i not in self.nodes:
                raise ValueError(f"unknown input {i} of {node.name}")
        self.nodes[node.name] = node
        self._order.append(node.name)
        self.output = node.name
        return node.name

    def shape_of(self, name: str) -> Tuple[int, ...]:
        if name == self.INPUT:
            return self.input_shape
        return self.nodes[name].out_shape

    # -- parameters -----------------------------------------------------------
    def init(self, key: jax.Array) -> Params:
        params: Params = {}
        keys = jax.random.split(key, max(1, len(self._order)))
        for k, name in zip(keys, self._order):
            p = self.nodes[name].init(k)
            if p:
                params[name] = p
        return params

    @property
    def total_params(self) -> int:
        return sum(n.params_count for n in self.nodes.values())

    @property
    def total_macs(self) -> int:
        return sum(n.macs for n in self.nodes.values())

    # -- execution --------------------------------------------------------------
    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        acts: Dict[str, jax.Array] = {self.INPUT: x}
        for name in self._order:
            node = self.nodes[name]
            xs = [acts[i] for i in node.inputs]
            acts[name] = node.apply(params.get(name, {}), xs)
        assert self.output is not None
        return acts[self.output]

    def apply_subset(self, params: Params, boundary: Dict[str, jax.Array],
                     layer_names: Sequence[str]) -> Dict[str, jax.Array]:
        """Execute only `layer_names` (a contiguous depth range), reading
        cut-crossing inputs from `boundary`; returns activations needed by
        later layers (plus the model output if produced)."""
        subset = set(layer_names)
        acts: Dict[str, jax.Array] = dict(boundary)
        for name in self._order:
            if name not in subset:
                continue
            node = self.nodes[name]
            xs = [acts[i] for i in node.inputs]
            acts[name] = node.apply(params.get(name, {}), xs)
        # outputs = activations consumed outside the subset, or final output
        needed: Dict[str, jax.Array] = {}
        for name in self._order:
            if name in subset:
                continue
            for i in self.nodes[name].inputs:
                if i in subset:
                    needed[i] = acts[i]
        if self.output in subset:
            needed[self.output] = acts[self.output]
        return needed

    def stage_program(self, layer_names: Sequence[str],
                      stage: int) -> Callable:
        """``apply_subset`` over ``layer_names`` as one jitted program
        ``(params, boundary) -> outputs``: what pipeline stage ``stage``
        runs.  It is named ``<model name>_stage<stage>``, which a profile
        shows as the program's module."""
        names = tuple(layer_names)

        def program(params: Params, boundary: Dict[str, jax.Array]
                    ) -> Dict[str, jax.Array]:
            return self.apply_subset(params, boundary, layer_names=names)

        program.__name__ = program.__qualname__ = f"{self.name}_stage{stage}"
        return jax.jit(program)

    # -- lowering to the segmentation representation ----------------------------
    def to_layer_graph(self) -> LayerGraph:
        g = LayerGraph(self.name)
        for name in self._order:
            node = self.nodes[name]
            inputs = [i for i in node.inputs if i != self.INPUT]
            g.add_layer(name, params=node.params_count, macs=node.macs,
                        out_bytes=node.out_bytes, inputs=inputs, kind=node.kind)
        return g


def build_stage_fns(model: GraphModel, params: Params, plan: Any,
                    devices: Optional[Sequence[Any]] = None
                    ) -> List[Callable[[Dict[str, jax.Array]],
                                       Dict[str, jax.Array]]]:
    """One stage function per stage of ``plan`` (a ``PlacementPlan`` over
    ``model.to_layer_graph()``), for the host pipeline executor.

    Stage ``s`` runs :meth:`GraphModel.stage_program` over
    ``plan.stage_layers[s]``.  Its parameters are placed on ``devices[s]``
    once, here; each call first moves its input boundary onto that device,
    so consecutive stages may sit on different chips.  ``devices`` defaults
    to ``jax.devices()[0]`` for every stage.  A stage returns what later
    stages read: its own outputs plus the boundary tensors that cross it
    untouched (a skip connection spanning the whole stage).

    Each call waits for its outputs (``block_until_ready``) before it
    returns, so the executor's per-stage busy time
    (``snapshot()["stage_busy_s"]``) is device time, not dispatch time.
    A call times its three steps as spans of the executor item it serves
    (``core.pipeline.span``): ``stage<s>.hop``, the call that moves the
    boundary onto the stage's device (for stage 0 the image's copy from
    the host; it returns once the copy is queued, and the copy ends
    inside the wait), ``stage<s>.dispatch``, the program's asynchronous
    dispatch, and ``stage<s>.wait``, the wait for its outputs.  It also
    adds the bytes of the boundary it moves to its stage's hop-bytes
    counter (``core.pipeline.count_hop_bytes``, read as
    ``snapshot()["stage_hop_bytes"]``).
    """
    stage_layers = [list(ls) for ls in plan.stage_layers]
    if devices is None:
        devices = [jax.devices()[0]] * len(stage_layers)
    if len(devices) != len(stage_layers):
        raise ValueError(f"{len(devices)} devices for "
                         f"{len(stage_layers)} stages")

    def make(s: int) -> Callable:
        names, dev = stage_layers[s], devices[s]
        read_later = {i for ls in stage_layers[s + 1:] for n in ls
                      for i in model.nodes[n].inputs}
        passed = read_later - set(names)
        program = model.stage_program(names, s)
        stage_params = jax.device_put(
            {n: params[n] for n in names if n in params}, dev)
        hop, dispatch, wait = (f"stage{s}.{step}"
                               for step in ("hop", "dispatch", "wait"))

        def run(boundary: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
            count_hop_bytes(sum(v.nbytes for v in boundary.values()))
            with span(hop):
                boundary = jax.device_put(boundary, dev)
            with span(dispatch):
                out = program(stage_params, boundary)
            out.update({k: v for k, v in boundary.items() if k in passed})
            with span(wait):
                return jax.block_until_ready(out)

        return run

    return [make(s) for s in range(len(stage_layers))]


# ---------------------------------------------------------------------------
# Builder: tracks spatial shapes and emits OpNodes with cost annotations.
# ---------------------------------------------------------------------------
class Builder:
    """Convenience layer-emitter for CNN definitions (NHWC, single image)."""

    def __init__(self, name: str, input_hw: Tuple[int, int], channels: int = 3):
        h, w = input_hw
        self.model = GraphModel(name, (h, w, channels))
        self._n = 0

    def _uniq(self, prefix: str) -> str:
        self._n += 1
        return f"{prefix}_{self._n}"

    # ---- primitive ops -------------------------------------------------------
    def conv(self, x: str, filters: int, kernel: int | Tuple[int, int],
             stride: int = 1, padding: str = "same", use_bias: bool = True,
             name: Optional[str] = None, groups: int = 1) -> str:
        kh, kw = (kernel, kernel) if isinstance(kernel, int) else kernel
        in_shape = self.model.shape_of(x)
        h, w, cin = in_shape
        if cin % groups:
            raise ValueError("cin % groups != 0")
        if padding == "same":
            oh, ow = math.ceil(h / stride), math.ceil(w / stride)
        else:
            oh, ow = (h - kh) // stride + 1, (w - kw) // stride + 1
        wshape = (kh, kw, cin // groups, filters)
        pcount = int(np.prod(wshape)) + (filters if use_bias else 0)
        macs = (cin // groups) * filters * kh * kw * oh * ow
        nm = name or self._uniq("conv")

        def init(key: jax.Array) -> Params:
            fan_in = kh * kw * (cin // groups)
            wkey, _ = jax.random.split(key)
            p = {"w": jax.random.normal(wkey, wshape, jnp.float32)
                      * (1.0 / math.sqrt(fan_in))}
            if use_bias:
                p["b"] = jnp.zeros((filters,), jnp.float32)
            return p

        pad = padding.upper()
        strides = (stride, stride)

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            y = jax.lax.conv_general_dilated(
                xs[0], p["w"], window_strides=strides, padding=pad,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=groups)
            if use_bias:
                y = y + p["b"]
            return y

        self.model.add(OpNode(nm, [x], init, apply, pcount, macs,
                              (oh, ow, filters), kind="conv"))
        return nm

    def dwconv(self, x: str, kernel: int, stride: int = 1,
               padding: str = "same", use_bias: bool = True,
               name: Optional[str] = None, multiplier: int = 1) -> str:
        in_shape = self.model.shape_of(x)
        _, _, cin = in_shape
        return self.conv(x, cin * multiplier, kernel, stride, padding,
                         use_bias, name or self._uniq("dwconv"), groups=cin)

    def bn(self, x: str, name: Optional[str] = None) -> str:
        h, w, c = self.model.shape_of(x)
        nm = name or self._uniq("bn")

        def init(key: jax.Array) -> Params:
            return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
                    "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            inv = jax.lax.rsqrt(p["var"] + 1e-3)
            return (xs[0] - p["mean"]) * inv * p["gamma"] + p["beta"]

        # Keras counts all 4 BN tensors in "params" (2 trainable + 2 stats)
        self.model.add(OpNode(nm, [x], init, apply, 4 * c, 0, (h, w, c),
                              kind="bn"))
        return nm

    def act(self, x: str, fn: str = "relu", name: Optional[str] = None) -> str:
        shape = self.model.shape_of(x)
        nm = name or self._uniq(fn)
        f = {"relu": jax.nn.relu,
             "relu6": lambda v: jnp.clip(v, 0, 6),
             "swish": jax.nn.silu,
             "sigmoid": jax.nn.sigmoid}[fn]

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            return f(xs[0])

        self.model.add(OpNode(nm, [x], lambda k: {}, apply, 0, 0, shape,
                              kind="act"))
        return nm

    def pool(self, x: str, kind: str, size: int, stride: int,
             padding: str = "same", name: Optional[str] = None) -> str:
        h, w, c = self.model.shape_of(x)
        if padding == "same":
            oh, ow = math.ceil(h / stride), math.ceil(w / stride)
        else:
            oh, ow = (h - size) // stride + 1, (w - size) // stride + 1
        nm = name or self._uniq(f"{kind}pool")
        pad = padding.upper()

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            v = xs[0]
            if kind == "max":
                return jax.lax.reduce_window(
                    v, -jnp.inf, jax.lax.max, (1, size, size, 1),
                    (1, stride, stride, 1), pad)
            s = jax.lax.reduce_window(
                v, 0.0, jax.lax.add, (1, size, size, 1),
                (1, stride, stride, 1), pad)
            ones = jnp.ones_like(v)
            cnt = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, (1, size, size, 1),
                (1, stride, stride, 1), pad)
            return s / cnt

        self.model.add(OpNode(nm, [x], lambda k: {}, apply, 0, 0,
                              (oh, ow, c), kind="pool"))
        return nm

    def gap(self, x: str, name: Optional[str] = None) -> str:
        _, _, c = self.model.shape_of(x)
        nm = name or self._uniq("gap")

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            return jnp.mean(xs[0], axis=(1, 2))

        self.model.add(OpNode(nm, [x], lambda k: {}, apply, 0, 0, (c,),
                              kind="pool"))
        return nm

    def dense(self, x: str, units: int, use_bias: bool = True,
              name: Optional[str] = None) -> str:
        shape = self.model.shape_of(x)
        fin = int(np.prod(shape))
        nm = name or self._uniq("dense")
        pcount = fin * units + (units if use_bias else 0)

        def init(key: jax.Array) -> Params:
            p = {"w": jax.random.normal(key, (fin, units), jnp.float32)
                      * (1.0 / math.sqrt(fin))}
            if use_bias:
                p["b"] = jnp.zeros((units,))
            return p

        def apply(p: Params, xs: List[jax.Array]) -> jax.Array:
            v = xs[0].reshape((xs[0].shape[0], -1))
            y = v @ p["w"]
            return y + p["b"] if use_bias else y

        self.model.add(OpNode(nm, [x], init, apply, pcount, fin * units,
                              (units,), kind="dense"))
        return nm

    def add(self, xs: Sequence[str], name: Optional[str] = None) -> str:
        shape = self.model.shape_of(xs[0])
        nm = name or self._uniq("add")

        def apply(p: Params, vs: List[jax.Array]) -> jax.Array:
            out = vs[0]
            for v in vs[1:]:
                out = out + v
            return out

        self.model.add(OpNode(nm, list(xs), lambda k: {}, apply, 0, 0, shape,
                              kind="add"))
        return nm

    def concat(self, xs: Sequence[str], name: Optional[str] = None) -> str:
        shapes = [self.model.shape_of(x) for x in xs]
        h, w = shapes[0][0], shapes[0][1]
        c = sum(s[2] for s in shapes)
        nm = name or self._uniq("concat")

        def apply(p: Params, vs: List[jax.Array]) -> jax.Array:
            return jnp.concatenate(vs, axis=-1)

        self.model.add(OpNode(nm, list(xs), lambda k: {}, apply, 0, 0,
                              (h, w, c), kind="concat"))
        return nm

    # ---- compound blocks ------------------------------------------------------
    def conv_bn(self, x: str, filters: int, kernel, stride: int = 1,
                padding: str = "same", act: Optional[str] = "relu",
                prefix: Optional[str] = None) -> str:
        p = prefix or self._uniq("cb")
        y = self.conv(x, filters, kernel, stride, padding, use_bias=False,
                      name=f"{p}_conv")
        y = self.bn(y, name=f"{p}_bn")
        if act:
            y = self.act(y, act, name=f"{p}_{act}")
        return y

    def build(self) -> GraphModel:
        return self.model
