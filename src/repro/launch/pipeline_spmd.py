"""SPMD pipeline execution: lower a PlacementPlan onto a device mesh.

The host-threaded executor (core/pipeline.py) is paper-faithful for a PCIe
card of Edge TPUs; on a pod the stage-to-stage hop is a
``jax.lax.ppermute`` over ICI inside ``shard_map``.  This module lowers
*any* unreplicated :class:`~repro.core.placement.PlacementPlan` onto a mesh
axis:

* **CNN GraphModels** — each stage's layer range is fused into one traced
  per-stage callable built on ``GraphModel.apply_subset``; the tensors
  crossing each cut (skip connections included — a tensor produced in
  stage 0 and consumed in stage 3 rides through the intermediate stages)
  are flat-packed into one fixed-size ``(microbatch, FLAT)`` f32 buffer so
  every stage has a uniform signature, selected per device with
  ``jax.lax.switch`` on the stage index.
* **LM scan-block families** — contiguous block ranges per stage.  Uneven
  per-stage block counts are executed *without* the identity-masked
  padding tax: stages are grouped by distinct count and each group scans a
  statically-sliced ``blocks[:c]`` inside a ``lax.switch`` branch (a plan
  with equal counts compiles to a plain scan, no switch at all).

GPipe circular schedule, M microbatches over S stages::

    t = 0 .. M+S-2:
      stage 0 injects microbatch t (while t < M)
      every stage applies its fused range to its current input
      outputs rotate to the next stage via ppermute
      stage S-1 emits microbatch t-S+1

Output collection is a **last-stage-only gather** (``out_specs``
sharded over the stage axis; the host reads the final shard) — not the
previous O(S) ``psum`` broadcast that materialized the full output buffer
on every device.

**Weight streaming** (:func:`stream_stage_weights`): per-stage weight
shards are placed on their pipeline devices with asynchronous transfers
issued in stage order — stage *k+1*'s copy is in flight while stage *k*'s
lands — and the pipeline's AOT compilation runs while they land, so the
non-amortizing ``t_weight_load`` fill the placement DP models is
overlapped with bring-up instead of serialized in front of it.  The
:class:`StreamReport` separates the wall fill from ``blocked_s`` — the
time the host spent *waiting* on transfers.  Overlapped streaming drives
``blocked_s`` to ~0 (the transfers land behind the compile) on any
backend; the *wall* fill only shrinks where transfers have their own DMA
engine (real TPUs) — on the CPU-emulated mesh host-to-device copies run
on the same worker pool and memory bus as every other XLA operation, so
wall time is conserved no matter the issue order, and ``blocked_s`` is
the number the benchmark asserts on.

:class:`SpmdPipelineExecutor` wraps the lowering behind the
``Deployment.executor(backend="spmd")`` front door, with buffer donation
(``donate_argnums``) on the inter-stage microbatch buffer, batch padding
for microbatch counts that do not divide the batch, and per-stage
predicted-vs-achieved probes for the modeled-vs-real loop.

Replicated-stage plans belong to the host executor:
:func:`_require_unreplicated` fails fast for direct low-level calls, and
the front door (``Deployment.executor``) downgrades that to a logged
fallback onto :class:`~repro.core.pipeline.PipelineExecutor`.
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.placement import PlacementPlan
from ..models.layers import GraphModel

Params = Any

# the CPU backend cannot always honor donation; the result is correct,
# the warning is noise on the emulated mesh
_DONATION_NOISE = "Some donated buffers were not usable"


# ---------------------------------------------------------------------------
# plan-side helpers
# ---------------------------------------------------------------------------
def stage_block_counts(plan: PlacementPlan, n_blocks: int) -> List[int]:
    """Blocks per stage from a plan over the full LayerGraph (embed +
    block_i + final_norm/head nodes): count only block_* layers."""
    counts = []
    for layers in plan.stage_layers:
        counts.append(sum(1 for l in layers if l.startswith("block_")))
    assert sum(counts) == n_blocks, (counts, n_blocks)
    return counts


def plan_supports_spmd(plan: PlacementPlan) -> bool:
    """One stage == one mesh slice: replicated stages need the host
    executor's round-robin fan-out."""
    reps = getattr(plan, "replica_counts", None)
    return not (reps and any(r != 1 for r in reps))


def _require_unreplicated(plan: PlacementPlan) -> None:
    """Hard error for direct low-level calls; the ``Deployment.executor``
    front door checks :func:`plan_supports_spmd` first and falls back to
    the host executor with a logged notice instead of reaching this."""
    if not plan_supports_spmd(plan):
        raise NotImplementedError(
            f"SPMD pipeline does not support replicated stages "
            f"(replica_counts={plan.replica_counts}); use the host "
            f"PipelineExecutor or re-plan with replicate=False")


def _stage_devices(mesh: Mesh, stage_axis: str) -> List[Any]:
    """One representative device per pipeline stage (the first of each
    mesh slice along ``stage_axis``)."""
    ax = list(mesh.axis_names).index(stage_axis)
    grid = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    return [grid[s].flat[0] for s in range(grid.shape[0])]


def default_stage_mesh(n_stages: int, stage_axis: str = "model") -> Mesh:
    """A (1, S) mesh over the first S local devices (tests / benches force
    the device count via XLA_FLAGS=--xla_force_host_platform_device_count)."""
    devs = jax.devices()
    if len(devs) < n_stages:
        raise ValueError(
            f"SPMD pipeline needs >= {n_stages} devices for {n_stages} "
            f"stages; this process sees {len(devs)} (force a host mesh "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_stages} before the first jax import)")
    return Mesh(np.asarray(devs[:n_stages]).reshape(1, n_stages),
                ("data", stage_axis))


# ---------------------------------------------------------------------------
# the circular GPipe schedule (shared by the CNN and LM lowerings)
# ---------------------------------------------------------------------------
def _gpipe_outputs(stage_apply: Callable[[jax.Array], jax.Array],
                   sid: jax.Array, x_all: jax.Array, n_stages: int,
                   stage_axis: str) -> jax.Array:
    """Run the schedule inside shard_map; returns the (m, mb, ...) outputs
    buffer, valid on the last stage only (callers gather that shard)."""
    m = x_all.shape[0]
    state = jnp.zeros_like(x_all[0])
    outputs = jnp.zeros_like(x_all)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(t, carry):
        state, outputs = carry
        inj = x_all[jnp.clip(t, 0, m - 1)]
        inp = jnp.where(jnp.logical_and(sid == 0, t < m), inj, state)
        out = stage_apply(inp)
        widx = t - (n_stages - 1)
        write = jnp.logical_and(sid == n_stages - 1,
                                jnp.logical_and(widx >= 0, widx < m))
        upd = jax.lax.dynamic_update_slice(
            outputs, out[None], (jnp.clip(widx, 0, m - 1),) + (0,) * out.ndim)
        outputs = jnp.where(write, upd, outputs)
        state = jax.lax.ppermute(out, stage_axis, perm)
        return state, outputs

    _, outputs = jax.lax.fori_loop(0, m + n_stages - 1, step,
                                   (state, outputs))
    return outputs


def _last_stage_block(out: jax.Array) -> jax.Array:
    """The last stage's ``(m, mb, ...)`` block of a stage-sharded pipeline
    output, read from that stage's own device: the other stages' blocks
    are never gathered (indexing the global array would need them)."""
    n = out.shape[0]
    for shard in out.addressable_shards:
        if shard.index[0].indices(n)[1] == n:
            return shard.data[-1]
    raise RuntimeError("this process holds no shard of the last stage")


# ---------------------------------------------------------------------------
# LM lowering: contiguous block ranges, unpadded uneven stages
# ---------------------------------------------------------------------------
def build_stage_blocks(blocks: Params, counts: Sequence[int]
                       ) -> Tuple[Params, jax.Array]:
    """Repack the (L, ...) stacked blocks into (S, max_c, ...) + count mask.

    Padding slots replicate block 0; the unpadded switch path never reads
    them (each stage scans a static ``[:count]`` slice), the mask is kept
    for callers that still want the identity-masked view."""
    s = len(counts)
    max_c = max(counts)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    mask = np.zeros((s, max_c), np.bool_)
    for i, c in enumerate(counts):
        mask[i, :c] = True

    def repack(leaf):
        parts = []
        for i, c in enumerate(counts):
            seg = leaf[offsets[i]:offsets[i + 1]]
            if c < max_c:
                pad = jnp.broadcast_to(leaf[:1],
                                       (max_c - c,) + leaf.shape[1:])
                seg = jnp.concatenate([seg, pad], axis=0)
            parts.append(seg)
        return jnp.stack(parts, axis=0)

    return jax.tree.map(repack, blocks), jnp.asarray(mask)


def _lm_stage_apply_builder(cfg, counts: Sequence[int]):
    """Per-device stage body: scan exactly this stage's blocks.

    Equal counts compile to one plain scan; uneven counts become a
    ``lax.switch`` over the *distinct* counts, each branch scanning a
    statically-sliced ``blocks[:c]`` — no identity-masked padding compute."""
    from ..models import lm
    distinct = sorted(set(counts))
    count_idx = np.asarray([distinct.index(c) for c in counts], np.int32)

    def make(blocks_l, positions, sid):
        fn = lm._block_fn(cfg)

        def scan_c(c):
            def apply_c(x):
                if c == 0:
                    return x

                def body(x, bp):
                    return fn(x, bp, positions), None

                sliced = jax.tree.map(lambda a: a[:c], blocks_l)
                x, _ = jax.lax.scan(body, x, sliced)
                return x

            return apply_c

        if len(distinct) == 1:
            return scan_c(distinct[0])
        branches = [scan_c(c) for c in distinct]
        my_idx = jnp.asarray(count_idx)[sid]
        return lambda x: jax.lax.switch(my_idx, branches, x)

    return make


def make_pipeline_hidden(cfg, mesh: Mesh, plan: PlacementPlan,
                         n_microbatches: int, stage_axis: str = "model",
                         donate: bool = True):
    """Returns hidden_fn(params, batch) -> (B, S, D) hidden states, with the
    blocks executed as a `stage_axis`-wide pipeline per the plan."""
    from ..models import lm
    _require_unreplicated(plan)
    n_stages = mesh.shape[stage_axis]
    assert plan.n_stages == n_stages, (plan.n_stages, n_stages)
    counts = stage_block_counts(plan, cfg.n_layers)
    m = n_microbatches
    apply_builder = _lm_stage_apply_builder(cfg, counts)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(stage_axis), P(), P()),
                       out_specs=P(stage_axis), check_vma=False)
    def pipe(blocks_sh, x_all, positions):
        blocks_l = jax.tree.map(lambda a: a[0], blocks_sh)
        sid = jax.lax.axis_index(stage_axis)
        stage_apply = apply_builder(blocks_l, positions, sid)
        outputs = _gpipe_outputs(stage_apply, sid, x_all, n_stages,
                                 stage_axis)
        # last-stage-only gather: each device contributes its (m, mb, s, d)
        # block; the host reads shard S-1 instead of a psum broadcast
        return outputs[None]

    pipe_jit = jax.jit(pipe, donate_argnums=(1,) if donate else ())

    def hidden_fn(params: Params, batch: Dict[str, jax.Array]) -> jax.Array:
        x = lm.embed_tokens(cfg, params, batch["tokens"])
        if cfg.family == "vlm" and "embeds" in batch:
            x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
        b, s, d = x.shape
        assert b % m == 0, (b, m)
        mb = b // m
        positions = jnp.arange(s)[None, :]
        if cfg.family == "vlm":
            positions = jnp.broadcast_to(positions[None], (3, 1, s))
        stage_blocks, _ = build_stage_blocks(params["blocks"], counts)
        x_mb = x.reshape(m, mb, s, d)
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATION_NOISE)
            out = pipe_jit(stage_blocks, x_mb, positions)
        return jax.device_get(_last_stage_block(out)).reshape(b, s, d)

    return hidden_fn


def pipeline_logits(cfg, mesh: Mesh, plan: PlacementPlan,
                    params: Params, batch: Dict[str, jax.Array],
                    n_microbatches: int = 4) -> jax.Array:
    from ..models import lm
    hidden_fn = make_pipeline_hidden(cfg, mesh, plan, n_microbatches)
    h = hidden_fn(params, batch)
    return lm.unembed(cfg, params, h)


# ---------------------------------------------------------------------------
# CNN lowering: fused apply_subset ranges behind flat boundary buffers
# ---------------------------------------------------------------------------
def _cnn_stage_of(model: GraphModel, plan: PlacementPlan) -> Dict[str, int]:
    stage_of: Dict[str, int] = {}
    for s, layers in enumerate(plan.stage_layers):
        for name in layers:
            stage_of[name] = s
    missing = [n for n in model._order if n not in stage_of]
    if missing:
        raise ValueError(f"plan does not cover model layers {missing[:5]}; "
                         f"was it planned over {model.name}'s LayerGraph?")
    return stage_of


def cnn_boundary_specs(model: GraphModel, plan: PlacementPlan
                       ) -> Tuple[List[List[Tuple[str, Tuple[int, ...]]]],
                                  List[Tuple[str, Tuple[int, ...]]]]:
    """Per-stage input boundaries as ordered ``(name, shape)`` lists.

    ``B[s]`` is everything stage ``s`` reads that it does not compute:
    the model input for stage 0, and for later stages every tensor
    produced at a stage ``< s`` with a consumer at a stage ``>= s``
    (skip connections make these multi-tensor and make tensors ride
    through intermediate stages unchanged).  Also returns the packed
    output spec of the last stage."""
    S = plan.n_stages
    stage_of = _cnn_stage_of(model, plan)
    consumers: Dict[str, List[str]] = {}
    for name in model._order:
        for i in model.nodes[name].inputs:
            consumers.setdefault(i, []).append(name)
    B: List[List[Tuple[str, Tuple[int, ...]]]] = [
        [(GraphModel.INPUT, tuple(model.input_shape))]]
    for s in range(1, S):
        names: List[Tuple[str, Tuple[int, ...]]] = []
        if any(stage_of[c] >= s
               for c in consumers.get(GraphModel.INPUT, ())):
            names.append((GraphModel.INPUT, tuple(model.input_shape)))
        for name in model._order:
            if stage_of[name] >= s:
                continue
            if any(stage_of[c] >= s for c in consumers.get(name, ())):
                names.append((name, tuple(model.nodes[name].out_shape)))
        B.append(names)
    assert model.output is not None
    out_spec = [(model.output, tuple(model.nodes[model.output].out_shape))]
    return B, out_spec


def _specs_elems(specs: Sequence[Tuple[str, Tuple[int, ...]]]) -> int:
    return int(sum(int(np.prod(shape)) for _, shape in specs))


def _pack(acts: Dict[str, jax.Array],
          specs: Sequence[Tuple[str, Tuple[int, ...]]],
          flat: int) -> jax.Array:
    mb = next(iter(acts.values())).shape[0]
    parts = [acts[name].reshape(mb, -1).astype(jnp.float32)
             for name, _ in specs]
    buf = jnp.concatenate(parts, axis=1)
    if buf.shape[1] < flat:
        buf = jnp.pad(buf, ((0, 0), (0, flat - buf.shape[1])))
    return buf


def _unpack(buf: jax.Array,
            specs: Sequence[Tuple[str, Tuple[int, ...]]]
            ) -> Dict[str, jax.Array]:
    out: Dict[str, jax.Array] = {}
    off = 0
    for name, shape in specs:
        n = int(np.prod(shape))
        out[name] = buf[:, off:off + n].reshape((buf.shape[0],)
                                                + tuple(shape))
        off += n
    return out


def _flatten_stage_params(params: Params, layer_names: Sequence[str]):
    """One f32 vector per stage + the layout to rebuild the subtree inside
    a traced branch (uniform with the LM stacked blocks for streaming)."""
    sub = {n: params[n] for n in layer_names if n in params and params[n]}
    leaves, treedef = jax.tree.flatten(sub)
    layout = [(tuple(np.shape(l)), jnp.asarray(l).dtype) for l in leaves]
    if leaves:
        flat = np.concatenate([np.asarray(l, np.float32).ravel()
                               for l in leaves])
    else:
        flat = np.zeros((0,), np.float32)
    return flat, treedef, layout


def _unflatten_stage_params(w: jax.Array, treedef, layout) -> Params:
    leaves, off = [], 0
    for shape, dtype in layout:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(w[off:off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def make_cnn_pipeline(model: GraphModel, plan: PlacementPlan, mesh: Mesh,
                      n_microbatches: int, stage_axis: str = "model",
                      donate: bool = True):
    """Boundary/packing metadata for lowering a CNN GraphModel + plan.

    Returns ``(B, out_spec, flat, make_branch)``: the per-stage input
    boundary specs, the packed output spec, the flat buffer width, and a
    factory ``make_branch(s, treedef, layout)`` producing stage ``s``'s
    fused callable ``branch(w_row, buf) -> buf`` (unpack boundary →
    ``apply_subset`` over the stage's layer range → pack the next
    boundary).  :class:`SpmdPipelineExecutor.for_cnn` assembles these into
    the jitted shard_map program; the branches are also used stand-alone
    by the achieved-time probes."""
    _require_unreplicated(plan)
    n_stages = mesh.shape[stage_axis]
    assert plan.n_stages == n_stages, (plan.n_stages, n_stages)
    B, out_spec = cnn_boundary_specs(model, plan)
    flat = max(max(_specs_elems(b) for b in B), _specs_elems(out_spec))
    stage_layers = plan.stage_layers

    def make_branch(s: int, treedef, layout):
        in_specs = B[s]
        nxt = B[s + 1] if s + 1 < n_stages else out_spec

        def branch(w_row: jax.Array, buf: jax.Array) -> jax.Array:
            stage_params = _unflatten_stage_params(w_row, treedef, layout)
            boundary = _unpack(buf, in_specs)
            acts = model.apply_subset(stage_params, boundary,
                                      stage_layers[s])
            avail = {**boundary, **acts}
            return _pack(avail, nxt, flat)

        return branch

    return B, out_spec, flat, make_branch


class _CnnLowering:
    """Everything the executor needs for one CNN plan on one mesh."""

    def __init__(self, model: GraphModel, params: Params,
                 plan: PlacementPlan, mesh: Mesh, n_microbatches: int,
                 stage_axis: str, donate: bool):
        self.model, self.plan, self.mesh = model, plan, mesh
        self.stage_axis, self.m = stage_axis, n_microbatches
        n_stages = plan.n_stages
        B, out_spec, flat, make_branch = make_cnn_pipeline(
            model, plan, mesh, n_microbatches, stage_axis, donate)
        self.B, self.out_spec, self.flat = B, out_spec, flat

        flats, self.branches = [], []
        for s in range(n_stages):
            w, treedef, layout = _flatten_stage_params(
                params, plan.stage_layers[s])
            flats.append(w)
            self.branches.append(make_branch(s, treedef, layout))
        wmax = max(1, max(f.size for f in flats))
        self.stacked_host = np.stack(
            [np.pad(f, (0, wmax - f.size)) for f in flats])   # (S, Wmax)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(stage_axis), P()),
                           out_specs=P(stage_axis), check_vma=False)
        def pipe(weights_sh, x_all):
            w_row = weights_sh[0]
            sid = jax.lax.axis_index(stage_axis)
            branches = self.branches

            def stage_apply(buf):
                return jax.lax.switch(sid, branches, w_row, buf)

            outputs = _gpipe_outputs(stage_apply, sid, x_all, n_stages,
                                     stage_axis)
            return outputs[None]        # last-stage-only gather

        self.pipe_jit = jax.jit(pipe,
                                donate_argnums=(1,) if donate else ())

    def pack_input(self, x: jax.Array) -> jax.Array:
        b = x.shape[0]
        mb = b // self.m
        buf = _pack({GraphModel.INPUT: x}, self.B[0], self.flat)
        return buf.reshape(self.m, mb, self.flat)

    def unpack_output(self, out_last: jax.Array, b: int) -> jax.Array:
        m, mb, _ = out_last.shape
        name, shape = self.out_spec[0]
        flat_out = out_last.reshape(m * mb, self.flat)
        n = int(np.prod(shape))
        return flat_out[:b, :n].reshape((b,) + tuple(shape))


# ---------------------------------------------------------------------------
# overlapped weight streaming
# ---------------------------------------------------------------------------
class StreamReport:
    """Timing record of one :func:`stream_stage_weights` call.

    * ``fill_s`` — wall-clock bring-up fill: transfers + compile.
    * ``blocked_s`` — the part of ``fill_s`` the host spent *waiting* on
      transfers (``block_until_ready``).  This is what overlapped issue
      eliminates: the transfers land behind the compile and the final
      drain finds them done.  The wall fill only shrinks too where
      transfers have a DMA engine of their own (real accelerators); on a
      CPU-emulated mesh host-to-device copies share the worker pool and
      memory bus with all other XLA work, so wall time is conserved and
      ``blocked_s`` is the honest overlap metric.
    """

    __slots__ = ("fill_s", "blocked_s")

    def __init__(self, fill_s: float, blocked_s: float):
        self.fill_s = fill_s
        self.blocked_s = blocked_s

    def __repr__(self):
        return (f"StreamReport(fill_s={self.fill_s:.4f}, "
                f"blocked_s={self.blocked_s:.4f})")


def stream_stage_weights(mesh: Mesh, stacked: Params,
                         stage_axis: str = "model", *,
                         overlap: bool = True,
                         compile_fn: Optional[Callable[[], Any]] = None
                         ) -> Tuple[Params, Any, StreamReport]:
    """Place per-stage weight shards on their pipeline devices.

    ``stacked`` is a pytree of host arrays with leading dimension S (the
    stage axis); each stage's slice lands on that stage's mesh devices,
    sharded ``P(stage_axis)``.

    * ``overlap=True`` — double-buffered streaming: per-stage transfers
      are *issued* asynchronously in stage order (stage k+1's copy is in
      flight while stage k's lands) and ``compile_fn`` — typically the
      pipeline's AOT compile, the bring-up work that needs only shapes —
      runs while they land.
    * ``overlap=False`` — the non-overlapped reference: each stage's
      transfer completes before the next stage's is issued, and
      ``compile_fn`` runs only after the last one landed.

    Returns ``(global_tree, compile_result, report)`` where ``report``
    is a :class:`StreamReport` (wall fill + host-blocked seconds)."""
    leaves, treedef = jax.tree.flatten(stacked)
    leaves = [np.asarray(l) for l in leaves]
    shardings = [NamedSharding(mesh, P(*([stage_axis]
                                         + [None] * (l.ndim - 1))))
                 for l in leaves]
    ax = list(mesh.axis_names).index(stage_axis)
    grid = np.moveaxis(np.asarray(mesh.devices), ax, 0)
    stage_of_dev = {d.id: s for s in range(grid.shape[0])
                    for d in grid[s].flat}
    puts = []                       # (stage, device, leaf_idx, nd_index)
    for li, (leaf, sh) in enumerate(zip(leaves, shardings)):
        for dev, index in sh.addressable_devices_indices_map(
                leaf.shape).items():
            puts.append((stage_of_dev[dev.id], dev, li, index))
    puts.sort(key=lambda r: r[0])

    shards: Dict[int, List[Any]] = {li: [] for li in range(len(leaves))}
    compiled = None
    blocked_s = 0.0
    t0 = time.perf_counter()
    if overlap:
        for _, dev, li, index in puts:
            shards[li].append(jax.device_put(leaves[li][index], dev))
        if compile_fn is not None:
            compiled = compile_fn()
        tw = time.perf_counter()
        for arrs in shards.values():
            for a in arrs:
                a.block_until_ready()
        blocked_s = time.perf_counter() - tw
    else:
        def drain(pending):
            nonlocal blocked_s
            tw = time.perf_counter()
            for a in pending:
                a.block_until_ready()
            blocked_s += time.perf_counter() - tw

        cur, pending = None, []
        for s, dev, li, index in puts:
            if cur is not None and s != cur:
                drain(pending)
                pending = []
            cur = s
            a = jax.device_put(leaves[li][index], dev)
            pending.append(a)
            shards[li].append(a)
        drain(pending)
        if compile_fn is not None:
            compiled = compile_fn()
    fill_s = time.perf_counter() - t0

    glb = [jax.make_array_from_single_device_arrays(
               leaves[li].shape, shardings[li], shards[li])
           for li in range(len(leaves))]
    return (jax.tree.unflatten(treedef, glb), compiled,
            StreamReport(fill_s, blocked_s))


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------
class SpmdPipelineExecutor:
    """Run an unreplicated PlacementPlan as a shard_map pipeline.

    Mirrors the host :class:`~repro.core.pipeline.PipelineExecutor`'s
    batch surface (``run_batch`` / ``close`` / context manager;
    ``start``/``stop`` are no-ops — there are no worker threads) and adds
    the modeled-vs-real probes the SPMD tier exists for:

    * :attr:`fill_s` / :attr:`fill_blocked_s` — bring-up fill cost
      (weight streaming + compile) and the host-blocked part of it,
      overlapped or serial per ``overlap_streaming`` (see
      :class:`StreamReport`).
    * :meth:`predicted_stage_times` — the plan's modeled per-stage times.
    * :meth:`achieved_stage_times` — each stage's fused callable timed in
      isolation on its own mesh device.
    """

    def __init__(self, *, kind: str, plan: PlacementPlan, mesh: Mesh,
                 stage_axis: str, n_microbatches: int, fill_s: float,
                 overlap_streaming: bool, run_fn: Callable,
                 probe_fns: List[Callable[[], Callable[[], Any]]],
                 fill_blocked_s: float = 0.0):
        self.kind = kind
        self.plan = plan
        self.mesh = mesh
        self.stage_axis = stage_axis
        self.n_microbatches = n_microbatches
        self.fill_s = fill_s
        self.fill_blocked_s = fill_blocked_s
        self.overlap_streaming = overlap_streaming
        self._run = run_fn
        self._probe_fns = probe_fns
        self._closed = False

    # -- construction -------------------------------------------------------
    @classmethod
    def for_model(cls, model, params, plan: PlacementPlan, **kw
                  ) -> "SpmdPipelineExecutor":
        """Dispatch on the model object: a GraphModel lowers via
        apply_subset ranges, an LM config via scan-block ranges."""
        if isinstance(model, GraphModel):
            return cls.for_cnn(model, params, plan, **kw)
        if hasattr(model, "n_layers") and hasattr(model, "family"):
            return cls.for_lm(model, params, plan, **kw)
        raise TypeError(f"cannot lower {type(model).__name__} onto the "
                        f"SPMD pipeline; pass a GraphModel or an LMConfig")

    @classmethod
    def for_cnn(cls, model: GraphModel, params: Params,
                plan: PlacementPlan, *, mesh: Optional[Mesh] = None,
                n_microbatches: int = 4, stage_axis: str = "model",
                overlap_streaming: bool = True, donate: bool = True,
                batch_size: Optional[int] = None) -> "SpmdPipelineExecutor":
        _require_unreplicated(plan)
        if mesh is None:
            mesh = default_stage_mesh(plan.n_stages, stage_axis)
        low = _CnnLowering(model, params, plan, mesh, n_microbatches,
                           stage_axis, donate)
        m = n_microbatches

        compile_fn, aot_shape = None, None
        if batch_size is not None:
            bp0 = -(-batch_size // m) * m
            aot_shape = (m, bp0 // m, low.flat)
            x_struct = jax.ShapeDtypeStruct(
                aot_shape, jnp.float32,
                sharding=NamedSharding(mesh, P()))
            w_struct = jax.ShapeDtypeStruct(
                low.stacked_host.shape, jnp.float32,
                sharding=NamedSharding(mesh, P(stage_axis)))
            compile_fn = lambda: low.pipe_jit.lower(
                w_struct, x_struct).compile()
        weights, compiled, stream = stream_stage_weights(
            mesh, low.stacked_host, stage_axis,
            overlap=overlap_streaming, compile_fn=compile_fn)
        repl = NamedSharding(mesh, P())

        def run(x: jax.Array) -> jax.Array:
            b = x.shape[0]
            bp = -(-b // m) * m
            if bp != b:
                pad = jnp.broadcast_to(x[:1], (bp - b,) + x.shape[1:])
                x = jnp.concatenate([x, pad], axis=0)
            x_all = jax.device_put(
                low.pack_input(jnp.asarray(x, jnp.float32)), repl)
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=_DONATION_NOISE)
                if compiled is not None and x_all.shape == aot_shape:
                    out = compiled(weights, x_all)
                else:
                    out = low.pipe_jit(weights, x_all)
            return low.unpack_output(
                jax.device_get(_last_stage_block(out)), b)

        devs = _stage_devices(mesh, stage_axis)
        mb_probe = max(1, (batch_size or m) // m)

        def make_probe(s):
            def build():
                w_row = jax.device_put(low.stacked_host[s], devs[s])
                buf = jax.device_put(
                    np.zeros((mb_probe, low.flat), np.float32), devs[s])
                fn = jax.jit(low.branches[s])

                def probe():
                    return fn(w_row, buf).block_until_ready()

                return probe

            return build

        return cls(kind="cnn", plan=plan, mesh=mesh, stage_axis=stage_axis,
                   n_microbatches=m, fill_s=stream.fill_s,
                   fill_blocked_s=stream.blocked_s,
                   overlap_streaming=overlap_streaming, run_fn=run,
                   probe_fns=[make_probe(s) for s in range(plan.n_stages)])

    @classmethod
    def for_lm(cls, cfg, params: Params, plan: PlacementPlan, *,
               mesh: Optional[Mesh] = None, n_microbatches: int = 4,
               stage_axis: str = "model", overlap_streaming: bool = True,
               donate: bool = True, batch_size: Optional[int] = None,
               seq_len: Optional[int] = None) -> "SpmdPipelineExecutor":
        from ..models import lm
        _require_unreplicated(plan)
        if cfg.family not in ("dense", "moe"):
            raise ValueError(f"SPMD LM executor supports the dense/moe "
                             f"scan-block families, not {cfg.family!r}")
        if mesh is None:
            mesh = default_stage_mesh(plan.n_stages, stage_axis)
        n_stages = plan.n_stages
        counts = stage_block_counts(plan, cfg.n_layers)
        m = n_microbatches
        apply_builder = _lm_stage_apply_builder(cfg, counts)

        stacked_dev, _ = build_stage_blocks(params["blocks"], counts)
        stacked_host = jax.tree.map(np.asarray, stacked_dev)
        rest = {k: v for k, v in params.items() if k != "blocks"}

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P(stage_axis), P(), P()),
                           out_specs=P(stage_axis), check_vma=False)
        def pipe(blocks_sh, x_all, positions):
            blocks_l = jax.tree.map(lambda a: a[0], blocks_sh)
            sid = jax.lax.axis_index(stage_axis)
            stage_apply = apply_builder(blocks_l, positions, sid)
            outputs = _gpipe_outputs(stage_apply, sid, x_all, n_stages,
                                     stage_axis)
            return outputs[None]

        pipe_jit = jax.jit(pipe, donate_argnums=(1,) if donate else ())
        embed_jit = jax.jit(
            lambda p, tok: lm.embed_tokens(cfg, p, tok))
        unembed_jit = jax.jit(
            lambda p, h: lm.unembed(cfg, p, h))

        compile_fn, aot_shape = None, None
        if batch_size is not None and seq_len is not None:
            bp0 = -(-batch_size // m) * m
            aot_shape = (m, bp0 // m, seq_len, cfg.d_model)
            x_struct = jax.ShapeDtypeStruct(
                aot_shape, jnp.float32,
                sharding=NamedSharding(mesh, P()))
            pos_struct = jax.ShapeDtypeStruct((1, seq_len), jnp.int32,
                                              sharding=NamedSharding(
                                                  mesh, P()))
            b_structs = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=NamedSharding(
                        mesh, P(*([stage_axis]
                                  + [None] * (a.ndim - 1))))),
                stacked_host)
            compile_fn = lambda: pipe_jit.lower(
                b_structs, x_struct, pos_struct).compile()
        blocks_glb, compiled, stream = stream_stage_weights(
            mesh, stacked_host, stage_axis,
            overlap=overlap_streaming, compile_fn=compile_fn)
        repl = NamedSharding(mesh, P())

        def run(tokens: jax.Array) -> jax.Array:
            b = tokens.shape[0]
            bp = -(-b // m) * m
            if bp != b:
                pad = jnp.broadcast_to(tokens[:1],
                                       (bp - b,) + tokens.shape[1:])
                tokens = jnp.concatenate([tokens, pad], axis=0)
            x = embed_jit(rest, tokens)
            _, s, d = x.shape
            positions = jax.device_put(jnp.arange(s)[None, :], repl)
            x_mb = jax.device_put(
                jnp.asarray(x, jnp.float32).reshape(m, bp // m, s, d),
                repl)
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=_DONATION_NOISE)
                if compiled is not None and x_mb.shape == aot_shape:
                    out = compiled(blocks_glb, x_mb, positions)
                else:
                    out = pipe_jit(blocks_glb, x_mb, positions)
            h = jax.device_get(_last_stage_block(out)).reshape(bp, s, d)
            return unembed_jit(rest, jnp.asarray(h))[:b]

        devs = _stage_devices(mesh, stage_axis)
        mb_probe = max(1, (batch_size or m) // m)
        probe_seq = seq_len or 16

        def make_probe(s):
            def build():
                c = counts[s]
                blocks_s = jax.tree.map(
                    lambda a: jax.device_put(a[s, :max(c, 1)], devs[s]),
                    stacked_host)
                x0 = jax.device_put(
                    np.zeros((mb_probe, probe_seq, cfg.d_model),
                             np.float32), devs[s])
                positions = jax.device_put(
                    np.arange(probe_seq, dtype=np.int32)[None, :], devs[s])
                fn = lm._block_fn(cfg)

                @jax.jit
                def stage(blocks_s, x, positions):
                    if c == 0:
                        return x

                    def body(x, bp):
                        return fn(x, bp, positions), None

                    x, _ = jax.lax.scan(body, x, blocks_s)
                    return x

                def probe():
                    return stage(blocks_s, x0,
                                 positions).block_until_ready()

                return probe

            return build

        return cls(kind="lm", plan=plan, mesh=mesh, stage_axis=stage_axis,
                   n_microbatches=m, fill_s=stream.fill_s,
                   fill_blocked_s=stream.blocked_s,
                   overlap_streaming=overlap_streaming, run_fn=run,
                   probe_fns=[make_probe(s) for s in range(n_stages)])

    # -- execution ----------------------------------------------------------
    def __call__(self, batch: jax.Array) -> jax.Array:
        if self._closed:
            raise RuntimeError("executor is closed")
        return self._run(batch)

    def run_batch(self, items: Sequence[Any]) -> Tuple[List[Any], Dict]:
        """Host-executor-shaped batch entry: a list of unbatched items in,
        a list of outputs + a stats record out."""
        x = jnp.stack([jnp.asarray(i) for i in items])
        t0 = time.perf_counter()
        out = self(x)
        dt = time.perf_counter() - t0
        stats = {"batch_s": dt, "items_per_s": len(items) / dt,
                 "fill_s": self.fill_s,
                 "fill_blocked_s": self.fill_blocked_s,
                 "n_microbatches": self.n_microbatches}
        return [out[i] for i in range(len(items))], stats

    # -- modeled-vs-real probes ---------------------------------------------
    def predicted_stage_times(self) -> List[Optional[float]]:
        """The plan's modeled per-stage seconds (the placement DP's view)."""
        return list(self.plan.stage_times_s)

    def achieved_stage_times(self, reps: int = 5, warmup: int = 2
                             ) -> List[float]:
        """Each stage's fused callable timed in isolation on its own mesh
        device (median of ``reps``): the 'achieved' column of the
        modeled-vs-real loop."""
        times = []
        for build in self._probe_fns:
            probe = build()
            for _ in range(warmup):
                probe()
            samples = []
            for _ in range(reps):
                t0 = time.perf_counter()
                probe()
                samples.append(time.perf_counter() - t0)
            times.append(float(np.median(samples)))
        return times

    # -- lifecycle (host-executor parity) ------------------------------------
    def start(self) -> "SpmdPipelineExecutor":
        return self          # no worker threads to start

    def stop(self) -> None:
        self.close()

    def close(self) -> None:
        self._closed = True

    def __enter__(self) -> "SpmdPipelineExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
