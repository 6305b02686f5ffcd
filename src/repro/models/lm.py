"""LM architecture family: config, parameter init, and forward passes.

One config dataclass covers the 10 assigned architectures; ``family``
dispatches to the dense/MoE path here, or to the hybrid (rglru.py), ssm
(rwkv6.py) and enc-dec (whisper.py) modules.

Implementation notes (dry-run driven):
* homogeneous blocks are **stacked** along a leading layer axis and executed
  with ``jax.lax.scan`` — keeps HLO size O(1) in depth so an 80-layer model
  compiles quickly even on the CPU host that carries 512 fake devices;
* MoE uses GShard-style dense dispatch (one-hot capacity routing) — no ragged
  ops, shardable over the expert axis;
* attention dispatches to full/chunked/decode variants (attention.py);
* params are bf16; losses/softmax in fp32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A


Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    family: str                   # dense | moe | vlm | hybrid | ssm | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    mlp_kind: str = "swiglu"      # swiglu | geglu | relu2 | gelu
    rope_theta: float = 1_000_000.0
    tie_embeddings: bool = False
    norm: str = "rms"             # rms | layer
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_group: int = 512          # routing group size (dispatch-cost bound)
    # --- hybrid (recurrentgemma / griffin) ---
    attn_every: int = 0           # every k-th layer (k=3: rec,rec,attn)
    local_window: int = 2048
    conv_width: int = 4
    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    n_frames: int = 1500
    # --- vlm (qwen2-vl) ---
    mrope_sections: Tuple[int, ...] = ()
    n_patches: int = 0
    # --- rwkv ---
    rwkv_head_dim: int = 64
    # --- numerics / memory ---
    dtype: Any = jnp.bfloat16
    remat: bool = True
    q_chunk: int = 1024           # chunked-attention query block (long prefill)
    seq_shard_acts: bool = True   # Megatron-SP activation sharding at block
                                  # boundaries (see seq_shard_constraint)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.hd

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.hd

    def validate(self) -> "LMConfig":
        assert self.n_heads % max(1, self.n_kv_heads) == 0, "GQA group size"
        if self.family == "moe":
            assert self.n_experts > 0 and 0 < self.top_k <= self.n_experts
        if self.family == "vlm":
            assert self.mrope_sections and sum(self.mrope_sections) == self.hd // 2
        if self.family == "hybrid":
            assert self.attn_every >= 2
        return self


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------
def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


def _stack_init(key, n: int, fn):
    """Initialize n copies of a param tree and stack along axis 0."""
    keys = jax.random.split(key, n)
    trees = [fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs, 0), *trees)


def init_attn_params(cfg: LMConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 8)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p: Params = {
        "wq": _dense_init(ks[0], (d, qd), dtype),
        "wk": _dense_init(ks[1], (d, kvd), dtype),
        "wv": _dense_init(ks[2], (d, kvd), dtype),
        "wo": _dense_init(ks[3], (qd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dtype)
        p["bk"] = jnp.zeros((kvd,), dtype)
        p["bv"] = jnp.zeros((kvd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.hd,), dtype)
        p["k_norm"] = jnp.zeros((cfg.hd,), dtype)
    return p


def init_mlp_params(cfg: LMConfig, key, dtype) -> Params:
    ks = jax.random.split(key, 4)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.family == "moe":
        e = cfg.n_experts
        return {
            "router": _dense_init(ks[0], (d, e), jnp.float32),
            "wg": _dense_init(ks[1], (e, d, f), dtype),
            "wu": _dense_init(ks[2], (e, d, f), dtype),
            "wd": _dense_init(ks[3], (e, f, d), dtype),
        }
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {"wg": _dense_init(ks[0], (d, f), dtype),
                "wu": _dense_init(ks[1], (d, f), dtype),
                "wd": _dense_init(ks[2], (f, d), dtype)}
    return {"wu": _dense_init(ks[0], (d, f), dtype),
            "wd": _dense_init(ks[1], (f, d), dtype)}


def _norm_params(cfg: LMConfig, dtype) -> Params:
    if cfg.norm == "layer":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    return {"scale": jnp.zeros((cfg.d_model,), dtype)}


def init_block_params(cfg: LMConfig, key, dtype) -> Params:
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_params(cfg, dtype),
            "attn": init_attn_params(cfg, k1, dtype),
            "ln2": _norm_params(cfg, dtype),
            "mlp": init_mlp_params(cfg, k2, dtype)}


def init_params(cfg: LMConfig, key) -> Params:
    """Init for dense / moe / vlm families (hybrid/ssm/encdec: own modules)."""
    dtype = cfg.dtype
    k_emb, k_blocks, k_head = jax.random.split(key, 3)
    params: Params = {
        "embed": _dense_init(k_emb, (cfg.vocab, cfg.d_model), dtype, scale=0.02),
        "blocks": _stack_init(k_blocks, cfg.n_layers,
                              lambda k: init_block_params(cfg, k, dtype)),
        "final_norm": _norm_params(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = _dense_init(k_head, (cfg.d_model, cfg.vocab), dtype)
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------
def _norm(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "layer":
        return A.layer_norm(x, p["scale"], p["bias"])
    return A.rms_norm(x, p["scale"])


def _qkv(cfg: LMConfig, p: Params, x: jax.Array):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.hd)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.hd)
    if cfg.qk_norm:
        q = A.rms_norm(q, p["q_norm"])
        k = A.rms_norm(k, p["k_norm"])
    return q, k, v


def _rope_qk(cfg: LMConfig, q, k, positions):
    if cfg.family == "vlm":
        return (A.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta),
                A.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta))
    return (A.apply_rope(q, positions, cfg.rope_theta),
            A.apply_rope(k, positions, cfg.rope_theta))


def attn_block(cfg: LMConfig, p: Params, x: jax.Array, positions,
               window: Optional[int] = None) -> jax.Array:
    """Full-sequence causal attention (train / prefill)."""
    q, k, v = _qkv(cfg, p, x)
    q, k = _rope_qk(cfg, q, k, positions)
    q, k, v = attn_shard_constraints(q, k, v)
    s = x.shape[1]
    if s > cfg.q_chunk:
        out = A.chunked_attention(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                                  window=window)
    else:
        out = A.full_attention(q, k, v, causal=True, window=window)
    b = x.shape[0]
    return out.reshape(b, s, cfg.q_dim) @ p["wo"]


def attn_block_decode(cfg: LMConfig, p: Params, x: jax.Array,
                      k_cache: jax.Array, v_cache: jax.Array,
                      cache_len: jax.Array, positions,
                      window: Optional[int] = None):
    """Single-token decode; returns (out, new_k_cache, new_v_cache).

    Caches are (B, T, Hkv, D).  For windowed layers T may be the window size
    and slots are addressed modulo T (ring buffer).
    """
    b = x.shape[0]
    q, k, v = _qkv(cfg, p, x)                       # S == 1
    q, k = _rope_qk(cfg, q, k, positions)
    t = k_cache.shape[1]
    slot = jnp.mod(cache_len - 1, t)
    from ..launch import variants
    if cfg.family in ("dense", "moe", "vlm") and not variants.on("cache_hd"):
        # DEFAULT: sequence-sharded cache (flash-decoding; 2.9x decode win,
        # EXPERIMENTS.md §Perf).  A dynamic-update-slice across the sharded
        # T axis forces a full reshard in GSPMD; the one-hot masked write
        # is pointwise over T and stays local.
        hit = (jnp.arange(t) == slot)[None, :, None, None]
        k_cache = jnp.where(hit, k.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(hit, v.astype(v_cache.dtype), v_cache)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k, (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v, (0, slot, 0, 0))
    if window is not None and t <= window:
        # ring buffer: all t slots are valid once cache_len >= t
        valid_len = jnp.minimum(cache_len, t)
        out = A.decode_attention(q, k_cache, v_cache, valid_len[None],
                                 window=None)
    else:
        out = A.decode_attention(q, k_cache, v_cache, cache_len[None],
                                 window=window)
    return out.reshape(b, 1, cfg.q_dim) @ p["wo"], k_cache, v_cache


def mlp_block(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.family == "moe":
        return moe_block(cfg, p, x)
    kind = cfg.mlp_kind
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ p["wg"]) * (x @ p["wu"])
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["wu"]))
    else:  # gelu
        h = jax.nn.gelu(x @ p["wu"])
    return h @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (GShard dense dispatch; EP-shardable over the expert axis)
# ---------------------------------------------------------------------------
def moe_block(cfg: LMConfig, p: Params, x: jax.Array) -> jax.Array:
    """Grouped GShard dispatch: tokens are routed within contiguous groups
    of ``moe_group`` tokens, keeping the one-hot dispatch einsum cost
    O(group * E * cap * D) — linear in sequence length (the ungrouped
    dispatch is quadratic and would dominate FLOPs at 32k prefill)."""
    bb, ss, d = x.shape
    g = min(cfg.moe_group, ss)
    assert ss % g == 0, (ss, g)
    x = x.reshape(bb * (ss // g), g, d)
    b, s, _ = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = min(int(cfg.capacity_factor * s * k / e) + 1, s)

    logits = (x.astype(jnp.float32) @ p["router"])          # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)            # (B,S,k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    # one-hot dispatch with capacity: position of each token within its expert
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (B,S,k,E)
    # fold the top-k choices into a single (B,S,E) assignment weight
    combine_w = jnp.einsum("bske,bsk->bse", onehot, gate_vals)
    assign = jnp.max(onehot, axis=2)                         # (B,S,E) 0/1
    pos_in_expert = jnp.cumsum(assign, axis=1) * assign - 1  # (B,S,E)
    keep = (pos_in_expert >= 0) & (pos_in_expert < cap)
    pos_clamped = jnp.clip(pos_in_expert, 0, cap - 1).astype(jnp.int32)
    slot_oh = jax.nn.one_hot(pos_clamped, cap, dtype=jnp.float32)  # (B,S,E,C)
    dispatch = slot_oh * keep[..., None]                     # (B,S,E,C)
    combine = dispatch * combine_w[..., None]                # (B,S,E,C)

    xt = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(jnp.float32))
    xt = xt.astype(x.dtype)                                  # (E,B,C,D)
    h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xt, p["wg"])) * \
        jnp.einsum("ebcd,edf->ebcf", xt, p["wu"])
    y = jnp.einsum("ebcf,efd->ebcd", h, p["wd"])             # (E,B,C,D)
    out = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), y)
    return out.reshape(bb, ss, d)


def moe_aux_loss(cfg: LMConfig, logits: jax.Array, gate_idx: jax.Array) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    e = cfg.n_experts
    probs = jax.nn.softmax(logits, axis=-1)
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx.reshape(-1), e, dtype=jnp.float32),
                  axis=0)
    return e * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Whole-model forward (dense / moe / vlm)
# ---------------------------------------------------------------------------
def _mesh_info():
    """Mesh names/sizes at trace time (launchers register via
    mesh_context; get_abstract_mesh is empty under a plain `with mesh:`)."""
    from ..launch.mesh import current_mesh_info
    info = current_mesh_info()
    if info is not None:
        return info
    am = jax.sharding.get_abstract_mesh()
    names = tuple(am.axis_names)
    if not names:
        return None
    return names, dict(zip(names, am.axis_sizes))


def attn_shard_constraints(q: jax.Array, k: jax.Array, v: jax.Array):
    """Explicit attention shardings (perf knob ``attn_shard``): q sharded
    over heads when divisible, k/v replicated over model.  Stops GSPMD from
    propagating the kv-feature sharding into the score einsums (which
    otherwise psums fp32 score tensors per layer)."""
    from ..launch import variants
    if not variants.on("attn_shard"):
        return q, k, v
    info = _mesh_info()
    if info is None or "model" not in info[0]:
        return q, k, v
    names, sizes = info
    daxes = tuple(a for a in ("pod", "data") if a in names)
    dsize = 1
    for a in daxes:
        dsize *= sizes[a]
    bspec = (daxes if len(daxes) > 1 else daxes[0]) \
        if (daxes and q.shape[0] % dsize == 0) else None
    from jax.sharding import PartitionSpec as P
    try:
        hq = q.shape[2]
        qspec = P(bspec, None, "model" if hq % sizes["model"] == 0 else None,
                  None)
        q = jax.lax.with_sharding_constraint(q, qspec)
        kvspec = P(bspec, None, None, None)
        k = jax.lax.with_sharding_constraint(k, kvspec)
        v = jax.lax.with_sharding_constraint(v, kvspec)
    except Exception:
        pass
    return q, k, v


def weight_gather_constraint(bp: Params) -> Params:
    """FSDP weight-gathering (the MaxText pattern): inside the layer scan,
    constrain every block tensor to its TP-only spec.  Without this, GSPMD
    may instead run matmuls with the *data-sharded weight dim as a split
    contraction* and all-reduce the activations — measured at 11.6 TiB of
    all-reduce per step on qwen2.5-14b train (EXPERIMENTS.md §Perf).  With
    it, each layer all-gathers its (small) weight slice once per pass.
    No-op when params are not data-sharded or no mesh is active.
    Disable with the ``no_wgather`` variant knob."""
    from ..launch import variants
    if variants.on("no_wgather"):
        return bp
    info = _mesh_info()
    if info is None or "model" not in info[0]:
        return bp
    names_axes, sizes = info
    msize = sizes["model"]
    from ..launch.sharding import _path_names, _spec_for_param

    def one(path, leaf):
        names = _path_names(path)
        sp = _spec_for_param(names, leaf.shape, msize, True)
        try:
            return jax.lax.with_sharding_constraint(leaf, sp)
        except Exception:
            return leaf

    return jax.tree_util.tree_map_with_path(one, bp)


def seq_shard_constraint(x: jax.Array) -> jax.Array:
    """Megatron-SP-style activation sharding at block boundaries: shard the
    carry (B, S, D) as (data, model, None) when a mesh is active and the
    dims divide.  The remat-saved residual stack inherits this sharding —
    for an 80L x 8192d model that is a 16x reduction of the dominant
    activation buffer (85 GiB -> 5.3 GiB/device); XLA inserts the per-layer
    all-gather/reduce-scatter pair this implies.  No-op outside a mesh."""
    from ..launch import variants
    if variants.on("no_seqshard"):
        return x
    info = _mesh_info()
    if info is None:
        return x
    names, sizes = info
    if "model" not in names or x.ndim != 3:
        return x
    daxes = tuple(a for a in ("pod", "data") if a in names)
    dsize = 1
    for a in daxes:
        dsize *= sizes[a]
    spec_b = None
    if daxes and x.shape[0] % dsize == 0:
        spec_b = daxes if len(daxes) > 1 else daxes[0]
    if x.shape[1] % sizes["model"] != 0:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            x, P(spec_b, "model", None))
    except Exception:
        return x


def _block_fn(cfg: LMConfig, window: Optional[int] = None):
    def fn(x, bp, positions):
        bp = weight_gather_constraint(bp)
        x = x + attn_block(cfg, bp["attn"], _norm(cfg, bp["ln1"], x),
                           positions, window=window)
        x = x + mlp_block(cfg, bp["mlp"], _norm(cfg, bp["ln2"], x))
        return x
    return fn


def embed_tokens(cfg: LMConfig, params: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(cfg: LMConfig, params: Params, x: jax.Array) -> jax.Array:
    x = _norm(cfg, params["final_norm"], x)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return (x @ w).astype(jnp.float32)


def _default_positions(cfg: LMConfig, batch: Dict[str, jax.Array],
                       seq: int) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    pos = jnp.arange(seq)[None, :]
    if cfg.family == "vlm":
        return jnp.broadcast_to(pos[None], (3,) + (batch["tokens"].shape[0], seq))
    return pos


def forward(cfg: LMConfig, params: Params, batch: Dict[str, jax.Array],
            last_token_only: bool = False) -> jax.Array:
    """Full-sequence forward -> fp32 logits (B, S, V).

    batch["tokens"]: (B, S) int32.  For vlm, batch["embeds"] (B, P, D) is
    prepended (stub vision frontend) and positions are (3, B, P+S).
    ``last_token_only``: unembed only the final position (prefill serving
    path — avoids materializing (B, S, V) logits).
    """
    x = embed_tokens(cfg, params, batch["tokens"])
    if cfg.family == "vlm" and "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    seq = x.shape[1]
    positions = _default_positions(cfg, batch, seq)
    fn = _block_fn(cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn)

    def body(x, bp):
        # barrier between the remat-saved carry and its f32 consumers:
        # without it XLA convert-motion rewrites the stacked bf16 residual
        # buffer updates in f32 (2x the activation stack).
        if cfg.seq_shard_acts:
            x = seq_shard_constraint(x)
        return fn(jax.lax.optimization_barrier(x), bp, positions), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    if last_token_only:
        x = x[:, -1:]
    return unembed(cfg, params, x)


def forward_hidden(cfg: LMConfig, params: Params,
                   batch: Dict[str, jax.Array]) -> jax.Array:
    """Post-block hidden states (B, S, D) — pair with :func:`unembed`
    for chunked (memory-bounded) loss computation."""
    x = embed_tokens(cfg, params, batch["tokens"])
    if cfg.family == "vlm" and "embeds" in batch:
        x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
    seq = x.shape[1]
    positions = _default_positions(cfg, batch, seq)
    fn = _block_fn(cfg)
    if cfg.remat:
        fn = jax.checkpoint(fn)

    def body(x, bp):
        if cfg.seq_shard_acts:
            x = seq_shard_constraint(x)
        return fn(jax.lax.optimization_barrier(x), bp, positions), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return x


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------
def init_cache(cfg: LMConfig, batch: int, max_len: int) -> Params:
    t = max_len
    shape = (cfg.n_layers, batch, t, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "len": jnp.zeros((), jnp.int32)}


def forward_decode(cfg: LMConfig, params: Params, tokens: jax.Array,
                   cache: Params) -> Tuple[jax.Array, Params]:
    """One decode step: tokens (B, 1) -> logits (B, 1, V), updated cache."""
    x = embed_tokens(cfg, params, tokens)
    new_len = cache["len"] + 1
    pos = (new_len - 1)[None, None]                     # (1,1) broadcast
    if cfg.family == "vlm":
        pos = jnp.broadcast_to(pos[None], (3, 1, 1))

    def body(x, xs):
        bp, kc, vc = xs
        # barrier: prevents CPU float-normalization from hoisting an f32
        # convert of the whole stacked cache out of the layer loop (a
        # CPU-only legalization; TPU dots consume bf16 natively)
        kc, vc = jax.lax.optimization_barrier((kc, vc))
        h = _norm(cfg, bp["ln1"], x)
        out, kc, vc = attn_block_decode(cfg, bp["attn"], h, kc, vc,
                                        new_len, pos)
        x = x + out
        x = x + mlp_block(cfg, bp["mlp"], _norm(cfg, bp["ln2"], x))
        return x, (kc, vc)

    x, (k_new, v_new) = jax.lax.scan(body, x,
                                     (params["blocks"], cache["k"], cache["v"]))
    logits = unembed(cfg, params, x)
    return logits, {"k": k_new, "v": v_new, "len": new_len}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits: jax.Array, labels: jax.Array,
            mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy in fp32; labels (B, S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
