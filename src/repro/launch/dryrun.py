"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the fake-device flag before ANY other import (jax locks the device
count on first init)::

    python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out benchmarks/artifacts
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax               # noqa: E402
import numpy as np       # noqa: E402

from repro import configs                          # noqa: E402
from repro.configs.common import SHAPES, input_specs  # noqa: E402
from repro.launch import sharding as shd           # noqa: E402
from repro.launch import steps as steps_lib        # noqa: E402
from repro.launch.hlo_analysis import analyze      # noqa: E402
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,  # noqa: E402
                               make_production_mesh, mesh_context)
from repro.models import api                       # noqa: E402
from repro.optim import AdamWConfig                # noqa: E402

HBM_PER_CHIP = 16 * 1024**3          # v5e


def _sharded_leaf_bytes(leaf, sh, mesh) -> float:
    """Per-device bytes of one array under its NamedSharding."""
    n = float(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    spec = getattr(sh, "spec", None)
    if spec is None:
        return n
    denom = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            denom *= mesh.shape[a]
    return n / denom


def analytic_state_bytes(trees_and_shardings, mesh) -> float:
    total = 0.0
    for tree, sh_tree in trees_and_shardings:
        leaves = jax.tree.leaves(tree)
        shs = jax.tree.leaves(sh_tree,
                              is_leaf=lambda x: hasattr(x, "spec"))
        for leaf, sh in zip(leaves, shs):
            total += _sharded_leaf_bytes(leaf, sh, mesh)
    return total


def analytic_activation_bytes(cfg, spec, mesh) -> float:
    """Per-device activation working set (documented model, see
    EXPERIMENTS.md §Dry-run): remat residual stack + transients + logits
    shard + attention score chunk."""
    from repro.launch.mesh import data_parallel_size, model_axis_size
    dp = data_parallel_size(mesh)
    tp = model_axis_size(mesh)
    b = spec.global_batch
    b_loc = b / dp if b % dp == 0 else b
    s = spec.seq_len if spec.kind != "decode" else 1
    d = cfg.d_model
    v_loc = cfg.vocab / tp if cfg.vocab % tp == 0 else cfg.vocab
    h_loc = max(1, cfg.n_heads / tp)
    act = 0.0
    f_loc = cfg.d_ff / tp if cfg.d_ff % tp == 0 else cfg.d_ff
    if cfg.family == "moe":
        e_loc = max(1, cfg.n_experts / tp)
        f_loc = f_loc * e_loc * 3          # dispatch keeps E_loc expert bufs
    if spec.kind == "train":
        # remat carry stack is sequence-sharded over `model` when divisible
        s_stack = s / tp if (cfg.seq_shard_acts and s % tp == 0) else s
        act += cfg.n_layers * b_loc * s_stack * d * 2  # remat carry stack
        # in-block transients: 2 bf16 full-seq residual copies + gated MLP
        # hidden shards + 2 fp32 seq-sharded norm buffers
        act += 2 * b_loc * s * d * 2
        act += 2 * b_loc * s * f_loc * 2
        act += 2 * b_loc * s_stack * d * 4
        act += 2 * b_loc * 512 * v_loc * 4             # chunked-loss logits
        act += 2 * b_loc * h_loc * min(s, cfg.q_chunk) * s * 4   # scores
    elif spec.kind == "prefill":
        act += 3 * b_loc * s * d * 2 + b_loc * s * f_loc * 2
        act += b_loc * h_loc * min(s, cfg.q_chunk) * s * 4
        act += b_loc * v_loc * 4                       # last-token logits
    else:
        act += 4 * b_loc * d * 4 + b_loc * v_loc * 4
    return act


def _mem_dict(mem) -> Dict[str, int]:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        try:
            out[attr] = int(getattr(mem, attr))
        except (AttributeError, TypeError):
            pass
    return out


def model_flops(arch: str, shape_name: str) -> float:
    """'Useful' FLOPs: 6*N_active*tokens (train) / 2*N_active*tokens (fwd)."""
    spec = SHAPES[shape_name]
    cfg = configs.get(arch).config()
    n = api.active_param_count(cfg)
    if spec.kind == "train":
        tokens = spec.global_batch * spec.seq_len
        return 6.0 * n * tokens
    if spec.kind == "prefill":
        tokens = spec.global_batch * spec.seq_len
        return 2.0 * n * tokens
    tokens = spec.global_batch * 1          # decode: one new token
    return 2.0 * n * tokens


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                verbose: bool = True) -> Dict[str, Any]:
    """Lower+compile one cell; returns the roofline record."""
    mod = configs.get(arch)
    skip = mod.SKIP_SHAPES.get(shape_name)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": 512 if multi_pod else 256,
    }
    if skip:
        rec["status"] = "skipped"
        rec["skip_reason"] = skip
        return rec

    cfg = mod.config()
    spec = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    with mesh_context(mesh):
        if spec.kind == "train":
            from repro.launch import variants
            params_s, opt_s = steps_lib.train_state_shapes(cfg)
            batch_s = input_specs(cfg, spec)
            fsdp = ("blocks" if not (variants.on("no_fsdp")
                                     or variants.on("full_fsdp"))
                    else (True if variants.on("full_fsdp") else False))
            in_sh = (shd.param_shardings(mesh, params_s, fsdp=fsdp),
                     shd.opt_state_shardings(mesh, opt_s),
                     shd.batch_shardings(mesh, batch_s))
            fn = steps_lib.make_train_step(
                cfg, AdamWConfig(),
                loss_chunk=2048 if variants.on("loss_chunk_2k") else 512)
            out_sh = (in_sh[0], in_sh[1], shd.replicated(mesh, {
                "lr": 0, "grad_norm": 0, "loss": 0}))
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch_s)
        elif spec.kind == "prefill":
            params_s = jax.eval_shape(lambda k: api.init(cfg, k),
                                      jax.ShapeDtypeStruct((2,), "uint32"))
            batch_s = input_specs(cfg, spec)
            in_sh = (shd.param_shardings(mesh, params_s),
                     shd.batch_shardings(mesh, batch_s))
            fn = steps_lib.make_prefill_step(cfg)
            jitted = jax.jit(fn, in_shardings=in_sh)
            lowered = jitted.lower(params_s, batch_s)
        else:  # decode
            from repro.launch import variants
            params_s = jax.eval_shape(lambda k: api.init(cfg, k),
                                      jax.ShapeDtypeStruct((2,), "uint32"))
            cache_s = steps_lib.cache_shapes(cfg, spec.global_batch,
                                             spec.seq_len)
            tok_s = input_specs(cfg, spec)["tokens"]
            # flash-decoding seq-sharded cache is the default for the
            # attention families (2.9x decode win); `cache_hd` reverts
            cache_mode = ("seq" if (cfg.family in ("dense", "moe", "vlm")
                                    and not variants.on("cache_hd"))
                          else "hd")
            in_sh = (shd.param_shardings(mesh, params_s),
                     shd.cache_shardings(mesh, cache_s, mode=cache_mode),
                     shd.batch_shardings(mesh, {"tokens": tok_s})["tokens"])
            fn = steps_lib.make_decode_step(cfg)
            out_sh = (None, in_sh[1])
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=(1,))
            lowered = jitted.lower(params_s, cache_s, tok_s)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = _mem_dict(compiled.memory_analysis())
    raw_cost = compiled.cost_analysis()
    totals = analyze(compiled.as_text())
    n_dev = rec["n_devices"]

    flops_dev = totals.flops
    bytes_dev = totals.hbm_bytes
    coll_dev = totals.coll_bytes
    mf = model_flops(arch, shape_name)

    compute_s = flops_dev / PEAK_FLOPS_BF16
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)

    # per-device residency: sharded state (exact) + activation model
    state_pairs = []
    if spec.kind == "train":
        state_pairs = [(params_s, in_sh[0]), (opt_s, in_sh[1])]
    elif spec.kind == "prefill":
        state_pairs = [(params_s, in_sh[0])]
    else:
        state_pairs = [(params_s, in_sh[0]), (cache_s, in_sh[1])]
    state_bytes = analytic_state_bytes(state_pairs, mesh)
    act_bytes = analytic_activation_bytes(cfg, spec, mesh)
    dev_bytes = state_bytes + act_bytes

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis_raw": mem,     # CPU backend: includes bf16->f32
                                        # legalization temps (see DESIGN.md)
        "state_bytes_per_device": state_bytes,
        "activation_bytes_per_device": act_bytes,
        "device_bytes": dev_bytes,
        "fits_hbm": bool(dev_bytes <= HBM_PER_CHIP),
        "hlo_flops_per_device": flops_dev,
        "hlo_flops_raw_cost_analysis": float(raw_cost.get("flops", 0.0)),
        "hlo_bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_breakdown": totals.coll_by_kind,
        "collective_counts": totals.coll_counts,
        "roofline": dict(terms, dominant=dominant),
        "model_flops_global": mf,
        "useful_flops_ratio": (mf / (flops_dev * n_dev)
                               if flops_dev else None),
    })
    if verbose:
        print(f"[{rec['mesh']}] {arch} x {shape_name}: "
              f"compile {t_compile:.1f}s, "
              f"{dev_bytes/2**30:.2f} GiB/dev (fits={rec['fits_hbm']}), "
              f"terms(ms): C={compute_s*1e3:.2f} M={memory_s*1e3:.2f} "
              f"X={collective_s*1e3:.2f} -> {dominant}, "
              f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for aid, sname, _skip in configs.cells(include_skipped=True):
            cells.append((aid, sname))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            try:
                rec = dryrun_cell(arch, shape, mp)
            except Exception as e:   # noqa: BLE001 — record and continue
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()}
                failures.append(tag)
                print(f"FAILED {tag}: {e}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("\nall dry-run cells green")


if __name__ == "__main__":
    main()
