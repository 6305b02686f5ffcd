"""int8 x int8 -> int32 tiled matmul — the Edge TPU systolic-array analogue.

The Edge TPU performs all inference as int8 MACs on a 64x64 systolic array
(paper §2.1).  On a TPU v5e the equivalent compute unit is the 128x128 MXU;
this kernel expresses the quantized matmul with MXU-aligned tiles:

* grid (M/bm, N/bn, K/bk); K is the innermost (sequential) dimension,
* x tile (bm, bk) int8 and w tile (bk, bn) int8 live in VMEM,
* accumulation in an int32 VMEM scratch across the K loop
  (zeroed at k==0, flushed to the output at k==nk-1),
* per-tensor scales are folded in by the ops.py wrapper (dequantize).

Block defaults (128, 128, 128): one MXU-shaped tile per step; VMEM working
set = bm*bk + bk*bn (int8) + bm*bn*4 (int32 acc) ~= 96 KiB, far below the
~16 MiB/core VMEM budget so the pipeline can double-buffer HBM streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (128, 128, 128)


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands straight into the MXU, int32 accumulation (Mosaic
    # refuses an int32 x int32 matmul)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


def matmul_qi8(x: jax.Array, w: jax.Array,
               block=DEFAULT_BLOCK, interpret: bool = False) -> jax.Array:
    """x: (M, K) int8; w: (K, N) int8 -> (M, N) int32."""
    assert x.dtype == jnp.int8 and w.dtype == jnp.int8
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm, bn, bk = block
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"shape {(m, k, n)} not divisible by block {block}"
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w)
