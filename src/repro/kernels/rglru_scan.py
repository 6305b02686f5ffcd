"""Blocked RG-LRU linear recurrence (recurrentgemma temporal core).

    h_t = a_t * h_{t-1} + g_t        (diagonal, per channel)

The sequence axis is cut into chunks; the grid's sequential innermost
dimension walks the chunks in order while the carry ``h`` persists in fp32
VMEM scratch.  Within a chunk the recurrence runs as an unrolled VPU loop
over time steps — each step is a fused multiply-add over the (B, R) lane
tile, which is exactly how the TPU's vector unit wants this memory-bound
recurrence (contrast the GPU formulation: a warp-parallel Blelloch scan;
on TPU the sequential-grid + VMEM-carry shape avoids cross-core shuffles
entirely — see DESIGN.md hardware-adaptation notes).

Inputs a, g: (B, S, R) (decay and gated input, precomputed pointwise);
h0: (B, R) fp32.  Outputs: hidden sequence (B, S, R) + final carry.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, g_ref, h0_ref, y_ref, hout_ref, h_ref,
            *, chunk: int, n_chunks: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    # time sits on the sublane axis: load and store whole 8-row groups at
    # aligned offsets (Mosaic refuses a dynamic single-row index there) and
    # unroll the recurrence over the rows of a group
    rows = math.gcd(chunk, 8)

    def step(i, h):
        t = pl.ds(pl.multiple_of(i * rows, rows), rows)
        a = a_ref[:, t, :].astype(jnp.float32)
        g = g_ref[:, t, :].astype(jnp.float32)
        ys = []
        for j in range(rows):
            h = a[:, j, :] * h + g[:, j, :]
            ys.append(h)
        y_ref[:, t, :] = jnp.stack(ys, axis=1).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk // rows, step, h_ref[...])
    h_ref[...] = h

    @pl.when(pl.program_id(0) == n_chunks - 1)
    def _flush():
        hout_ref[...] = h


def rglru_scan(a: jax.Array, g: jax.Array, h0: jax.Array,
               chunk: int = 256, interpret: bool = False):
    """Returns (y, h_last).  a/g: (B, S, R); h0: (B, R)."""
    b, s, r = a.shape
    assert g.shape == (b, s, r) and h0.shape == (b, r)
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((b, chunk, r), lambda c: (0, c, 0)),
            pl.BlockSpec((b, chunk, r), lambda c: (0, c, 0)),
            pl.BlockSpec((b, r), lambda c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b, chunk, r), lambda c: (0, c, 0)),
            pl.BlockSpec((b, r), lambda c: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, r), a.dtype),
            jax.ShapeDtypeStruct((b, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, r), jnp.float32)],
        interpret=interpret,
    )(a, g, h0)
    return y, h_last
