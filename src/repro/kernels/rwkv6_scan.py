"""Blocked RWKV6 WKV recurrence (Finch time-mix core).

Per head with state S in R^(K x V):

    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t

The (K, V) state tile lives in fp32 VMEM scratch and persists across the
sequential chunk grid; within a chunk the recurrence is an unrolled loop of
rank-1 updates + (1, K) x (K, V) matvecs — MXU/VPU-friendly, no cross-core
communication (the GPU reference implementation's shared-memory tiling maps
to the VMEM-resident state here; see DESIGN.md).

Inputs r/k/v/w: (B, H, S, D) with D = head_dim (K == V == D); u: (H, D).
Outputs y: (B, H, S, D) + final state (B, H, D, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
            st_ref, *, chunk: int, n_chunks: int):
    # the state is carried transposed, T = S^T (V x K), so every per-step
    # operand stays a (1, D) row: the decay scales T's columns and the
    # rank-1 update is a contraction over a length-1 axis
    @pl.when(pl.program_id(2) == 0)
    def _init():
        st_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                     # (1, K)
    rows = math.gcd(chunk, 8)
    outer = (((0,), (0,)), ((), ()))                     # (1,V),(1,K)->(V,K)
    rowdot = (((1,), (1,)), ((), ()))                    # (1,K),(V,K)->(1,V)

    def step(i, state):
        # 8-row groups at aligned offsets (Mosaic refuses a dynamic
        # single-row index on the sublane axis), unrolled per row
        t = pl.ds(pl.multiple_of(i * rows, rows), rows)
        rs, ks, vs, ws = (ref[0, 0, t, :].astype(jnp.float32)
                          for ref in (r_ref, k_ref, v_ref, w_ref))
        ys = []
        for j in range(rows):
            r, k, v, w = (x[j:j + 1] for x in (rs, ks, vs, ws))
            kv = jax.lax.dot_general(v, k, outer,
                                     preferred_element_type=jnp.float32)
            y = jax.lax.dot_general(r, state, rowdot,
                                    preferred_element_type=jnp.float32)
            ys.append(y + jnp.sum(r * u * k, axis=1, keepdims=True) * v)
            state = state * w + kv
        y_ref[0, 0, t, :] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return state

    state = jax.lax.fori_loop(0, chunk // rows, step, st_ref[...])
    st_ref[...] = state

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _flush():
        sout_ref[0, 0] = state


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, s0: jax.Array, chunk: int = 128,
               interpret: bool = False):
    """Returns (y, s_last).  r/k/v/w: (B,H,S,D); u: (H,D); s0: (B,H,D,D)."""
    b, h, s, d = r.shape
    assert u.shape == (h, d) and s0.shape == (b, h, d, d)
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    y, s_last = pl.pallas_call(
        kernel,
        grid=(b, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, c: (bb, hh, c, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, c: (bb, hh, c, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, c: (bb, hh, c, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, c: (bb, hh, c, 0)),
            pl.BlockSpec((1, 1, d), lambda bb, hh, c: (hh, 0, 0)),
            pl.BlockSpec((1, 1, d, d), lambda bb, hh, c: (bb, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda bb, hh, c: (bb, hh, c, 0)),
            pl.BlockSpec((1, 1, d, d), lambda bb, hh, c: (bb, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u[:, None, :], jnp.swapaxes(s0, -1, -2))
    return y, jnp.swapaxes(s_last, -1, -2)
