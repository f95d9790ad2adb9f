"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

One grid step processes one (batch, head, chunk) tile:

  * intra-chunk: the *dual quadratic form* — three MXU matmuls
    ``(C·Bᵀ ⊙ L) · X`` with the decay mask ``L = exp(segsum(Δt·A))``;
  * inter-chunk: the running ``[P, N]`` SSD state is carried in VMEM
    scratch across the (innermost, sequential) chunk grid dimension and
    reset at chunk 0 — no HBM round-trip for the recurrence.

Inputs are pre-scaled in ``ops.py`` (``xdt = x·Δt``, ``da = Δt·A``) so the
kernel sees only matmul-shaped work. Tiles: chunk Q=256 (rows), headdim
P=64 and state N=128 (lanes) — all MXU/VREG aligned for v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _ssd_kernel(
    xdt_ref,    # [1, 1, Q, P]  x * dt        (f32)
    da_ref,     # [1, 1, 1, Q]  dt * A        (f32, negative)
    b_ref,      # [1, 1, Q, N]
    c_ref,      # [1, 1, Q, N]
    y_ref,      # [1, 1, Q, P]  output
    state_ref,  # scratch [P, N] f32 — carried across chunks
    *,
    q_len: int,
):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0, 0]                       # [Q, P]
    da = da_ref[0, 0]                         # [1, Q]
    b = b_ref[0, 0].astype(jnp.float32)       # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)       # [Q, N]

    # Inclusive prefix sum of da as masked reductions over the chunk's
    # triangles (Mosaic has no cumsum). Both layouts are needed: a column
    # [Q, 1] (lane reduction) and a row [1, Q] (sublane reduction of da
    # moved to a column through the diagonal).
    rows = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q_len, q_len), 1)
    lower = rows >= cols
    da_sq = jnp.broadcast_to(da, (q_len, q_len))            # [l, s] = da[s]
    cum = jnp.sum(jnp.where(lower, da_sq, 0.0), axis=1, keepdims=True)  # [Q, 1]
    da_col = jnp.sum(jnp.where(rows == cols, da_sq, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(
        jnp.where(rows <= cols, jnp.broadcast_to(da_col, (q_len, q_len)), 0.0),
        axis=0, keepdims=True,
    )                                          # [1, Q]
    total = jnp.sum(da, axis=1, keepdims=True)  # [1, 1]
    # Decay mask L[l, s] = exp(cum[l] - cum[s]) for l >= s.
    l_mat = jnp.exp(jnp.where(lower, cum - cum_row, NEG_INF))

    # Intra-chunk: (C Bᵀ ⊙ L) X.
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # [Q, Q]
    y_intra = jax.lax.dot_general(
        cb * l_mat, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [Q, P]

    # Inter-chunk: contribution of the carried state, decayed to each row.
    state = state_ref[...]                     # [P, N]
    c_scaled = c * jnp.exp(cum)                # [Q, N]
    y_inter = jax.lax.dot_general(
        c_scaled, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # [Q, P]

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # State update: decay to chunk end, add this chunk's contribution.
    xd = xdt * jnp.exp(total - cum)            # [Q, P]
    s_c = jax.lax.dot_general(
        xd, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # [P, N]
    state_ref[...] = state * jnp.exp(total) + s_c


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bhsd(
    xdt: jax.Array,   # [B, H, S, P]  (x * dt, f32)
    da: jax.Array,    # [B, H, 1, S]  (dt * A, f32)
    b_mat: jax.Array, # [B, G, S, N]
    c_mat: jax.Array, # [B, G, S, N]
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bsz, h, s, p = xdt.shape
    g, n = b_mat.shape[1], b_mat.shape[3]
    hpg = h // g
    if s % chunk != 0:
        pad = chunk - s % chunk
        xdt = jnp.pad(xdt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        da = jnp.pad(da, ((0, 0), (0, 0), (0, 0), (0, pad)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, 0), (0, pad), (0, 0)))
    s_pad = xdt.shape[2]
    nc = s_pad // chunk

    grid = (bsz, h, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, q_len=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_ // hpg, c_, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda b_, h_, c_: (b_, h_ // hpg, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s_pad, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xdt, da, b_mat, c_mat)
    return out[:, :, :s, :]
