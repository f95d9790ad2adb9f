"""Mamba-2 (SSD — state-space duality) mixer block.

Implements the chunked SSD algorithm (Dao & Gu, arXiv:2405.21060): the
sequence is split into chunks; within a chunk the recurrence is computed
in its *dual* quadratic-attention form (MXU-friendly matmuls), across
chunks a linear recurrence carries the [H, P, N] state. The same block
exposes a single-token :func:`ssd_step` for decode — state size is
constant in sequence length, which is what makes the ``long_500k`` shape
tractable for the ssm/hybrid archs.

Layout notes (TPU adaptation): heads H shard over the ``model`` mesh axis;
chunk size Q is the Pallas kernel's sequence tile; P (headdim) and N
(state) are 64/128 — multiples of the MXU/VREG lane width.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import _dtype, _init_linear

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_mamba(cfg, rng: jax.Array) -> Dict:
    dtype = _dtype(cfg.param_dtype)
    d, di = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_nheads
    conv_dim = di + 2 * g * n
    keys = jax.random.split(rng, 4)

    # in_proj is split into [z | xBC | dt] projections so each shards
    # cleanly over the tensor-parallel axis (the packed 2·di+2GN+H width
    # is not TP-divisible for e.g. mamba2-2.7b).
    kz, kx, kdt = jax.random.split(keys[0], 3)
    params = {
        "in_proj_z": _init_linear(kz, d, di, dtype),
        "in_proj_xbc": _init_linear(kx, d, di + 2 * g * n, dtype),
        "in_proj_dt": _init_linear(kdt, d, h, dtype),
        "conv_w": (
            jax.random.normal(keys[1], (cfg.ssm_conv, conv_dim), jnp.float32)
            * (1.0 / jnp.sqrt(jnp.float32(cfg.ssm_conv)))
        ).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(
            jnp.linspace(1.0, 16.0, h, dtype=jnp.float32)
        ).astype(jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(
            jnp.expm1(
                jnp.exp(
                    jax.random.uniform(
                        keys[2], (h,), jnp.float32,
                        minval=jnp.log(0.001), maxval=jnp.log(0.1),
                    )
                )
            )
        ).astype(jnp.float32),
        "norm_scale": jnp.ones((di,), dtype),
        "out_proj": _init_linear(keys[3], di, d, dtype),
    }
    return params


# ---------------------------------------------------------------------------
# SSD core (chunked scan) — pure jnp; the Pallas kernel mirrors the
# intra-chunk dual form.
# ---------------------------------------------------------------------------


def segsum(a: jax.Array) -> jax.Array:
    """Lower-triangular pairwise segment sums: out[..., i, j] = sum_{j<k<=i} a[k].

    a: [..., Q] → [..., Q, Q] with -inf above the diagonal.
    """
    q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), dtype=bool))
    return jnp.where(mask, diff, NEG_INF)


def ssd_chunked(
    x: jax.Array,        # [B,S,H,P]  (pre-multiplied by nothing; dt applied here)
    dt: jax.Array,       # [B,S,H]    (post-softplus, positive)
    a: jax.Array,        # [H]        (negative; A = -exp(a_log))
    b_mat: jax.Array,    # [B,S,G,N]
    c_mat: jax.Array,    # [B,S,G,N]
    chunk: int,
    initial_state: Optional[jax.Array] = None,  # [B,H,P,N]
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD scan. Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    orig_s = s
    if s % chunk != 0:
        # Pad to a chunk multiple: dt=0 on padded steps makes both the decay
        # (exp(0)=1) and the input contribution (x·dt=0) identity ops, so the
        # final state and the unpadded outputs are unaffected.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s = s + pad
    nc = s // chunk
    hpg = h // g  # heads per group

    f32 = jnp.float32
    dt = dt.astype(f32)
    da = dt * a.astype(f32)[None, None, :]                     # [B,S,H] (negative)
    xdt = (x.astype(f32) * dt[..., None])                       # [B,S,H,P]

    # Reshape into chunks.
    da_c = da.reshape(bsz, nc, chunk, h).transpose(0, 3, 1, 2)  # [B,H,C,Q]
    x_c = xdt.reshape(bsz, nc, chunk, h, p)                     # [B,C,Q,H,P]
    b_c = b_mat.astype(f32).reshape(bsz, nc, chunk, g, n)       # [B,C,Q,G,N]
    c_c = c_mat.astype(f32).reshape(bsz, nc, chunk, g, n)

    # Broadcast groups to heads.
    def to_heads(t):  # [B,C,Q,G,N] -> [B,C,Q,H,N]
        return jnp.repeat(t, hpg, axis=3)

    b_h = to_heads(b_c)
    c_h = to_heads(c_c)

    cum = jnp.cumsum(da_c, axis=-1)                             # [B,H,C,Q]
    seg = segsum(da_c)                                          # [B,H,C,Q,Q]
    l_mat = jnp.exp(seg)

    # 1) Intra-chunk (dual quadratic form).
    y_intra = jnp.einsum(
        "bclhn,bcshn,bhcls,bcshp->bclhp", c_h, b_h, l_mat, x_c
    )

    # 2) Per-chunk final states: decay each position to the chunk end.
    decay_to_end = jnp.exp(cum[..., -1:] - cum)                 # [B,H,C,Q]
    states = jnp.einsum("bcshn,bhcs,bcshp->bchpn", b_h, decay_to_end, x_c)

    # 3) Inter-chunk recurrence (scan over chunks).
    chunk_decay = jnp.exp(cum[..., -1])                         # [B,H,C]
    init = (
        jnp.zeros((bsz, h, p, n), f32)
        if initial_state is None
        else initial_state.astype(f32)
    )

    def step(carry, inputs):
        s_c, decay_c = inputs                                   # [B,H,P,N], [B,H]
        new = carry * decay_c[..., None, None] + s_c
        return new, carry                                        # emit state at chunk *start*

    xs = (
        states.transpose(1, 0, 2, 3, 4),                        # [C,B,H,P,N]
        chunk_decay.transpose(2, 0, 1),                         # [C,B,H]
    )
    final_state, start_states = jax.lax.scan(step, init, xs)
    start_states = start_states.transpose(1, 0, 2, 3, 4)        # [B,C,H,P,N]

    # 4) Inter-chunk contribution: state at chunk start, decayed to l.
    state_decay = jnp.exp(cum)                                  # [B,H,C,Q]
    y_inter = jnp.einsum(
        "bclhn,bhcl,bchpn->bclhp", c_h, state_decay, start_states
    )

    y = (y_intra + y_inter).reshape(bsz, s, h, p)[:, :orig_s]
    return y, final_state


def ssd_step(
    x: jax.Array,       # [B,H,P]
    dt: jax.Array,      # [B,H]
    a: jax.Array,       # [H]
    b_vec: jax.Array,   # [B,G,N]
    c_vec: jax.Array,   # [B,G,N]
    state: jax.Array,   # [B,H,P,N]
) -> Tuple[jax.Array, jax.Array]:
    """Single decode step of the SSD recurrence."""
    f32 = jnp.float32
    h = x.shape[1]
    g = b_vec.shape[1]
    hpg = h // g
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32)[None, :])                # [B,H]
    b_h = jnp.repeat(b_vec.astype(f32), hpg, axis=1)            # [B,H,N]
    c_h = jnp.repeat(c_vec.astype(f32), hpg, axis=1)
    dbx = jnp.einsum("bh,bhn,bhp->bhpn", dt, b_h, x.astype(f32))
    state = state * decay[..., None, None] + dbx
    y = jnp.einsum("bhpn,bhn->bhp", state, c_h)
    return y, state


# ---------------------------------------------------------------------------
# Full Mamba-2 block
# ---------------------------------------------------------------------------


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array, eps: float):
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32))


def _in_proj(cfg, params: Dict, x: jax.Array, cdt):
    z = x @ params["in_proj_z"].astype(cdt)
    xbc = x @ params["in_proj_xbc"].astype(cdt)
    dt = x @ params["in_proj_dt"].astype(cdt)
    return z, xbc, dt


def apply_mamba(
    cfg, params: Dict, x: jax.Array, *, initial_state=None
) -> jax.Array:
    """Full-sequence Mamba-2 block. x: [B,S,D] → [B,S,D]."""
    cdt = _dtype(cfg.compute_dtype)
    bsz, s, _ = x.shape
    di, g, n, h, p = (
        cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim,
    )

    z, xbc, dt_raw = _in_proj(cfg, params, x.astype(cdt), cdt)

    # Causal depthwise conv over the sequence.
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(cdt)

    xs = xbc[..., :di].reshape(bsz, s, h, p)
    b_mat = xbc[..., di : di + g * n].reshape(bsz, s, g, n)
    c_mat = xbc[..., di + g * n :].reshape(bsz, s, g, n)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :]
    )
    a = -jnp.exp(params["a_log"])

    with jax.named_scope("ssd"):
        if cfg.use_kernels:
            from repro.kernels.ops import ssd_scan

            y, _ = ssd_scan(xs, dt, a, b_mat, c_mat, chunk=cfg.ssm_chunk)
        else:
            y, _ = ssd_chunked(
                xs, dt, a, b_mat, c_mat, cfg.ssm_chunk, initial_state
            )
    y = y + xs.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, di)

    y = _gated_rmsnorm(y, z, params["norm_scale"], cfg.norm_eps).astype(cdt)
    return y @ params["out_proj"].astype(cdt)


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. x: [B,S,C]; w: [W,C]."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(width):  # unrolled: width is 4
        out = out + pad[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(
            jnp.float32
        )
    return (out + b.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def init_mamba_cache(cfg, batch: int, dtype=jnp.float32) -> Dict:
    di, g, n = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state
    conv_dim = di + 2 * g * n
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_headdim, n), dtype),
    }


def apply_mamba_step(
    cfg, params: Dict, x: jax.Array, cache: Dict
) -> Tuple[jax.Array, Dict]:
    """One-token decode. x: [B,1,D] → ([B,1,D], new cache)."""
    cdt = _dtype(cfg.compute_dtype)
    bsz = x.shape[0]
    di, g, n, h, p = (
        cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim,
    )

    z, xbc, dt_raw = _in_proj(cfg, params, x[:, 0, :].astype(cdt), cdt)

    # Rolling conv buffer: window = [cache | current].
    window = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)  # [B,W,C]
    conv_out = jnp.einsum(
        "bwc,wc->bc", window.astype(jnp.float32), params["conv_w"].astype(jnp.float32)
    ) + params["conv_b"].astype(jnp.float32)
    xbc_t = jax.nn.silu(conv_out).astype(cdt)
    new_conv = window[:, 1:, :]

    xs = xbc_t[..., :di].reshape(bsz, h, p)
    b_vec = xbc_t[..., di : di + g * n].reshape(bsz, g, n)
    c_vec = xbc_t[..., di + g * n :].reshape(bsz, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, :])
    a = -jnp.exp(params["a_log"])

    y, new_ssm = ssd_step(xs, dt, a, b_vec, c_vec, cache["ssm"])
    y = y + xs.astype(jnp.float32) * params["d_skip"][None, :, None]
    y = y.reshape(bsz, di)

    y = _gated_rmsnorm(y, z, params["norm_scale"], cfg.norm_eps).astype(cdt)
    out = (y @ params["out_proj"].astype(cdt))[:, None, :]
    return out, {"conv": new_conv, "ssm": new_ssm}
