"""Jit'd public wrappers around the Pallas kernels.

Layout adaptation lives here (the models use ``[B, S, H, D]``; the kernels
use ``[B, H, S, D]``), as does the interpret-mode switch: on a CPU backend
(this container) the kernels execute via ``interpret=True`` — the kernel
body runs in Python/XLA exactly as written — while on TPU they compile to
Mosaic. The pure-jnp oracles live in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd_scan_bhsd


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# Scheduler batch-routing kernel
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=())
def _select_first_available_jax(words32: jax.Array, orders: jax.Array) -> jax.Array:
    # words32: uint32 [m, 2W] — each uint64 mask word split into
    # (low, high) halves, low half at even indices (jax runs with x64
    # disabled on this container, so uint64 lanes are unavailable;
    # position p lives at word p>>5, bit p&31).
    valid = orders >= 0
    safe = jnp.where(valid, orders, 0)
    gathered = jnp.take_along_axis(
        jnp.broadcast_to(words32, (orders.shape[0], words32.shape[-1])),
        safe >> 5,
        axis=1,
    )
    bits = (gathered >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    hit = (bits != 0) & valid
    found = hit.any(axis=1)
    first = hit.argmax(axis=1)
    picks = jnp.take_along_axis(orders, first[:, None], axis=1)[:, 0]
    return jnp.where(found, picks, -1).astype(jnp.int32)


def select_first_available(avail_words, orders, *, backend: str = "numpy"):
    """First-set-bit-in-order over availability mask planes (batched).

    The scheduler's mask-plane routing kernel: ``orders`` is an int32
    ``[m, L]`` plane of candidate positions (one row per distinct
    function hash at a routing stage, ``-1``-padded); ``avail_words`` is
    the stage's uint64 availability bitmask (``[W]``, broadcast across
    rows, or per-row ``[m, W]``). Returns int32 ``[m]`` picks, ``-1``
    where no ordered candidate is available.

    ``backend="numpy"`` uses the reference in :mod:`repro.kernels.ref`;
    ``backend="jax"`` runs the identical computation as a jit'd XLA
    program (correctness-equal; useful once mask planes live on an
    accelerator alongside the model kernels).
    """
    from repro.kernels.ref import select_first_available_np

    if backend == "jax":
        import numpy as np

        words = np.ascontiguousarray(avail_words, dtype=np.uint64)
        if words.ndim == 1:
            words = words[None, :]
        # Split each uint64 word into (low, high) uint32 halves by value
        # — not via a .view(), whose half order depends on host byte
        # order — so position p lives at word p>>5, bit p&31 on any
        # endianness (matching _select_first_available_jax's indexing).
        words32 = np.empty(
            (words.shape[0], 2 * words.shape[1]), dtype=np.uint32
        )
        words32[:, 0::2] = (words & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        words32[:, 1::2] = (words >> np.uint64(32)).astype(np.uint32)
        ordered = np.ascontiguousarray(orders, dtype=np.int32)
        if ordered.ndim == 1:
            ordered = ordered[None, :]
        out = _select_first_available_jax(jnp.asarray(words32), jnp.asarray(ordered))
        return np.asarray(out)
    if backend != "numpy":
        raise ValueError(f"unknown select_first_available backend: {backend!r}")
    return select_first_available_np(avail_words, orders)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------


def flash_attention(
    q: jax.Array,    # [B, S, H, D]   (model layout)
    k: jax.Array,    # [B, T, KV, D]
    v: jax.Array,    # [B, T, KV, D]
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
) -> jax.Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(
        qt, kt, vt, causal=causal, bq=bq, bk=bk,
        interpret=_interpret_default(),
    )
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# MoE grouped matmul FFN
# ---------------------------------------------------------------------------


def moe_ffn_gmm(cfg, params: Dict, buffer: jax.Array) -> jax.Array:
    """Expert FFN over the packed [E, C, d] buffer via grouped matmuls."""
    interp = _interpret_default()
    cdt = buffer.dtype
    if cfg.mlp_kind in ("swiglu", "geglu"):
        gate = gmm(buffer, params["w_gate"].astype(cdt), interpret=interp)
        up = gmm(buffer, params["w_up"].astype(cdt), interpret=interp)
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
        h = (act(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(cdt)
    elif cfg.mlp_kind == "squared_relu":
        h = gmm(buffer, params["w_up"].astype(cdt), interpret=interp)
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(cdt)
    else:
        h = gmm(buffer, params["w_up"].astype(cdt), interpret=interp)
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(cdt)
    return gmm(h, params["w_down"].astype(cdt), interpret=interp)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------


def ssd_scan(
    x: jax.Array,     # [B, S, H, P]  (model layout)
    dt: jax.Array,    # [B, S, H]     (post-softplus)
    a: jax.Array,     # [H]           (negative)
    b_mat: jax.Array, # [B, S, G, N]
    c_mat: jax.Array, # [B, S, G, N]
    *,
    chunk: int = 256,
) -> Tuple[jax.Array, None]:
    f32 = jnp.float32
    dt_f = dt.astype(f32)
    xdt = (x.astype(f32) * dt_f[..., None]).transpose(0, 2, 1, 3)   # [B,H,S,P]
    da = (dt_f * a.astype(f32)[None, None, :]).transpose(0, 2, 1)   # [B,H,S]
    y = ssd_scan_bhsd(
        xdt,
        da[:, :, None, :],
        b_mat.transpose(0, 2, 1, 3),
        c_mat.transpose(0, 2, 1, 3),
        chunk=min(chunk, x.shape[1]) if x.shape[1] % min(chunk, x.shape[1]) == 0
        else chunk,
        interpret=_interpret_default(),
    )
    return y.transpose(0, 2, 1, 3), None
