"""Basic layers: norms, RoPE, embeddings, dense FFNs.

All layers are (init, apply) function pairs over plain dict pytrees. The
``compute`` dtype is applied by the caller; norms always run in float32
for numerical stability and cast back.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp


def _dtype(name: str):
    return jnp.dtype(name)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg, dim: Optional[int] = None) -> Dict:
    dim = dim or cfg.d_model
    params = {"scale": jnp.ones((dim,), dtype=_dtype(cfg.param_dtype))}
    if cfg.norm_kind == "layernorm":
        params["bias"] = jnp.zeros((dim,), dtype=_dtype(cfg.param_dtype))
    return params


def apply_norm(cfg, params: Dict, x: jax.Array) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_kind == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        x = x * params["scale"].astype(jnp.float32)
        x = x + params["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(ms + cfg.norm_eps)
        x = x * params["scale"].astype(jnp.float32)
    return x.astype(orig_dtype)


def rms_norm_headwise(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Per-head RMS norm for qk-norm (normalises the trailing head_dim)."""
    orig = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
    return out.astype(orig)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # [head_dim/2]


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float
) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)            # [D/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., :, None, :]               # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------


def init_embedding(cfg, rng: jax.Array) -> Dict:
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.d_model))
    table = (
        jax.random.normal(rng, (cfg.vocab_size, cfg.d_model), dtype=jnp.float32)
        * scale
    ).astype(_dtype(cfg.param_dtype))
    return {"table": table}


def embed(cfg, params: Dict, tokens: jax.Array) -> jax.Array:
    out = jnp.take(params["table"], tokens, axis=0)
    out = out.astype(_dtype(cfg.compute_dtype))
    if cfg.embedding_multiplier != 1.0:
        out = out * cfg.embedding_multiplier
    return out


def unembed(cfg, params: Dict, x: jax.Array) -> jax.Array:
    """Project to vocab logits (tied or untied); returns float32 logits."""
    table = params["table"]
    logits = jnp.einsum(
        "...d,vd->...v", x.astype(jnp.float32), table.astype(jnp.float32)
    )
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logit_softcap > 0:
        cap = cfg.logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


# ---------------------------------------------------------------------------
# Dense FFN
# ---------------------------------------------------------------------------


def _init_linear(rng, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / jnp.sqrt(jnp.float32(d_in))
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32) * scale).astype(
        dtype
    )


def init_ffn(cfg, rng: jax.Array, d_ff: Optional[int] = None) -> Dict:
    """A dense FFN of width ``d_ff`` (default ``cfg.d_ff``)."""
    dtype = _dtype(cfg.param_dtype)
    f = d_ff or cfg.d_ff
    keys = jax.random.split(rng, 3)
    params: Dict = {}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        params["w_gate"] = _init_linear(keys[0], cfg.d_model, f, dtype)
        params["w_up"] = _init_linear(keys[1], cfg.d_model, f, dtype)
        params["w_down"] = _init_linear(keys[2], f, cfg.d_model, dtype)
    else:  # squared_relu | gelu
        params["w_up"] = _init_linear(keys[0], cfg.d_model, f, dtype)
        params["w_down"] = _init_linear(keys[1], f, cfg.d_model, dtype)
    return params


def apply_ffn(cfg, params: Dict, x: jax.Array) -> jax.Array:
    cdt = _dtype(cfg.compute_dtype)
    x = x.astype(cdt)
    if cfg.mlp_kind in ("swiglu", "geglu"):
        gate = x @ params["w_gate"].astype(cdt)
        up = x @ params["w_up"].astype(cdt)
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
        h = act(gate) * up
    elif cfg.mlp_kind == "squared_relu":
        h = x @ params["w_up"].astype(cdt)
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_kind == "gelu":
        h = x @ params["w_up"].astype(cdt)
        h = jax.nn.gelu(h)
    else:
        raise ValueError(f"unknown mlp_kind {cfg.mlp_kind!r}")
    return h @ params["w_down"].astype(cdt)
