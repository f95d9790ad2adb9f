"""Grouped-query attention with RoPE / qk-norm / bias variants + KV cache.

The KV cache keeps positions last: K and V are ``[B, KV, Dh, T]``. That is
the layout the decode dots read with positions minor, and the one-token
write is a select on the position axis in that same layout, so a decode
step reads and writes the cache as stored, with no relayout of it.

Three entry points:
  * :func:`attend_full`   — full-sequence causal (train / prefill);
  * :func:`attend_cached` — one-step decode against a KV cache;
  * :func:`attend_cross`  — encoder-decoder cross attention.

The full path optionally routes through the Pallas flash-attention kernel
(``cfg.use_kernels``); the jnp path is the XLA/GSPMD roofline baseline and
the oracle the kernel is validated against.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import (
    _dtype,
    _init_linear,
    apply_rope,
    rms_norm_headwise,
)

NEG_INF = -1e30


def init_attention(cfg, rng: jax.Array, *, cross: bool = False) -> Dict:
    dtype = _dtype(cfg.param_dtype)
    keys = jax.random.split(rng, 5)
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    params: Dict = {
        "wq": _init_linear(keys[0], d, h * hd, dtype),
        "wk": _init_linear(keys[1], d, kv * hd, dtype),
        "wv": _init_linear(keys[2], d, kv * hd, dtype),
        "wo": _init_linear(keys[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias and not cross:
        params["bq"] = jnp.zeros((h * hd,), dtype)
        params["bk"] = jnp.zeros((kv * hd,), dtype)
        params["bv"] = jnp.zeros((kv * hd,), dtype)
    if cfg.qk_norm and not cross:
        params["q_norm"] = jnp.ones((hd,), dtype)
        params["k_norm"] = jnp.ones((hd,), dtype)
    return params


def _project_qkv(
    cfg,
    params: Dict,
    x: jax.Array,
    kv_input: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    *,
    use_rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    cdt = _dtype(cfg.compute_dtype)
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = x.astype(cdt)
    kv_src = x if kv_input is None else kv_input.astype(cdt)

    q = x @ params["wq"].astype(cdt)
    k = kv_src @ params["wk"].astype(cdt)
    v = kv_src @ params["wv"].astype(cdt)
    if "bq" in params:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)

    q = q.reshape(*q.shape[:-1], h, hd)
    k = k.reshape(*k.shape[:-1], kv, hd)
    v = v.reshape(*v.shape[:-1], kv, hd)

    if "q_norm" in params:
        q = rms_norm_headwise(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm_headwise(k, params["k_norm"], cfg.norm_eps)

    if use_rope and cfg.pos_embedding == "rope" and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    scale: float = 0.0,
) -> jax.Array:
    """q: [B,S,H,D]; k,v: [B,KV,D,T] (positions last) — grouped-query
    dot-product attention. mask broadcasts against [B,KV,G,S,T]. Scores are
    scaled by ``scale``, or by 1/sqrt(D) when it is 0."""
    b, s, h, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    qg = q.reshape(b, s, kvh, group, d)
    scores = jnp.einsum("bskgd,bkdt->bkgst", qg, k).astype(jnp.float32)
    if scale:
        scores = scores * scale
    else:
        scores = scores / jnp.sqrt(jnp.float32(d))
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bkdt->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def positions_last(x: jax.Array) -> jax.Array:
    """[B,T,KV,Dh] (as projected) → [B,KV,Dh,T] (the cache layout)."""
    return x.transpose(0, 2, 3, 1)


def attend_full(
    cfg,
    params: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
) -> jax.Array:
    """Full-sequence self attention. x: [B,S,D]; positions: [B,S]."""
    cdt = _dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(cfg, params, x, positions=positions)
    s = x.shape[1]
    mask = None
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))[None, None, None, :, :]
    if cfg.use_kernels:
        from repro.kernels.ops import flash_attention

        out = flash_attention(q, k, v, causal=causal)
    else:
        out = _sdpa(q, positions_last(k), positions_last(v), mask, cfg.attn_scale)
    out = out.reshape(*out.shape[:-2], cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(cdt)


def attend_cached(
    cfg,
    params: Dict,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    position: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: [B,1,D]; cache_{k,v}: [B,KV,Dh,T]; position: [B].

    Returns (attn output [B,1,D], new cache_k, new cache_v). The new token's
    K/V are written at ``position``; attention masks out cache slots beyond
    ``position``.
    """
    cdt = _dtype(cfg.compute_dtype)
    q, k_new, v_new = _project_qkv(
        cfg, params, x, positions=position[:, None]
    )
    ref = cache_k["q"] if isinstance(cache_k, dict) else cache_k
    t_idx = jnp.arange(ref.shape[-1])[None, :]  # [1,T]

    # The write is a select on the position axis: it reads and writes the
    # cache in its stored layout (a scatter there wants head_dim minor and
    # makes the compiler relayout the layer's whole cache around it).
    at = t_idx == position[:, None]  # [B,T]
    cache_k = write_kv(cache_k, k_new[:, 0], at)
    cache_v = write_kv(cache_v, v_new[:, 0], at)

    # Mask: only slots <= position are attendable.
    valid = t_idx <= position[:, None]  # [B,T]
    mask = valid[:, None, None, None, :]  # [B,KV,G,1,T]
    out = _sdpa(q, dequant_kv(cache_k, cdt), dequant_kv(cache_v, cdt), mask,
                cfg.attn_scale)
    out = out.reshape(*out.shape[:-2], cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(cdt), cache_k, cache_v


def attend_cross(
    cfg,
    params: Dict,
    x: jax.Array,
    enc_out: jax.Array,
) -> jax.Array:
    """Cross attention (decoder query, encoder memory); no mask, no rope."""
    cdt = _dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(cfg, params, x, kv_input=enc_out, use_rope=False)
    out = _sdpa(q, positions_last(k), positions_last(v), None, cfg.attn_scale)
    out = out.reshape(*out.shape[:-2], cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(cdt)


def kv_cache_shape(cfg, batch: int, length: int) -> Tuple[int, ...]:
    """Shape of one K or V cache: ``[B, KV, Dh, T]``, positions last."""
    return (batch, cfg.n_kv_heads, cfg.head_dim, length)


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """KV cache pair, each :func:`kv_cache_shape`. With
    ``cfg.kv_cache_dtype == "int8"`` each of K/V is a dict {"q": int8
    [B,KV,Dh,T], "scale": f32 [B,KV,1,T]} (per-token, per-head absmax
    quantisation) — halving decode's dominant HBM term."""
    shape = kv_cache_shape(cfg, batch, max_len)
    if cfg.kv_cache_dtype == "int8":
        def q8():
            return {
                "q": jnp.zeros(shape, jnp.int8),
                "scale": jnp.zeros(shape[:-2] + (1, max_len), jnp.float32),
            }
        return q8(), q8()
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def quant_kv(x: jax.Array):
    """Per-(token, head) absmax int8 quantisation of positions-last K or V
    (``[..., Dh, T]``: the absmax runs over head_dim, axis -2)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(xf), axis=-2, keepdims=True) / 127.0, 1e-20
    )
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequant_kv(c, dtype) -> jax.Array:
    if isinstance(c, dict):
        return (c["q"].astype(jnp.float32) * c["scale"]).astype(dtype)
    return c.astype(dtype)


def write_kv(cache, new: jax.Array, at: jax.Array):
    """Write one token's K or V (``new`` [B,KV,Dh]) into the positions-last
    cache where ``at`` ([B,T] bool, one true per row) is set."""
    at = at[:, None, None, :]
    new = new[..., None]  # [B,KV,Dh,1]
    if isinstance(cache, dict):
        enc = quant_kv(new)
        return {
            "q": jnp.where(at, enc["q"], cache["q"]),
            "scale": jnp.where(at, enc["scale"], cache["scale"]),
        }
    return jnp.where(at, new.astype(cache.dtype), cache)


def write_kv_prefix(cache, new: jax.Array):
    """Write a prompt's K or V (``new`` [B,S,KV,Dh], as projected) into the
    first S positions of the positions-last cache (prefill path)."""
    new = positions_last(new)
    s = new.shape[-1]
    if isinstance(cache, dict):
        enc = quant_kv(new)
        return {
            "q": cache["q"].at[..., :s].set(enc["q"]),
            "scale": cache["scale"].at[..., :s].set(enc["scale"]),
        }
    return cache.at[..., :s].set(new.astype(cache.dtype))
