"""Grouped-query attention with RoPE / qk-norm / bias variants + KV cache.

The KV cache keeps positions last: K and V are ``[B, KV, Dh, T]``. That is
the layout the decode dots read with positions minor. A decode step reads
the cache once and changes only the new token's position: attention takes
the token's own K/V beside the cache, and :func:`write_kv` then puts them
into the (donated) stacked cache, touching only the tiles that hold the
new positions.

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
from repro.sharding.ctx import map_kv_shards

NEG_INF = -1e30
LANES = 128  # positions in one tile of the cache's minor (lane) axis


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
    scores = _scaled(
        jnp.einsum("bskgd,bkdt->bkgst", qg, k).astype(jnp.float32), scale, d
    )
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,bkdt->bskgd", probs, v)
    return out.reshape(b, s, h, d)


def _scaled(scores: jax.Array, scale: float, d: int) -> jax.Array:
    if scale:
        return scores * scale
    return scores / jnp.sqrt(jnp.float32(d))


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

    Reads the cache and writes nothing to it. Returns (attn output
    [B,1,D], the token's K, its V), each of K and V as the cache stores
    it (:func:`encode_kv`), for :func:`write_kv` to put at ``position``.
    The token attends to the cached positions before ``position`` and to
    its own K/V, which is the softmax over positions ``<= position`` that
    the cache holds once the token is written.
    """
    cdt = _dtype(cfg.compute_dtype)
    q, k_new, v_new = _project_qkv(
        cfg, params, x, positions=position[:, None]
    )
    k_tok = encode_kv(k_new[:, 0], cache_k)
    v_tok = encode_kv(v_new[:, 0], cache_v)
    ref = cache_k["q"] if isinstance(cache_k, dict) else cache_k
    earlier = jnp.arange(ref.shape[-1])[None, :] < position[:, None]  # [B,T]
    out = _sdpa_step(
        q, dequant_kv(cache_k, cdt), dequant_kv(cache_v, cdt), earlier,
        dequant_kv(k_tok, cdt), dequant_kv(v_tok, cdt), cfg.attn_scale,
    )
    out = out.reshape(*out.shape[:-2], cfg.n_heads * cfg.head_dim)
    return out @ params["wo"].astype(cdt), k_tok, v_tok


def _sdpa_step(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    earlier: jax.Array,
    k_own: jax.Array,
    v_own: jax.Array,
    scale: float = 0.0,
) -> jax.Array:
    """:func:`_sdpa` for one query token. q: [B,1,H,D]; k,v: [B,KV,D,T]
    cached, of which the positions where ``earlier`` ([B,T]) is set count;
    k_own, v_own: [B,KV,D], the token's own. The own score is one more
    column of the float32 softmax: it joins the row's max and sum."""
    b, _, h, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(b, kvh, h // kvh, d)
    scores = _scaled(
        jnp.einsum("bkgd,bkdt->bkgt", qg, k).astype(jnp.float32), scale, d
    )
    scores = jnp.where(earlier[:, None, None, :], scores, NEG_INF)
    own = _scaled(
        jnp.einsum("bkgd,bkd->bkg", qg, k_own).astype(jnp.float32), scale, d
    )[..., None]
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), own)
    probs = jnp.exp(scores - top)
    own_prob = jnp.exp(own - top)
    total = jnp.sum(probs, axis=-1, keepdims=True) + own_prob
    out = jnp.einsum("bkgt,bkdt->bkgd", (probs / total).astype(v.dtype), v)
    out = out + (own_prob / total).astype(v.dtype) * v_own[:, :, None, :]
    return out.reshape(b, 1, h, d)


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


def encode_kv(new: jax.Array, cache):
    """One token's K or V (``new`` [B,KV,Dh]) as ``cache`` stores it: in
    the cache's dtype, or int8 ``{"q": [B,KV,Dh], "scale": [B,KV,1]}``."""
    if isinstance(cache, dict):
        return jax.tree.map(lambda a: a[..., 0], quant_kv(new[..., None]))
    return new.astype(cache.dtype)


def write_kv(cache, new, position: jax.Array):
    """Write each layer's token K/V into the stacked positions-last cache,
    in place under a jit that donates the cache.

    ``cache`` is a tree of ``[L,B,KV,Dh,T]`` leaves (K and V, or their
    int8 ``q`` and ``scale``) and ``new`` the same tree of ``[L,B,KV,Dh]``
    token values (:func:`encode_kv`); slot ``b`` is written at
    ``position[b]``. One loop over the slots reads and writes, in every
    leaf, only the tile of ``LANES`` positions that holds the slot's
    position. A scatter of the token's column instead makes the compiler
    relayout the whole cache around it. Under a mesh each device writes
    its own shard (:func:`map_kv_shards`).
    """
    return map_kv_shards(_write_tokens, cache, new, position)


def _write_tokens(cache, new, position: jax.Array):
    leaves, tree = jax.tree.flatten(cache)
    tokens = jax.tree.leaves(new)

    def slot(b, leaves):
        return [_write_tile(c, t, b, position[b]) for c, t in zip(leaves, tokens)]

    return jax.tree.unflatten(
        tree, jax.lax.fori_loop(0, leaves[0].shape[1], slot, leaves)
    )


def _write_tile(cache: jax.Array, new: jax.Array, b, pos) -> jax.Array:
    """``cache`` with slot ``b``'s column ``new[:, b]`` selected in at
    ``pos`` within the tile that holds it. A ``pos`` outside the position
    axis matches no column, and the tile is written back as it was."""
    n_layers, _, kvh, dh, t = cache.shape
    tile = min(LANES, t)
    start = jnp.clip(pos // tile * tile, 0, t - tile)
    old = jax.lax.dynamic_slice(
        cache, (0, b, 0, 0, start), (n_layers, 1, kvh, dh, tile)
    )
    col = jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1)[..., None]
    at = jnp.arange(tile) == pos - start
    return jax.lax.dynamic_update_slice(
        cache, jnp.where(at, col.astype(cache.dtype), old), (0, b, 0, 0, start)
    )


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
