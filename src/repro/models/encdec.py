"""Encoder-decoder transformer (Whisper-small backbone).

The audio frontend (log-mel + conv downsampling) is a STUB per the
assignment: ``input_specs()`` feeds precomputed frame embeddings
``[B, S_enc, d_model]`` directly to the encoder. Learned positional
embeddings, LayerNorm, GELU — per the Whisper architecture.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import basic
from repro.models.layers.attention import (
    _project_qkv,
    _sdpa,
    attend_cached,
    attend_cross,
    attend_full,
    init_attention,
    init_kv_cache,
    kv_cache_shape,
    positions_last,
    write_kv,
    write_kv_prefix,
)
from repro.sharding.ctx import constrain


def _init_pos_table(cfg, rng: jax.Array, n: int) -> jax.Array:
    return (
        0.01 * jax.random.normal(rng, (n, cfg.d_model), dtype=jnp.float32)
    ).astype(jnp.dtype(cfg.param_dtype))


def init_params(cfg: ModelConfig, rng: jax.Array) -> Dict:
    keys = jax.random.split(rng, 8)
    max_pos = cfg.max_position or 4096

    def enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {
            "attn_norm": basic.init_norm(cfg),
            "attn": init_attention(cfg, k1),
            "ffn_norm": basic.init_norm(cfg),
            "ffn": basic.init_ffn(cfg, k2),
        }

    def dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "self_norm": basic.init_norm(cfg),
            "self_attn": init_attention(cfg, k1),
            "cross_norm": basic.init_norm(cfg),
            "cross_attn": init_attention(cfg, k2, cross=True),
            "ffn_norm": basic.init_norm(cfg),
            "ffn": basic.init_ffn(cfg, k3),
        }

    enc_keys = jax.random.split(keys[0], cfg.encoder_layers)
    dec_keys = jax.random.split(keys[1], cfg.n_layers)
    return {
        "enc_pos": _init_pos_table(cfg, keys[2], max_pos),
        "dec_pos": _init_pos_table(cfg, keys[3], max_pos),
        "embed": basic.init_embedding(cfg, keys[4]),
        "encoder": jax.vmap(enc_layer)(enc_keys),
        "decoder": jax.vmap(dec_layer)(dec_keys),
        "enc_final_norm": basic.init_norm(cfg),
        "final_norm": basic.init_norm(cfg),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------


def encode(cfg: ModelConfig, params: Dict, frames: jax.Array) -> jax.Array:
    """frames: [B, S_enc, d_model] (stub frontend output) → [B, S_enc, d]."""
    cdt = jnp.dtype(cfg.compute_dtype)
    bsz, s, _ = frames.shape
    pos = params["enc_pos"][:s].astype(cdt)
    x = frames.astype(cdt) + pos[None]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))

    def body(x, layer):
        x = constrain(x, ("dp", "tp", None))
        h = basic.apply_norm(cfg, layer["attn_norm"], x)
        h = attend_full(cfg, layer["attn"], h, positions, causal=False)
        x = x + h
        h = basic.apply_norm(cfg, layer["ffn_norm"], x)
        x = x + basic.apply_ffn(cfg, layer["ffn"], h)
        return x, None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return basic.apply_norm(cfg, params["enc_final_norm"], x)


# ---------------------------------------------------------------------------
# Decoder (train / prefill forward)
# ---------------------------------------------------------------------------


def decode_full(
    cfg: ModelConfig, params: Dict, tokens: jax.Array, enc_out: jax.Array
) -> jax.Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    bsz, s = tokens.shape
    x = basic.embed(cfg, params["embed"], tokens)
    x = x + params["dec_pos"][:s].astype(cdt)[None]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))

    def body(x, layer):
        x = constrain(x, ("dp", "tp", None))
        h = basic.apply_norm(cfg, layer["self_norm"], x)
        h = attend_full(cfg, layer["self_attn"], h, positions, causal=True)
        x = x + h
        h = basic.apply_norm(cfg, layer["cross_norm"], x)
        h = attend_cross(cfg, layer["cross_attn"], h, enc_out)
        x = x + h
        h = basic.apply_norm(cfg, layer["ffn_norm"], x)
        x = x + basic.apply_ffn(cfg, layer["ffn"], h)
        return x, None

    x, _ = jax.lax.scan(body, x, params["decoder"])
    x = basic.apply_norm(cfg, params["final_norm"], x)
    logits = basic.unembed(cfg, params["embed"], x)  # tied head (Whisper ties)
    return constrain(logits, ("dp", None, "vocab"))


def loss_fn(
    cfg: ModelConfig, params: Dict, batch: Dict[str, jax.Array]
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: {"frames": [B,S_enc,d], "tokens": [B,S_dec]}."""
    enc_out = encode(cfg, params, batch["frames"])
    logits = decode_full(cfg, params, batch["tokens"], enc_out)
    targets = batch["tokens"][:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1, :], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = jnp.mean(nll)
    return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    enc_len: int,
    dtype=jnp.bfloat16,
) -> Dict:
    """Self- and cross-attention K/V per decoder layer, each stacked
    ``[L, B, KV, Dh, T]`` (positions last)."""
    k, v = init_kv_cache(cfg, batch, max_len, dtype)

    def stack(leaf):
        return jnp.broadcast_to(leaf[None], (cfg.n_layers,) + leaf.shape).copy()

    cross_shape = kv_cache_shape(cfg, batch, enc_len)
    return {
        "self_k": jax.tree.map(stack, k),
        "self_v": jax.tree.map(stack, v),
        "cross_k": jnp.zeros((cfg.n_layers,) + cross_shape, dtype),
        "cross_v": jnp.zeros((cfg.n_layers,) + cross_shape, dtype),
    }


def prefill(
    cfg: ModelConfig,
    params: Dict,
    frames: jax.Array,
    tokens: jax.Array,
    cache: Dict,
) -> Tuple[jax.Array, Dict]:
    """Encode + decoder prompt pass, filling self- and cross-KV caches."""
    enc_out = encode(cfg, params, frames)
    cdt = jnp.dtype(cfg.compute_dtype)
    bsz, s = tokens.shape
    x = basic.embed(cfg, params["embed"], tokens)
    x = x + params["dec_pos"][:s].astype(cdt)[None]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))

    def body(x, inputs):
        layer, ck, cv = inputs
        x = constrain(x, ("dp", "tp", None))  # sequence-parallel carry
        h = basic.apply_norm(cfg, layer["self_norm"], x)
        q, k, v = _project_qkv(cfg, layer["self_attn"], h, positions=positions)
        new_sk = write_kv_prefix(ck, k)
        new_sv = write_kv_prefix(cv, v)
        h = attend_full(cfg, layer["self_attn"], h, positions, causal=True)
        x = x + h
        h = basic.apply_norm(cfg, layer["cross_norm"], x)
        _, xk, xv = _project_qkv(
            cfg, layer["cross_attn"], h, kv_input=enc_out, use_rope=False
        )
        h = attend_cross(cfg, layer["cross_attn"], h, enc_out)
        x = x + h
        h = basic.apply_norm(cfg, layer["ffn_norm"], x)
        x = x + basic.apply_ffn(cfg, layer["ffn"], h)
        xk = positions_last(xk).astype(cache["cross_k"].dtype)
        xv = positions_last(xv).astype(cache["cross_v"].dtype)
        return x, (new_sk, new_sv, xk, xv)

    x, (sk, sv, xk, xv) = jax.lax.scan(
        body, x, (params["decoder"], cache["self_k"], cache["self_v"])
    )
    x = basic.apply_norm(cfg, params["final_norm"], x)
    logits = basic.unembed(cfg, params["embed"], x[:, -1:, :])
    return logits, {"self_k": sk, "self_v": sv, "cross_k": xk, "cross_v": xv}


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    cache: Dict,
    token: jax.Array,
    position: jax.Array,
) -> Tuple[jax.Array, Dict]:
    cdt = jnp.dtype(cfg.compute_dtype)
    x = basic.embed(cfg, params["embed"], token[:, None])
    pos_emb = jnp.take(params["dec_pos"], position, axis=0).astype(cdt)
    x = x + pos_emb[:, None, :]

    def body(x, inputs):
        layer, sk, sv, xk, xv = inputs
        h = basic.apply_norm(cfg, layer["self_norm"], x)
        h, k, v = attend_cached(cfg, layer["self_attn"], h, sk, sv, position)
        x = x + h
        h = basic.apply_norm(cfg, layer["cross_norm"], x)
        # Cross attention against the precomputed encoder K/V.
        q, _, _ = _project_qkv(cfg, layer["cross_attn"], h, use_rope=False)
        o = _sdpa(q, xk.astype(cdt), xv.astype(cdt), None)
        o = o.reshape(*o.shape[:-2], cfg.n_heads * cfg.head_dim)
        x = x + o @ layer["cross_attn"]["wo"].astype(cdt)
        h = basic.apply_norm(cfg, layer["ffn_norm"], x)
        x = x + basic.apply_ffn(cfg, layer["ffn"], h)
        return x, (k, v)

    # The self K/V are read in the scan; only the token's K/V leave it,
    # written in place after it. The cross K/V pass through unchanged.
    x, (k, v) = jax.lax.scan(
        body,
        x,
        (
            params["decoder"],
            cache["self_k"],
            cache["self_v"],
            cache["cross_k"],
            cache["cross_v"],
        ),
    )
    x = basic.apply_norm(cfg, params["final_norm"], x)
    logits = basic.unembed(cfg, params["embed"], x)
    own = write_kv(
        {"self_k": cache["self_k"], "self_v": cache["self_v"]},
        {"self_k": k, "self_v": v},
        position,
    )
    return logits, {
        **own,
        "cross_k": cache["cross_k"],
        "cross_v": cache["cross_v"],
    }
