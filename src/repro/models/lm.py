"""Decoder-only language model covering the lm / hybrid / ssm families.

The layer stack is ``n_periods`` repetitions of the config's period
pattern (see :meth:`ModelConfig.layer_pattern`). Parameters of each
period-position are stacked along a leading ``n_periods`` axis and the
stack is traversed with ``jax.lax.scan`` — one compiled block body
regardless of depth (72-layer Jamba lowers as 9 scan steps of an 8-layer
body). Activation checkpointing wraps the scan body per the config's
remat policy.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import basic
from repro.models.layers.attention import (
    _project_qkv,
    attend_cached,
    attend_full,
    init_attention,
    init_kv_cache,
    write_kv,
    write_kv_prefix,
)
from repro.models.layers.moe import apply_moe, init_moe
from repro.models.layers.ssm import (
    apply_mamba,
    apply_mamba_step,
    init_mamba,
    init_mamba_cache,
)
from repro.sharding.ctx import constrain

AUX_LOSS_WEIGHT = 0.01


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_period(cfg: ModelConfig, rng: jax.Array) -> Dict:
    """Parameters for one period (pattern of layers)."""
    pattern = cfg.layer_pattern()
    params: Dict = {}
    keys = jax.random.split(rng, 2 * len(pattern))
    for i, (mixer, ffn) in enumerate(pattern):
        sub: Dict = {"mixer_norm": basic.init_norm(cfg)}
        if mixer == "attn":
            sub["attn"] = init_attention(cfg, keys[2 * i])
        else:
            sub["mamba"] = init_mamba(cfg, keys[2 * i])
        if ffn == "dense":
            sub["ffn_norm"] = basic.init_norm(cfg)
            sub["ffn"] = basic.init_ffn(cfg, keys[2 * i + 1])
        elif ffn == "moe":
            sub["ffn_norm"] = basic.init_norm(cfg)
            sub["moe"] = init_moe(cfg, keys[2 * i + 1])
        params[f"pos{i}"] = sub
    return params


def init_params(cfg: ModelConfig, rng: jax.Array) -> Dict:
    k_embed, k_blocks, k_head = jax.random.split(rng, 3)
    block_keys = jax.random.split(k_blocks, cfg.n_periods)
    blocks = jax.vmap(lambda k: init_period(cfg, k))(block_keys)
    params: Dict = {
        "embed": basic.init_embedding(cfg, k_embed),
        "blocks": blocks,
        "final_norm": basic.init_norm(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = basic.init_embedding(cfg, k_head)
    return params


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_period(
    cfg: ModelConfig,
    period_params: Dict,
    x: jax.Array,
    positions: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One period of layers. Returns (x, aux_loss)."""
    aux_total = jnp.zeros((), jnp.float32)
    for i, (mixer, ffn) in enumerate(cfg.layer_pattern()):
        sub = period_params[f"pos{i}"]
        h = basic.apply_norm(cfg, sub["mixer_norm"], x)
        with jax.named_scope(mixer):
            if mixer == "attn":
                h = attend_full(cfg, sub["attn"], h, positions)
            else:
                h = apply_mamba(cfg, sub["mamba"], h)
        x = residual_add(cfg, x, h)
        if ffn != "none":
            h = basic.apply_norm(cfg, sub["ffn_norm"], x)
            if ffn == "moe":
                h, aux = apply_moe(cfg, sub["moe"], h)
                aux_total = aux_total + aux
            else:
                h = basic.apply_ffn(cfg, sub["ffn"], h)
            x = residual_add(cfg, x, h)
    return x, aux_total


def residual_add(cfg: ModelConfig, x: jax.Array, h: jax.Array) -> jax.Array:
    """``x + h``, with ``h`` scaled by the config's residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return x + h


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    raise ValueError(f"unknown remat policy {cfg.remat!r}")


def forward(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    *,
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full forward pass. Returns (logits [B,S,V] float32, aux loss)."""
    if embeds is None:
        x = basic.embed(cfg, params["embed"], tokens)
    else:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    bsz, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))

    period_fn = _remat_wrap(
        cfg,
        functools.partial(_apply_period, cfg),
    )

    def scan_body(carry, period_params):
        x, aux = carry
        # Sequence parallelism on the residual stream between periods: the
        # stored scan carry shards S over the TP axis (see sharding/ctx.py).
        x = constrain(x, ("dp", "tp", None))
        x, aux_p = period_fn(period_params, x, positions)
        x = constrain(x, ("dp", "tp", None))
        return (x, aux + aux_p), None

    (x, aux), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )

    x = basic.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = basic.unembed(cfg, head, x)
    logits = constrain(logits, ("dp", None, "vocab"))  # vocab-parallel CE
    return logits, aux


def loss_fn(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy (+ MoE aux). batch: {"tokens": [B,S]}."""
    tokens = batch["tokens"]
    logits, aux = forward(cfg, params, tokens, embeds=batch.get("embeds"))
    targets = tokens[:, 1:]
    logits = logits[:, :-1, :]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    else:
        ce = jnp.mean(nll)
    total = ce + AUX_LOSS_WEIGHT * aux
    return total, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with caches
# ---------------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Dict:
    """Stacked per-period cache pytree matching params["blocks"]."""

    def one_period() -> Dict:
        cache: Dict = {}
        for i, (mixer, _ffn) in enumerate(cfg.layer_pattern()):
            if mixer == "attn":
                k, v = init_kv_cache(cfg, batch, max_len, dtype)
                cache[f"pos{i}"] = {"k": k, "v": v}
            else:
                cache[f"pos{i}"] = init_mamba_cache(cfg, batch)
        return cache

    single = one_period()
    return jax.tree.map(
        lambda leaf: jnp.broadcast_to(
            leaf[None], (cfg.n_periods,) + leaf.shape
        ).copy(),
        single,
    )


def prefill(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    cache: Dict,
    *,
    embeds: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Process a full prompt, filling the cache. Returns (logits, cache)."""
    if embeds is None:
        x = basic.embed(cfg, params["embed"], tokens)
    else:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    bsz, s = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (bsz, s))

    def scan_body(x, inputs):
        period_params, period_cache = inputs
        new_cache: Dict = {}
        x = constrain(x, ("dp", "tp", None))  # sequence-parallel carry
        for i, (mixer, ffn) in enumerate(cfg.layer_pattern()):
            sub = period_params[f"pos{i}"]
            c = period_cache[f"pos{i}"]
            h = basic.apply_norm(cfg, sub["mixer_norm"], x)
            with jax.named_scope(mixer):
                if mixer == "attn":
                    _, k, v = _project_qkv(cfg, sub["attn"], h, positions=positions)
                    new_cache[f"pos{i}"] = {
                        "k": write_kv_prefix(c["k"], k),
                        "v": write_kv_prefix(c["v"], v),
                    }
                    h = attend_full(cfg, sub["attn"], h, positions)
                else:
                    h, mamba_state = apply_mamba_with_state(cfg, sub["mamba"], h)
                    new_cache[f"pos{i}"] = mamba_state
            x = residual_add(cfg, x, h)
            if ffn != "none":
                h = basic.apply_norm(cfg, sub["ffn_norm"], x)
                if ffn == "moe":
                    h, _ = apply_moe(cfg, sub["moe"], h)
                else:
                    h = basic.apply_ffn(cfg, sub["ffn"], h)
                x = residual_add(cfg, x, h)
        return x, new_cache

    x, new_cache = jax.lax.scan(scan_body, x, (params["blocks"], cache))
    x = basic.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = basic.unembed(cfg, head, x[:, -1:, :])
    return logits, new_cache


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    cache: Dict,
    token: jax.Array,       # [B] int32 — the most recent token
    position: jax.Array,    # [B] int32 — its cache slot
) -> Tuple[jax.Array, Dict]:
    """One incremental decode step. Returns (logits [B,1,V], new cache).

    Each period's cache comes in as the scan's ``xs``. The attention layers
    read their K/V there and emit only the token's K/V as ``ys``; one write
    after the scan puts those at ``position`` in place, so under a jit that
    donates the cache the step moves only the tiles it changes. Mamba
    state, which changes whole every step, leaves as the scan's ``ys``.
    """
    x = basic.embed(cfg, params["embed"], token[:, None])
    pattern = cfg.layer_pattern()

    def scan_body(x, inputs):
        period_params, period_cache = inputs
        out: Dict = {}
        for i, (mixer, ffn) in enumerate(pattern):
            sub = period_params[f"pos{i}"]
            c = period_cache[f"pos{i}"]
            h = basic.apply_norm(cfg, sub["mixer_norm"], x)
            with jax.named_scope(mixer):
                if mixer == "attn":
                    h, k, v = attend_cached(
                        cfg, sub["attn"], h, c["k"], c["v"], position
                    )
                    out[f"pos{i}"] = {"k": k, "v": v}
                else:
                    h, nc = apply_mamba_step(cfg, sub["mamba"], h, c)
                    out[f"pos{i}"] = nc
            x = residual_add(cfg, x, h)
            if ffn != "none":
                h = basic.apply_norm(cfg, sub["ffn_norm"], x)
                if ffn == "moe":
                    h, _ = apply_moe(cfg, sub["moe"], h)
                else:
                    h = basic.apply_ffn(cfg, sub["ffn"], h)
                x = residual_add(cfg, x, h)
        return x, out

    x, out = jax.lax.scan(scan_body, x, (params["blocks"], cache))
    new_cache = dict(out)
    attn = [f"pos{i}" for i, (mixer, _) in enumerate(pattern) if mixer == "attn"]
    if attn:
        new_cache.update(write_kv({key: cache[key] for key in attn},
                                  {key: out[key] for key in attn}, position))
    x = basic.apply_norm(cfg, params["final_norm"], x)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = basic.unembed(cfg, head, x)
    return logits, new_cache


def apply_mamba_with_state(cfg, params: Dict, x: jax.Array):
    """Like apply_mamba but also returns the decode cache (for prefill)."""
    # Re-run the input path to extract the final conv window + ssm state.
    from repro.models.layers.ssm import _causal_conv, _in_proj, ssd_chunked

    cdt = jnp.dtype(cfg.compute_dtype)
    bsz, s, _ = x.shape
    di, g, n, h, p = (
        cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim,
    )
    z, xbc_raw, dt_raw = _in_proj(cfg, params, x.astype(cdt), cdt)
    xbc = _causal_conv(xbc_raw, params["conv_w"], params["conv_b"])
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(cdt)

    xs = xbc[..., :di].reshape(bsz, s, h, p)
    b_mat = xbc[..., di : di + g * n].reshape(bsz, s, g, n)
    c_mat = xbc[..., di + g * n :].reshape(bsz, s, g, n)
    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :]
    )
    a = -jnp.exp(params["a_log"])
    with jax.named_scope("ssd"):
        y, final_state = ssd_chunked(xs, dt, a, b_mat, c_mat, cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = y.reshape(bsz, s, di)

    from repro.models.layers.ssm import _gated_rmsnorm

    y = _gated_rmsnorm(y, z, params["norm_scale"], cfg.norm_eps).astype(cdt)
    out = y @ params["out_proj"].astype(cdt)

    conv_window = xbc_raw[:, -(cfg.ssm_conv - 1):, :].astype(jnp.float32)
    cache = {"conv": conv_window, "ssm": final_state}
    return out, cache

