"""Plain float32 forward pass of a Llama-family decoder (Llama, SmolLM,
Qwen1.5/Qwen2), written from the published architecture and independent of
the served program.

Pre-norm blocks: RMSNorm, multi-head attention with grouped K/V heads and
rotary embeddings (Hugging Face ``rotate_half`` convention, base
``rope_theta``), optional Q/K/V bias, a residual add, RMSNorm, a SwiGLU
feed-forward, a residual add; a final RMSNorm and the (tied) embedding as
output head. Every matrix product runs in float32 at ``HIGHEST`` precision.

``fp8=True`` is the control: every matrix product's operands are rounded to
float8 e4m3 with one absmax scale per tensor (accumulating in float32), the
precision one step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.llama import sizes

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec: str, a: jax.Array, b: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def hidden_states(c: Dict, w: Dict[str, jax.Array], tokens: jax.Array, *,
                  fp8: bool = False) -> jax.Array:
    """Final-norm hidden states ``[S, d]`` of one sequence ``tokens [S]``."""
    s = sizes(c)
    n, heads, kv, hd = tokens.shape[0], s["heads"], s["kv"], s["hd"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]

    inv_freq = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    angles = np.concatenate([angles, angles], axis=-1)
    cos = jnp.asarray(np.cos(angles), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angles), jnp.float32)[:, None, :]
    causal = jnp.tril(jnp.ones((n, n), bool))

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], eps)
        q = _einsum("sd,de->se", h, lw["wq"], fp8)
        k = _einsum("sd,de->se", h, lw["wk"], fp8)
        v = _einsum("sd,de->se", h, lw["wv"], fp8)
        if "bq" in lw:
            q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
        q = q.reshape(n, heads, hd)
        k = k.reshape(n, kv, hd)
        v = v.reshape(n, kv, hd)
        q = q * cos + _rotate_half(q) * sin
        k = k * cos + _rotate_half(k) * sin
        k = jnp.repeat(k, heads // kv, axis=1)
        v = jnp.repeat(v, heads // kv, axis=1)
        scores = _einsum("shd,thd->hst", q, k, fp8) / np.sqrt(hd)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = _einsum("hst,thd->shd", probs, v, fp8).reshape(n, heads * hd)
        x = x + _einsum("se,ed->sd", attn, lw["wo"], fp8)
        h = _rms(x, lw["ffn_norm"], eps)
        gate = _einsum("sd,df->sf", h, lw["w_gate"], fp8)
        up = _einsum("sd,df->sf", h, lw["w_up"], fp8)
        x = x + _einsum("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_down"], fp8)
        return x, None

    per_layer = {k: v for k, v in w.items()
                 if k not in ("embed", "head", "final_norm")}
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, per_layer)
    return _rms(x, w["final_norm"], eps)


def logits_at(c: Dict, w: Dict[str, jax.Array], hidden: jax.Array,
              positions: jax.Array, *, fp8: bool = False) -> jax.Array:
    """Logits ``[P, V]`` of the hidden states at ``positions``."""
    head = w["embed"] if c["tie_word_embeddings"] else w["head"]
    return _einsum("pd,vd->pv", hidden[positions], head, fp8)


@functools.lru_cache(maxsize=None)
def gap_program(c_items: Tuple, control: bool):
    """A jitted ``(w, tokens [L], positions [P], served [P]) -> gaps``.

    ``gaps[i]`` is how far below the reference's best logit at
    ``positions[i]`` the served token lies. With ``control`` it returns
    ``(gaps, control_gaps)``, where the second reads the token the fp8
    forward puts first in place of the served one.
    """
    c = dict(c_items)

    def run(w, tokens, positions, served):
        ref = logits_at(c, w, hidden_states(c, w, tokens), positions)
        best = jnp.max(ref, axis=-1)
        gaps = best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        if not control:
            return gaps
        low = logits_at(c, w, hidden_states(c, w, tokens, fp8=True), positions,
                        fp8=True)
        pick = jnp.argmax(low, axis=-1)
        return gaps, best - jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]

    return jax.jit(run)
