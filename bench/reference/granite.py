"""Plain float32 forward pass of a Granite-4.0-H decoder
(``GraniteMoeHybridForCausalLM``), written from the published architecture
and independent of the served program.

The embedding is scaled by ``embedding_multiplier``. Each layer is
pre-norm: RMSNorm, then a Mamba-2 mixer or a NoPE attention layer as
``layer_types`` says, added to the residual scaled by
``residual_multiplier``; RMSNorm, then the routed experts plus the shared
expert, added the same way. A final RMSNorm, the tied embedding as head,
and the logits divided by ``logits_scaling``.

* Mamba-2: one input projection to z, xBC and dt; a causal depthwise conv
  of width ``mamba_d_conv`` with bias over xBC and SiLU; dt = softplus(dt +
  dt_bias), A = -exp(A_log); then the **sequential recurrence**, one
  position at a time, h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t and y_t =
  C_t h_t + D x_t (heads share B and C within a group); the gated RMSNorm
  of y * silu(z); the output projection.
* Attention: grouped K/V heads, no position embedding, scores scaled by
  ``attention_multiplier``, causal, computed in blocks of queries so that
  a few thousand positions fit.
* Experts: router logits over all ``router_experts``, the top
  ``num_experts_per_tok``, gates a softmax over those logits; the experts
  held here (``num_local_experts`` from ``expert_first``) each add
  gate * W_down(silu(W_gate x) * W_up x); the others add nothing. The
  shared expert is the same SwiGLU, ungated.

Every matrix product runs in float32 at ``HIGHEST`` precision. ``fp8=True``
is the control: every matrix product's operands (projections, attention,
experts, router, head) are rounded to float8 e4m3 with one absmax scale per
tensor, the precision one step below the bfloat16 the configuration
computes in; the recurrence itself stays float32.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.families.granite import sizes

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
#: Queries per block of the attention.
QUERY_BLOCK = 512


def _fp8(x: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(spec: str, a: jax.Array, b: jax.Array, fp8: bool) -> jax.Array:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def mamba(c: Dict, lw: Dict, x: jax.Array, fp8: bool) -> jax.Array:
    """Mamba-2 mixer over one sequence ``x [S, d]``."""
    s = sizes(c)
    n_pos = x.shape[0]
    mh, mp, n, g, w = s["mh"], s["mp"], s["n"], s["g"], s["conv"]
    z = _einsum("sd,de->se", x, lw["in_z"], fp8)
    xbc = _einsum("sd,de->se", x, lw["in_xbc"], fp8)
    dt = _einsum("sd,dh->sh", x, lw["in_dt"], fp8)

    padded = jnp.concatenate([jnp.zeros((w - 1, xbc.shape[1]), jnp.float32), xbc])
    conv_w = lw["conv_w"].astype(jnp.float32)
    conv = sum(padded[j:j + n_pos] * conv_w[j] for j in range(w))
    xbc = jax.nn.silu(conv + lw["conv_b"].astype(jnp.float32))
    xs = xbc[:, : s["di"]].reshape(n_pos, mh, mp)
    b_mat = jnp.repeat(xbc[:, s["di"]: s["di"] + g * n].reshape(n_pos, g, n), mh // g, axis=1)
    c_mat = jnp.repeat(xbc[:, s["di"] + g * n:].reshape(n_pos, g, n), mh // g, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    a = -jnp.exp(lw["a_log"])

    def step(h, inputs):
        x_t, b_t, c_t, dt_t = inputs                    # [H,P], [H,N], [H,N], [H]
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", h, c_t, precision=HIGHEST)
        return h, y

    _, y = jax.lax.scan(step, jnp.zeros((mh, mp, n), jnp.float32), (xs, b_mat, c_mat, dt))
    y = (y + lw["d_skip"][:, None] * xs).reshape(n_pos, s["di"])
    y = _rms(y * jax.nn.silu(z), lw["gate_norm"], c["rms_norm_eps"])
    return _einsum("se,ed->sd", y, lw["out"], fp8)


def attention(c: Dict, lw: Dict, x: jax.Array, fp8: bool) -> jax.Array:
    """Causal NoPE grouped-query attention over ``x [S, d]``, a block of
    queries at a time."""
    s = sizes(c)
    n_pos, heads, kv, hd = x.shape[0], s["heads"], s["kv"], s["hd"]
    q = _einsum("sd,de->se", x, lw["wq"], fp8).reshape(n_pos, heads, hd)
    k = _einsum("sd,de->se", x, lw["wk"], fp8).reshape(n_pos, kv, hd)
    v = _einsum("sd,de->se", x, lw["wv"], fp8).reshape(n_pos, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    block = QUERY_BLOCK if n_pos % QUERY_BLOCK == 0 else n_pos

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block)
        scores = _einsum("qhd,thd->hqt", qb, k, fp8) * c["attention_multiplier"]
        causal = start + jnp.arange(block)[:, None] >= jnp.arange(n_pos)[None, :]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return _einsum("hqt,thd->qhd", probs, v, fp8)

    out = jax.lax.map(one, jnp.arange(0, n_pos, block)).reshape(n_pos, heads * hd)
    return _einsum("se,ed->sd", out, lw["wo"], fp8)


def experts(c: Dict, lw: Dict, x: jax.Array, fp8: bool) -> jax.Array:
    """The held routed experts and the shared expert over ``x [S, d]``."""
    s = sizes(c)
    logits = _einsum("sd,de->se", x, lw["router"], fp8)
    top, ids = jax.lax.top_k(logits, s["k"])
    gates = jax.nn.softmax(top, axis=-1)
    held = s["first"] + jnp.arange(s["held"])
    weight = jnp.sum(jnp.where(ids[:, None, :] == held[None, :, None],
                               gates[:, None, :], 0.0), axis=-1)      # [S, held]
    gate = _einsum("sd,edf->esf", x, lw["w_gate"], fp8)
    up = _einsum("sd,edf->esf", x, lw["w_up"], fp8)
    out = _einsum("esf,efd->esd", jax.nn.silu(gate) * up, lw["w_down"], fp8)
    routed = jnp.einsum("esd,se->sd", out, weight, precision=HIGHEST)
    shared = jax.nn.silu(_einsum("sd,df->sf", x, lw["shared_gate"], fp8)) \
        * _einsum("sd,df->sf", x, lw["shared_up"], fp8)
    return routed + _einsum("sf,fd->sd", shared, lw["shared_down"], fp8)


def hidden_states(c: Dict, w: Dict, tokens: jax.Array, *, fp8: bool = False) -> jax.Array:
    """Final-norm hidden states ``[S, d]`` of one sequence ``tokens [S]``."""
    s = sizes(c)
    eps, res = c["rms_norm_eps"], c["residual_multiplier"]
    x = w["embed"][tokens].astype(jnp.float32) * c["embedding_multiplier"]
    for layer, kind in enumerate(s["types"]):
        period, i = divmod(layer, s["period"])
        lw = {k: v[period] for k, v in w["layers"][i].items()}
        h = _rms(x, lw["input_norm"], eps)
        h = attention(c, lw, h, fp8) if kind == "attention" else mamba(c, lw, h, fp8)
        x = x + res * h
        x = x + res * experts(c, lw, _rms(x, lw["post_norm"], eps), fp8)
    return _rms(x, w["final_norm"], eps)


def logits_at(c: Dict, w: Dict, hidden: jax.Array, positions: jax.Array, *,
              fp8: bool = False) -> jax.Array:
    """Logits ``[P, V]`` of the hidden states at ``positions``."""
    return _einsum("pd,vd->pv", hidden[positions], w["embed"], fp8) / c["logits_scaling"]


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

