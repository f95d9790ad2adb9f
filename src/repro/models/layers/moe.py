"""Mixture-of-Experts FFN with top-k routing and dropless dispatch.

The router scores all ``moe_experts`` experts and keeps the top k. The
layer holds ``cfg.experts_held`` of them, from index ``moe_first`` on (one
chip's share of an expert-parallel layer, or all of them), and adds only
their part of the result: a pair routed to an expert held elsewhere adds
nothing here.

Dispatch is dropless. The (token, expert) pairs are sorted by held expert
and each expert's rows go through a grouped matmul
(``jax.lax.ragged_dot``); pairs routed elsewhere sort last, into no group.
So a token's output depends on its own routing only, whatever other tokens
share the batch. With ``cfg.use_kernels`` the Pallas ``moe_gmm`` kernel
runs the experts over an ``[E, T, d]`` buffer, which holds every pair: a
token takes an expert at most once.

A shared expert (``cfg.moe_shared_ff``), where the config has one, is a
dense SwiGLU added for every token. Named scopes ``moe/route``,
``moe/experts`` and ``moe/shared`` mark the parts in the device trace.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers.basic import _dtype, _init_linear, apply_ffn, init_ffn


def init_moe(cfg, rng: jax.Array) -> Dict:
    dtype = _dtype(cfg.param_dtype)
    e, d, f = cfg.experts_held, cfg.d_model, cfg.d_ff
    keys = jax.random.split(rng, 5)

    def expert_stack(key, d_in, d_out):
        scale = 1.0 / jnp.sqrt(jnp.float32(d_in))
        w = jax.random.normal(key, (e, d_in, d_out), dtype=jnp.float32) * scale
        return w.astype(dtype)

    params: Dict = {"router": _init_linear(keys[0], d, cfg.moe_experts, dtype)}
    if cfg.mlp_kind in ("swiglu", "geglu"):
        params["w_gate"] = expert_stack(keys[1], d, f)
        params["w_up"] = expert_stack(keys[2], d, f)
        params["w_down"] = expert_stack(keys[3], f, d)
    else:
        params["w_up"] = expert_stack(keys[1], d, f)
        params["w_down"] = expert_stack(keys[2], f, d)
    if cfg.moe_shared_ff:
        params["shared"] = init_ffn(cfg, keys[4], cfg.moe_shared_ff)
    return params


def route(
    cfg, params: Dict, x2d: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Router logits → (top-k expert ids [T,k], gates [T,k], aux loss).

    The gates are the softmax renormalised over the top k, which equals a
    softmax over the top-k logits.
    """
    logits = (x2d.astype(jnp.float32)) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # [T,E]
    gates, expert_ids = jax.lax.top_k(probs, cfg.moe_top_k)      # [T,k]
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # Load-balancing auxiliary loss (Switch-style: fraction-of-tokens ×
    # fraction-of-probability per expert).
    e = cfg.moe_experts
    one_hot = jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32)
    density = jnp.mean(one_hot, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * e
    return expert_ids, gates, aux


def apply_moe(cfg, params: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [..., d] → (out [..., d], aux loss scalar).

    Dispatch is **group-local**: tokens are split into G groups aligned
    with the data-parallel sharding (G = dp size at trace time, 1 on CPU),
    and each group sorts and gathers its own tokens — so the sort and the
    token gather never cross devices.
    """
    from repro.sharding.ctx import constrain, current_dp_size

    cdt = _dtype(cfg.compute_dtype)
    d = x.shape[-1]
    with jax.named_scope("moe"):
        x_flat = x.reshape(-1, d)
        g = current_dp_size()
        if g <= 1 or x_flat.shape[0] % g != 0:
            g = 1
        out, aux = _moe_groups(cfg, params, x_flat.reshape(g, -1, d))
        if g > 1:
            out = constrain(out, ("dp", None, None))
        out = out.reshape(x.shape).astype(cdt)
        if "shared" in params:
            with jax.named_scope("shared"):
                out = out + apply_ffn(cfg, params["shared"], x)
    return out, jnp.mean(aux).astype(jnp.float32)


def _moe_groups(cfg, params: Dict, xg: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Routing, dispatch, held experts and combine, each token group on its
    own. xg: [G, T, d]."""
    cdt = _dtype(cfg.compute_dtype)
    g, t, d = xg.shape
    k, held = cfg.moe_top_k, cfg.experts_held

    with jax.named_scope("route"):
        expert_ids, gates, aux = jax.vmap(lambda x2d: route(cfg, params, x2d))(xg)

    with jax.named_scope("experts"):
        # Sort each group's (token, expert) pairs by held expert; a pair
        # routed to an expert held elsewhere gets key ``held`` and sorts
        # last. No token has more than min(k, held) pairs here, so no later
        # row is held.
        local = expert_ids.reshape(g, t * k) - cfg.moe_first             # [G, T*k]
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, axis=-1, stable=True)[:, : t * min(k, held)]
        sorted_local = jnp.take_along_axis(local, order, axis=-1)
        sorted_token = order // k
        sizes = jax.vmap(lambda l: jnp.bincount(l, length=held + 1)[:held])(local)
        rows = jnp.take_along_axis(xg.astype(cdt), sorted_token[..., None], axis=1)

        if cfg.use_kernels:
            h = jax.vmap(lambda r, sl, sz: _experts_gmm(cfg, params, r, sl, sz, t))(
                rows, sorted_local, sizes)
        else:
            h = _expert_ffn(cfg, params, rows,
                            lambda a, w: _grouped_mm(a, w.astype(cdt), sizes))

        gate = jnp.take_along_axis(gates.reshape(g, t * k), order, axis=-1)
        weighted = jnp.where((sorted_local < held)[..., None],
                             h * gate[..., None].astype(cdt), 0)
        out = jax.vmap(lambda tok, w: jnp.zeros((t, d), cdt).at[tok].add(w))(
            sorted_token, weighted)
    return out, aux


#: A grouped matmul per token group: lhs [G, R, k] ragged over R, rhs
#: [G, E, k, n] grouped over E, batched over G.
_PER_GROUP = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([2], [2]), ([0], [0])),
    lhs_ragged_dimensions=[1], rhs_group_dimensions=[1])


def _grouped_mm(a: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """Each row of ``a [G, R, k]`` times its expert's slice of ``w [E, k,
    n]``, the rows of each group sorted by expert, ``sizes [G, E]`` of them
    each (``jax.lax.ragged_dot``). Several groups share the experts through
    a batch axis, since ``ragged_dot``'s vmap rule does not cover its
    gradient."""
    if a.shape[0] == 1:
        return jax.lax.ragged_dot(a[0], w, sizes[0])[None]
    rhs = jnp.broadcast_to(w, a.shape[:1] + w.shape)
    return jax.lax.ragged_dot_general(a, rhs, sizes, _PER_GROUP)


def _expert_ffn(cfg, params: Dict, x: jax.Array, mm: Callable) -> jax.Array:
    """The experts' FFN over grouped rows; ``mm(x, w)`` multiplies each
    row by its own expert's slice of the stacked ``w``."""
    if cfg.mlp_kind in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_kind == "swiglu" else jax.nn.gelu
        h = act(mm(x, params["w_gate"])) * mm(x, params["w_up"])
    elif cfg.mlp_kind == "squared_relu":
        h = jnp.square(jax.nn.relu(mm(x, params["w_up"])))
    else:
        h = jax.nn.gelu(mm(x, params["w_up"]))
    return mm(h, params["w_down"])


def _experts_gmm(cfg, params: Dict, rows: jax.Array, sorted_local: jax.Array,
                 sizes: jax.Array, t: int) -> jax.Array:
    """The held experts through the ``moe_gmm`` kernel: rows packed into an
    ``[E, T, d]`` buffer (expert-major, in sorted order) and read back."""
    from repro.kernels.ops import moe_ffn_gmm

    held, d = cfg.experts_held, rows.shape[-1]
    starts = jnp.cumsum(sizes) - sizes
    pos = jnp.arange(rows.shape[0]) - starts[jnp.minimum(sorted_local, held - 1)]
    slot = jnp.where(sorted_local < held, sorted_local * t + pos, held * t)
    buffer = jnp.zeros((held * t, d), rows.dtype).at[slot].set(rows, mode="drop")
    h = moe_ffn_gmm(cfg, params, buffer.reshape(held, t, d))
    return h.reshape(held * t, d)[jnp.minimum(slot, held * t - 1)]
