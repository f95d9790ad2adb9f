"""The Granite-4.0-H family (``granitemoehybrid``): Mamba-2 mixers and NoPE
grouped-query attention in a repeating pattern (``layer_types``), each
layer followed by a routed mixture of SwiGLU experts and a shared SwiGLU
expert, with the muP constants of Granite (embedding, residual, attention
and logits multipliers), sized by the Hugging Face keys of the
configuration file.

The configuration may hold one chip's share of an expert-parallel layer:
``num_local_experts`` experts from ``expert_first`` on, of the router's
``published["num_local_experts"]``.

Weights are bfloat16 (the configuration's ``param_dtype``), but for the
Mamba-2 ``A_log``, ``dt_bias`` and ``D``, which are float32 as the program
keeps them. The served program is handed them in its own layout
(``program_tree``); the reference (``bench/reference/granite.py``) reads
the per-layer layout below and takes nothing from the program.

Work counts follow ``bench/counts.py``, and split the total by mechanism
(``Work.parts``: ``mamba``, ``attn``, ``moe``, ``head``):

* every held expert's weights are read once a call (with 32 slots a step,
  an expert goes unhit with under 1% chance under even routing);
* routed FLOPs are counted at the even-routing expectation of
  ``n * k * held / E`` tokens a layer;
* the Mamba-2 state (and the conv window) is read and written once a
  decode step per active slot, at 4 bytes, and written once a prefill;
  its FLOPs are the recurrence's, 4 per state element a token (the
  chunked scan's extra intra-chunk work is left out);
* K/V is read over live positions and written for new tokens.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import COMPUTE_BYTES
from bench.families import seed_key

#: Spread of every norm scale around 1, and of the conv bias around 0.
#: The embedding table's spread is 1 / (embedding_multiplier * sqrt(d)),
#: so that the scaled embedding has the unit norm of the Llama family's.
#: At 1 / sqrt(d) the embedding, 12 times over, outweighs what every layer
#: adds, and the tied head makes a random model repeat its input token at
#: every position, which no comparison of logits can tell from a broken
#: decode.
NORM_SPREAD = 0.1
CONV_BIAS_SPREAD = 0.02
#: Q and K are drawn wider by (attention_multiplier * sqrt(head_dim))^-1/2
#: each (``_maker``), so that attention scores have the unit spread under
#: Granite's 1/128 that 1/sqrt(head_dim) gives the Llama family's. At the
#: plain spread the scores spread by 0.09 and every query attends almost
#: evenly, which no comparison of logits can tell from RoPE applied or a
#: K/V cache read at the wrong positions.
#: Bytes of one element of the Mamba-2 state and conv window.
STATE_BYTES = 4

#: The keys of a configuration file the reference reads.
MODEL_KEYS = (
    "num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "shared_intermediate_size", "num_local_experts",
    "num_experts_per_tok", "expert_first", "vocab_size", "rms_norm_eps",
    "tie_word_embeddings", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
    "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size", "embedding_multiplier",
    "residual_multiplier", "attention_multiplier", "logits_scaling",
)


class Work(tuple):
    """(FLOPs, bytes) of one call, and ``parts``: the same per mechanism."""

    def __new__(cls, parts: Dict[str, tuple]):
        flops = sum(f for f, _ in parts.values())
        nbytes = sum(b for _, b in parts.values())
        work = super().__new__(cls, (float(flops), float(nbytes)))
        work.parts = {k: (float(f), float(b)) for k, (f, b) in parts.items()}
        return work


def sizes(c: Dict) -> Dict:
    layers = c["num_hidden_layers"]
    types = tuple(c["layer_types"][:layers])
    attn = [i for i, t in enumerate(types) if t == "attention"]
    period = attn[1] - attn[0] if len(attn) > 1 else layers
    pattern = types[:period]
    if layers % period or types != pattern * (layers // period) or attn[:1] == []:
        raise ValueError(f"layer_types do not repeat one attention layer a period: {types}")
    d, heads = c["hidden_size"], c["num_attention_heads"]
    mh, mp = c["mamba_n_heads"], c["mamba_d_head"]
    if mh * mp != c["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head must be mamba_expand * hidden_size")
    return dict(
        layers=layers, types=types, period=period, attn_index=attn[0],
        d=d, heads=heads, kv=c["num_key_value_heads"], hd=d // heads,
        mh=mh, mp=mp, di=mh * mp, n=c["mamba_d_state"], g=c["mamba_n_groups"],
        conv=c["mamba_d_conv"], conv_dim=mh * mp + 2 * c["mamba_n_groups"] * c["mamba_d_state"],
        chunk=c["mamba_chunk_size"],
        experts=router_experts(c),
        held=c["num_local_experts"], first=c.get("expert_first", 0),
        k=c["num_experts_per_tok"], ff=c["intermediate_size"],
        shared=c["shared_intermediate_size"], vocab=c["vocab_size"],
        tied=bool(c["tie_word_embeddings"]),
    )


def router_experts(c: Dict) -> int:
    """The experts the router scores: the published count where the file
    holds a share of them (``model_keys`` carries it as ``router_experts``)."""
    if "router_experts" in c:
        return c["router_experts"]
    return c.get("published", {}).get("num_local_experts", c["num_local_experts"])


def model_keys(c: Dict) -> Dict:
    keys = {k: c[k] for k in MODEL_KEYS if k in c}
    keys["layer_types"] = tuple(c["layer_types"][: c["num_hidden_layers"]])
    keys["mamba_expand"] = c["mamba_expand"]
    keys["router_experts"] = router_experts(c)
    return keys


def program_config(c: Dict):
    """The program's ``ModelConfig`` with every size, dtype and constant
    taken from the configuration file."""
    from repro.configs import get_config

    s = sizes(c)
    if not s["tied"]:
        raise ValueError("the Granite family here ties its embeddings")
    return dataclasses.replace(
        get_config(c["program_arch"]),
        n_layers=s["layers"], d_model=s["d"], n_heads=s["heads"], n_kv_heads=s["kv"],
        head_dim=s["hd"], d_ff=s["ff"], vocab_size=s["vocab"], tie_embeddings=True,
        norm_eps=float(c["rms_norm_eps"]), pos_embedding="none",
        attn_every=s["period"], attn_index=s["attn_index"],
        ssm_state=s["n"], ssm_headdim=s["mp"], ssm_expand=c["mamba_expand"],
        ssm_conv=s["conv"], ssm_chunk=s["chunk"], ssm_groups=s["g"],
        moe_experts=s["experts"], moe_top_k=s["k"], moe_held=s["held"],
        moe_first=s["first"], moe_every=1, moe_shared_ff=s["shared"],
        embedding_multiplier=float(c["embedding_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        attn_scale=float(c["attention_multiplier"]),
        logits_scaling=float(c["logits_scaling"]),
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
    )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _maker(c_items: tuple, dtype: str):
    c = dict(c_items)
    s = sizes(c)
    d, p = s["d"], s["layers"] // s["period"]
    dt = jnp.dtype(dtype)

    def make(key):
        k_embed, k_final, k_layers = jax.random.split(key, 3)

        def dense(key, shape, fan_in):
            w = jax.random.normal(key, (p,) + shape, jnp.float32) / np.sqrt(fan_in)
            return w.astype(dt)

        def norm(key, width):
            w = 1.0 + NORM_SPREAD * jax.random.normal(key, (p, width), jnp.float32)
            return w.astype(dt)

        layers = []
        for i, kind in enumerate(s["types"][: s["period"]]):
            keys = iter(jax.random.split(jax.random.fold_in(k_layers, i), 20))
            lw = {"input_norm": norm(next(keys), d), "post_norm": norm(next(keys), d)}
            if kind == "attention":
                hd = s["hd"]
                qk = (c["attention_multiplier"] * np.sqrt(hd)) ** -0.5
                lw.update(wq=dense(next(keys), (d, s["heads"] * hd), d / qk**2),
                          wk=dense(next(keys), (d, s["kv"] * hd), d / qk**2),
                          wv=dense(next(keys), (d, s["kv"] * hd), d),
                          wo=dense(next(keys), (s["heads"] * hd, d), s["heads"] * hd))
            else:
                mh, di, cd, w = s["mh"], s["di"], s["conv_dim"], s["conv"]
                dt_init = jnp.exp(jax.random.uniform(
                    next(keys), (p, mh), jnp.float32, np.log(1e-3), np.log(1e-1)))
                lw.update(
                    in_z=dense(next(keys), (d, di), d),
                    in_xbc=dense(next(keys), (d, cd), d),
                    in_dt=dense(next(keys), (d, mh), d),
                    conv_w=dense(next(keys), (w, cd), w),
                    conv_b=(CONV_BIAS_SPREAD * jax.random.normal(
                        next(keys), (p, cd), jnp.float32)).astype(dt),
                    a_log=jnp.broadcast_to(
                        jnp.log(jnp.arange(1, mh + 1, dtype=jnp.float32)), (p, mh)),
                    dt_bias=dt_init + jnp.log(-jnp.expm1(-dt_init)),
                    d_skip=jnp.ones((p, mh), jnp.float32),
                    gate_norm=norm(next(keys), di),
                    out=dense(next(keys), (di, d), di),
                )
            held, ff, sf = s["held"], s["ff"], s["shared"]
            lw.update(
                router=dense(next(keys), (d, s["experts"]), d),
                w_gate=dense(next(keys), (held, d, ff), d),
                w_up=dense(next(keys), (held, d, ff), d),
                w_down=dense(next(keys), (held, ff, d), ff),
                shared_gate=dense(next(keys), (d, sf), d),
                shared_up=dense(next(keys), (d, sf), d),
                shared_down=dense(next(keys), (sf, d), sf),
            )
            layers.append(lw)
        embed = jax.random.normal(k_embed, (s["vocab"], d), jnp.float32) / (
            np.sqrt(d) * c["embedding_multiplier"])
        final = 1.0 + NORM_SPREAD * jax.random.normal(k_final, (d,), jnp.float32)
        return {"embed": embed.astype(dt), "final_norm": final.astype(dt), "layers": layers}

    return jax.jit(make)


def make_weights(c: Dict, seed: int) -> Dict:
    """The weights of configuration ``c`` for ``seed``, on the default
    device: ``layers[i]`` holds period position ``i``, each array with a
    leading axis over the periods."""
    make = _maker(tuple(sorted(model_keys(c).items())), c["param_dtype"])
    return make(seed_key(seed))


def program_tree(w: Dict) -> Dict:
    """The same arrays nested as the served program's hybrid ``params``;
    no copies."""
    blocks = {}
    for i, lw in enumerate(w["layers"]):
        sub = {"mixer_norm": {"scale": lw["input_norm"]},
               "ffn_norm": {"scale": lw["post_norm"]},
               "moe": {"router": lw["router"], "w_gate": lw["w_gate"],
                       "w_up": lw["w_up"], "w_down": lw["w_down"],
                       "shared": {"w_gate": lw["shared_gate"], "w_up": lw["shared_up"],
                                  "w_down": lw["shared_down"]}}}
        if "wq" in lw:
            sub["attn"] = {k: lw[k] for k in ("wq", "wk", "wv", "wo")}
        else:
            sub["mamba"] = {
                "in_proj_z": lw["in_z"], "in_proj_xbc": lw["in_xbc"],
                "in_proj_dt": lw["in_dt"], "conv_w": lw["conv_w"],
                "conv_b": lw["conv_b"], "a_log": lw["a_log"], "d_skip": lw["d_skip"],
                "dt_bias": lw["dt_bias"], "norm_scale": lw["gate_norm"],
                "out_proj": lw["out"]}
        blocks[f"pos{i}"] = sub
    return {"embed": {"table": w["embed"]}, "blocks": blocks,
            "final_norm": {"scale": w["final_norm"]}}


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------


def _mamba_matmul(s: Dict) -> int:
    d, di = s["d"], s["di"]
    return d * (di + s["conv_dim"] + s["mh"]) + di * d


def _attn_matmul(s: Dict) -> int:
    d, h, kv, hd = s["d"], s["heads"], s["kv"], s["hd"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def _expert(s: Dict) -> int:
    return 3 * s["d"] * s["ff"]


def part_weights(c: Dict) -> Dict[str, int]:
    """Parameters of each mechanism, over every layer: norms go with the
    mixer or expert layer they feed, the final norm with the head."""
    s = sizes(c)
    d = s["d"]
    n_attn = s["types"].count("attention")
    n_mamba = s["layers"] - n_attn
    mamba = _mamba_matmul(s) + (s["conv"] + 1) * s["conv_dim"] + 3 * s["mh"] + s["di"] + d
    moe = d * s["experts"] + s["held"] * _expert(s) + 3 * d * s["shared"] + d
    return {"mamba": n_mamba * mamba, "attn": n_attn * (_attn_matmul(s) + d),
            "moe": s["layers"] * moe,
            "head": s["vocab"] * d * (1 if s["tied"] else 2) + d}


def weight_count(c: Dict) -> int:
    """Every parameter a forward pass reads."""
    return sum(part_weights(c).values())


def _state_bytes(s: Dict) -> int:
    """The Mamba-2 state and conv window of one sequence in one layer."""
    return STATE_BYTES * (s["mh"] * s["mp"] * s["n"] + (s["conv"] - 1) * s["conv_dim"])


def _work(c: Dict, tokens: int, outputs: int, attn_flops: float, kv_read: int,
          state_bytes: float) -> Work:
    """Work of a call over ``tokens`` new tokens with logits at ``outputs``
    positions, given its attention-score FLOPs, K/V positions read, and
    bytes of Mamba-2 state moved a layer."""
    s = sizes(c)
    weights = part_weights(c)
    n_attn = s["types"].count("attention")
    n_mamba = s["layers"] - n_attn
    kv_token = 2 * s["kv"] * s["hd"] * COMPUTE_BYTES
    ssm = 4 * s["mh"] * s["mp"] * s["n"]
    routed = tokens * s["k"] * s["held"] / s["experts"]
    moe_flops = s["layers"] * (
        2 * tokens * (s["d"] * s["experts"] + 3 * s["d"] * s["shared"])
        + 2 * routed * _expert(s))
    return Work({
        "mamba": (n_mamba * tokens * (2 * _mamba_matmul(s) + ssm),
                  COMPUTE_BYTES * weights["mamba"] + n_mamba * state_bytes),
        "attn": (n_attn * (2 * tokens * _attn_matmul(s) + attn_flops),
                 COMPUTE_BYTES * weights["attn"]
                 + n_attn * kv_token * (kv_read + tokens)),
        "moe": (moe_flops, COMPUTE_BYTES * weights["moe"]),
        "head": (2 * outputs * s["d"] * s["vocab"],
                 COMPUTE_BYTES * weights["head"] + 4 * outputs * s["vocab"]),
    })


def prefill_work(c: Dict, prompt_len: int) -> Work:
    """(FLOPs, bytes) of one prompt of ``prompt_len`` tokens, with the
    logits of its last position only."""
    s = sizes(c)
    n = prompt_len
    attn = 2 * s["heads"] * s["hd"] * n * (n + 1)            # QK^T and PV, causal
    return _work(c, n, 1, attn, 0, _state_bytes(s))


def decode_work(c: Dict, positions: Sequence[int]) -> Work:
    """(FLOPs, bytes) of one decode step over the active slots, each writing
    its new token at ``position`` after ``position`` cached ones."""
    s = sizes(c)
    b = len(positions)
    if b == 0:
        return Work({})
    live = sum(int(p) + 1 for p in positions)
    attn = 4 * s["heads"] * s["hd"] * live
    return _work(c, b, b, attn, sum(int(p) for p in positions), 2 * b * _state_bytes(s))
