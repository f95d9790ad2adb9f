"""The Llama family (Llama, SmolLM, Qwen1.5/Qwen2): pre-norm decoder blocks
of grouped-query attention with rotary embeddings and optional Q/K/V bias,
and a SwiGLU feed-forward, sized by the Hugging Face keys of the
configuration file.

Weights are float32 (the dtype the program stores and serves). The served
program is handed them in its own parameter layout (``program_tree``), and
the reference (``bench/reference/llama.py``) rebuilds them from the same
seed in the flat layout below, taking nothing from the program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import COMPUTE_BYTES
from bench.families import seed_key

#: Spread of the norm scales around 1 and of the Q/K/V biases around 0, so
#: that neither is a no-op a wrong program could skip unseen. A wider bias
#: spread makes random weights predict one token at every position (at
#: 0.5, qwen1.5-0.5b's argmax is one token at 59% of 384 positions, and
#: every served token on the chip was the reference's argmax), which no
#: comparison of logits can tell from a broken decode.
NORM_SPREAD = 0.1
BIAS_SPREAD = 0.02

#: The keys of a configuration file the reference reads.
MODEL_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings", "attention_bias",
              "qkv_bias")


def sizes(c: Dict) -> Dict[str, int]:
    heads = c["num_attention_heads"]
    return dict(
        layers=c["num_hidden_layers"],
        d=c["hidden_size"],
        heads=heads,
        kv=c["num_key_value_heads"],
        hd=c.get("head_dim") or c["hidden_size"] // heads,
        ff=c["intermediate_size"],
        vocab=c["vocab_size"],
        bias=bool(c.get("attention_bias") or c.get("qkv_bias")),
        tied=bool(c["tie_word_embeddings"]),
    )


def model_keys(c: Dict) -> Dict:
    return {k: c[k] for k in MODEL_KEYS if k in c}


def program_config(c: Dict):
    """The program's ``ModelConfig`` with every size, dtype and constant
    taken from the configuration file."""
    from repro.configs import get_config

    s = sizes(c)
    return dataclasses.replace(
        get_config(c["program_arch"]),
        n_layers=s["layers"], d_model=s["d"], n_heads=s["heads"],
        n_kv_heads=s["kv"], head_dim=s["hd"], d_ff=s["ff"],
        vocab_size=s["vocab"], qkv_bias=s["bias"], tie_embeddings=s["tied"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"],
    )


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _maker(layers: int, d: int, heads: int, kv: int, hd: int, ff: int,
           vocab: int, bias: bool, tied: bool):
    def make(key):
        names = ["embed", "head", "attn_norm", "ffn_norm", "final_norm", "wq",
                 "wk", "wv", "wo", "w_gate", "w_up", "w_down", "bq", "bk", "bv"]
        keys = dict(zip(names, jax.random.split(key, len(names))))

        def dense(name, shape, fan_in):
            return jax.random.normal(keys[name], shape, jnp.float32) / np.sqrt(fan_in)

        def norm(name, shape):
            return 1.0 + NORM_SPREAD * jax.random.normal(keys[name], shape, jnp.float32)

        w = {
            "embed": dense("embed", (vocab, d), d),
            "attn_norm": norm("attn_norm", (layers, d)),
            "ffn_norm": norm("ffn_norm", (layers, d)),
            "final_norm": norm("final_norm", (d,)),
            "wq": dense("wq", (layers, d, heads * hd), d),
            "wk": dense("wk", (layers, d, kv * hd), d),
            "wv": dense("wv", (layers, d, kv * hd), d),
            "wo": dense("wo", (layers, heads * hd, d), heads * hd),
            "w_gate": dense("w_gate", (layers, d, ff), d),
            "w_up": dense("w_up", (layers, d, ff), d),
            "w_down": dense("w_down", (layers, ff, d), ff),
        }
        if not tied:
            w["head"] = dense("head", (vocab, d), d)
        if bias:
            for name, width in (("bq", heads * hd), ("bk", kv * hd), ("bv", kv * hd)):
                w[name] = BIAS_SPREAD * jax.random.normal(keys[name], (layers, width),
                                                          jnp.float32)
        return w

    return jax.jit(make)


def make_weights(c: Dict, seed: int) -> Dict[str, jax.Array]:
    """The flat weights of configuration ``c`` for ``seed``, on the default
    device."""
    s = sizes(c)
    make = _maker(s["layers"], s["d"], s["heads"], s["kv"], s["hd"], s["ff"],
                  s["vocab"], s["bias"], s["tied"])
    return make(seed_key(seed))


def program_tree(w: Dict[str, jax.Array]) -> Dict:
    """The same arrays nested as the served program's decoder-only
    ``params`` (one layer per scan step, ``pos0``); no copies."""
    attn = {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv") if k in w}
    tree = {
        "embed": {"table": w["embed"]},
        "blocks": {"pos0": {
            "mixer_norm": {"scale": w["attn_norm"]},
            "attn": attn,
            "ffn_norm": {"scale": w["ffn_norm"]},
            "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
        "final_norm": {"scale": w["final_norm"]},
    }
    if "head" in w:
        tree["lm_head"] = {"table": w["head"]}
    return tree


# ---------------------------------------------------------------------------
# Work counts
# ---------------------------------------------------------------------------


def layer_matmul_params(c: Dict) -> int:
    """Weights one layer multiplies by: Q, K, V, O and the SwiGLU FFN."""
    s = sizes(c)
    d, h, kv, hd = s["d"], s["heads"], s["kv"], s["hd"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * s["ff"]


def weight_count(c: Dict) -> int:
    """Every parameter a forward pass reads: layers, norms, embedding (and
    the output head where it is not tied)."""
    s = sizes(c)
    per_layer = layer_matmul_params(c) + 2 * s["d"]
    if s["bias"]:
        per_layer += (s["heads"] + 2 * s["kv"]) * s["hd"]
    table = s["vocab"] * s["d"]
    return s["layers"] * per_layer + s["d"] + table * (1 if s["tied"] else 2)


def kv_bytes_per_token(c: Dict) -> int:
    s = sizes(c)
    return s["layers"] * 2 * s["kv"] * s["hd"] * COMPUTE_BYTES


def prefill_work(c: Dict, prompt_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prompt of ``prompt_len`` tokens, with the
    logits of its last position only."""
    s = sizes(c)
    n = prompt_len
    attn = 2 * s["heads"] * s["hd"] * n * (n + 1)           # QK^T and PV, causal
    flops = s["layers"] * (2 * n * layer_matmul_params(c) + attn)
    flops += 2 * s["d"] * s["vocab"]
    nbytes = COMPUTE_BYTES * weight_count(c) + n * kv_bytes_per_token(c)
    nbytes += 4 * s["vocab"]                                 # float32 logits out
    return float(flops), float(nbytes)


def decode_work(c: Dict, positions: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step over the active slots, each writing
    its new token at ``position`` after ``position`` cached ones."""
    s = sizes(c)
    b = len(positions)
    if b == 0:
        return 0.0, 0.0
    live = sum(int(p) + 1 for p in positions)
    attn = 4 * s["heads"] * s["hd"] * live
    flops = b * (s["layers"] * 2 * layer_matmul_params(c) + 2 * s["d"] * s["vocab"])
    flops += s["layers"] * attn
    kv_read = sum(int(p) for p in positions) * kv_bytes_per_token(c)
    kv_write = b * kv_bytes_per_token(c)
    nbytes = COMPUTE_BYTES * weight_count(c) + kv_read + kv_write
    nbytes += 4 * b * s["vocab"]
    return float(flops), float(nbytes)
