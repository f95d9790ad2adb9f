"""Seeded random weights for a Llama-family configuration, made on the device
in one jitted call, in float32 (the dtype the program stores and serves).

The benchmark owns the weights: the served program is handed them in its
own parameter layout (``program_tree``), and the reference rebuilds them
from the same seed in the flat layout below, taking nothing from the
program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import sizes

#: Spread of the norm scales around 1 and of the Q/K/V biases around 0, so
#: that neither is a no-op a wrong program could skip unseen. A wider bias
#: spread makes random weights predict one token at every position (at
#: 0.5, qwen1.5-0.5b's argmax is one token at 59% of 384 positions, and
#: every served token on the chip was the reference's argmax), which no
#: comparison of logits can tell from a broken decode.
NORM_SPREAD = 0.1
BIAS_SPREAD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole-number seed (64 bits and more)."""
    state = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    return jnp.asarray(state, dtype=jnp.uint32)


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
