"""A family that only the tests know: a smoke-sized Mamba-2 + attention
hybrid of the program, serving as both its family module and its reference
module. Weights come from the seed through the program's own initializer,
and the program's own forward pass, in float32, stands in as the reference.
The work counts are two FLOPs and two bytes per parameter a token reads.

The tests put this module into ``sys.modules`` under the names the harness
looks up, so no file of the harness names it.
"""
import dataclasses
import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench.families import seed_key

NAME = "hybrid_smoke"
MODEL_KEYS = ("hidden_size", "num_hidden_layers", "vocab_size", "compute_dtype")


def sizes(c: Dict) -> Dict[str, int]:
    return dict(layers=c["num_hidden_layers"], d=c["hidden_size"], vocab=c["vocab_size"])


def model_keys(c: Dict) -> Dict:
    return {k: c[k] for k in MODEL_KEYS}


def program_config(c: Dict):
    from repro.models.config import ModelConfig

    s = sizes(c)
    return ModelConfig(
        name=c["name"], family="hybrid", n_layers=s["layers"], d_model=s["d"],
        n_heads=4, n_kv_heads=2, d_ff=2 * s["d"], vocab_size=s["vocab"],
        attn_every=2, attn_index=1, ssm_state=16, ssm_headdim=16, ssm_chunk=8,
        tie_embeddings=True, param_dtype="float32", compute_dtype=c["compute_dtype"])


@functools.lru_cache(maxsize=None)
def _init(cfg):
    from repro.models import Model

    return jax.jit(Model(cfg).init_params)


def make_weights(c: Dict, seed: int) -> Dict:
    cfg = program_config(c)
    return _init(cfg)(seed_key(seed))


def program_tree(w: Dict) -> Dict:
    return w


def _params(c: Dict) -> int:
    return program_config(c).param_count()


def prefill_work(c: Dict, n: int) -> Tuple[float, float]:
    return float(2 * n * _params(c)), float(2 * _params(c))


def decode_work(c: Dict, positions: Sequence[int]) -> Tuple[float, float]:
    if not positions:
        return 0.0, 0.0
    return float(2 * len(positions) * _params(c)), float(2 * _params(c))


@functools.lru_cache(maxsize=None)
def gap_program(c_items: Tuple, control: bool):
    """As ``bench/reference/llama.py``'s, with no control."""
    from repro.models import lm

    if control:
        raise NotImplementedError("the test family has no control")
    c = dict(c_items, name=NAME)
    cfg = dataclasses.replace(program_config(c), compute_dtype="float32")

    def run(w, tokens, positions, served):
        with jax.default_matmul_precision("highest"):
            logits, _ = lm.forward(cfg, w, tokens[None])
        ref = logits[0, positions]
        best = jnp.max(ref, axis=-1)
        return best - jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]

    return jax.jit(run)
