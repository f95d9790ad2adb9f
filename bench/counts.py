"""The work the algorithm needs for one served call, and the chip's peaks.

Counts follow the model's sizes in its configuration file (Hugging Face
keys), not what today's programs do:

* weights are read once per call at the compute dtype, 2 bytes each, though
  the program stores them in float32;
* K/V are read only over each active slot's live positions and written only
  for the new tokens, 2 bytes per element;
* attention FLOPs are taken over live lengths, causal in prefill;
* a decode step counts active slots only;
* FLOPs are those of the matrix products (2 per multiply-add); norms, RoPE
  and softmax are left out, so the least time is a lower bound.

A program that stops copying the cache, reads bf16 weights or skips dead
positions then reads closer to 100%, never above it.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, Sequence, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
COMPUTE_BYTES = 2


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of one chip of ``device_kind``."""
    with open(PEAKS_FILE) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


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


def least_seconds(work: Tuple[float, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    flops, nbytes = work
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
