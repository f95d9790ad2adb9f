"""The chip's peaks, and the rules every family's work counts follow.

A family's ``prefill_work`` and ``decode_work`` (``bench/families/``)
count what the algorithm needs for one served call, from the model's sizes
in its configuration file, not what today's programs do:

* weights are read once per call at the compute dtype, ``COMPUTE_BYTES``
  each, though the program stores them in float32;
* cached state (K/V) is read only over each active slot's live positions
  and written only for the new tokens, ``COMPUTE_BYTES`` per element;
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
from typing import Dict, Tuple

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


def least_seconds(work: Tuple[float, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of FLOPs over peak
    FLOP/s and bytes over peak bytes/s."""
    flops, nbytes = work
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
