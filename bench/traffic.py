"""Seeded request schedules from a traffic mix file (``bench/mixes/<name>.json``).

One general generator reads every mix. A mix fixes the shape of its traffic
and names the public trace its numbers come from (``source``):

* ``loop``: ``open`` (arrivals on a schedule, Poisson at ``rate_rps``) or
  ``closed`` (``clients_per_slot`` clients per slot of the deployment, each
  sending its next request as soon as the last one is done);
* ``prompt``: log-normal lengths (``median``, ``sigma``), rounded *up* to
  ``quantum`` and clipped to ``[min, max]``, so every length lies on the
  mix's grid and set-up can compile each one;
* ``output``: log-normal new-token counts, rounded and clipped;
* ``tags``: the share of each policy tag (``untagged`` sends none);
* ``ramp_s``: seconds of traffic before the measured window opens.

The run seed draws everything: sizes, gaps, tags and prompt tokens. It
draws sizes and gaps stratified, one sample from each of ``BLOCK`` equal
slices of each distribution in every consecutive block of ``BLOCK``
requests, and gives each block the mix's tag shares exactly, in an order
of its own for each quantity. So each seed sends sizes no other seed
sends, while every stretch of a run (the ramp, the window's edges) holds
about the same work whatever the seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from statistics import NormalDist
from typing import Dict, List, Optional, Sequence

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "mixes"
#: Requests whose draws are stratified together. About a second of the
#: open-loop rates here: one set of sizes shuffled over a whole run moved
#: the tokens finished inside the window by 5-7% from seed to seed, and
#: blocks keep each stretch of a run at about the same work.
BLOCK = 16


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the client will send it."""

    due: float                    # seconds after the traffic starts (open loop)
    prompt_len: int
    max_new_tokens: int
    tag: Optional[str]
    tokens: np.ndarray


def load_mix(name: str) -> Dict:
    with open(MIX_DIR / f"{name}.json") as fh:
        mix = json.load(fh)
    if mix["name"] != name:
        raise ValueError(f"mix file {name}.json names itself {mix['name']!r}")
    return mix


def length_grid(prompt: Dict) -> List[int]:
    """Every prompt length the mix can draw."""
    q = prompt["quantum"]
    lo = math.ceil(prompt["min"] / q) * q
    return list(range(lo, prompt["max"] + 1, q))


def stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniforms in [0, 1): each consecutive block of ``BLOCK`` holds
    one from each of its ``BLOCK`` equal slices, in a random order."""
    out = np.empty(n)
    for start in range(0, n, BLOCK):
        b = min(BLOCK, n - start)
        out[start:start + b] = (rng.permutation(b) + rng.random(b)) / b
    return out


def _lognormal(u: np.ndarray, dist: Dict) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(min(max(x, 1e-12), 1 - 1e-12)) for x in u])
    return np.exp(math.log(dist["median"]) + dist["sigma"] * z)


def prompt_lengths(u: np.ndarray, prompt: Dict) -> np.ndarray:
    q = prompt["quantum"]
    raw = np.ceil(_lognormal(u, prompt) / q) * q
    grid = length_grid(prompt)
    return np.clip(raw, grid[0], grid[-1]).astype(np.int64)


def output_lengths(u: np.ndarray, output: Dict) -> np.ndarray:
    raw = np.rint(_lognormal(u, output))
    return np.clip(raw, output["min"], output["max"]).astype(np.int64)


def tag_list(tags: Dict[str, float], n: int) -> List[Optional[str]]:
    """``n`` tags in the mix's shares, by largest remainder (exact counts)."""
    names = list(tags)
    want = np.array([tags[t] for t in names], np.float64) * n
    counts = np.floor(want).astype(int)
    for i in np.argsort(-(want - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    out: List[Optional[str]] = []
    for name, count in zip(names, counts):
        out += [None if name == "untagged" else name] * int(count)
    return out


def drawn_tags(rng: np.random.Generator, tags: Dict[str, float],
               n: int) -> List[Optional[str]]:
    """Each consecutive block of ``BLOCK`` requests holds the mix's tag
    shares exactly, in a random order."""
    out: List[Optional[str]] = []
    for start in range(0, n, BLOCK):
        block = tag_list(tags, min(BLOCK, n - start))
        out += [block[i] for i in rng.permutation(len(block))]
    return out


def request_count(mix: Dict, seconds: float, slots: int) -> int:
    """How many requests a run of ``seconds`` (plus the ramp) draws."""
    span = seconds + mix.get("ramp_s", 0.0)
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_rps"] * span)))
    clients = mix["clients_per_slot"] * slots
    return clients * mix["requests_per_client"]


def schedule(mix: Dict, seed: int, seconds: float, *, slots: int,
             vocab_size: int) -> List[Spec]:
    """The run's requests, in the order the client sends them.

    Open loop: ``due`` times fill ``[0, ramp_s + seconds)`` at exactly the
    mix's rate, with exponential gaps. Closed loop: ``due`` is 0 and the
    harness stamps each request when a client sends it.
    """
    n = request_count(mix, seconds, slots)
    rng = np.random.default_rng([seed, 0x5EED])
    lengths = prompt_lengths(stratified(rng, n), mix["prompt"])
    outputs = output_lengths(stratified(rng, n), mix["output"])
    tags = drawn_tags(rng, mix["tags"], n)
    gaps = -np.log1p(-stratified(rng, n + 1))
    if mix["loop"] == "open":
        span = seconds + mix.get("ramp_s", 0.0)
        due = span * np.cumsum(gaps)[:n] / gaps.sum()
    else:
        due = np.zeros(n)
    return [
        Spec(
            due=float(due[i]),
            prompt_len=int(lengths[i]),
            max_new_tokens=int(outputs[i]),
            tag=tags[i],
            tokens=rng.integers(1, vocab_size, size=int(lengths[i]), dtype=np.int32),
        )
        for i in range(n)
    ]


def longest_request(mix: Dict) -> int:
    """Prompt plus new tokens of the longest request the mix can draw."""
    return length_grid(mix["prompt"])[-1] + mix["output"]["max"]


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of all ``values`` (q in 0..100)."""
    if not values:
        return float("nan")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])
