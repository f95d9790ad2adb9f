"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Builds a zoned edge/cloud replica deployment of one model at its published
widths, loads a tAPP script (file or the default below), submits a
synthetic request mix, and reports placements, latency in ticks, and queue
wait, time to first token and latency in ms from each request's own times.
``--reduced`` swaps in the small same-family config, for CPU runs only.

The functions here are the one serving path: ``chip_smoke.py`` and the
tests build and drive their engines through them.
"""
from __future__ import annotations

import argparse
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.core.scheduler.topology import DistributionPolicy
from repro.launch.compile_cache import use_compile_cache
from repro.models import Model
from repro.models.config import ModelConfig
from repro.runtime.serve_engine import Replica, Request, ServingEngine

DEFAULT_SCRIPT = """
- default:
  - workers:
    - set:
    strategy: platform
    invalidate: overload
- interactive:
  - workers:
    - set: edge
    strategy: random
    invalidate: capacity_used 75%
  - workers:
    - set: cloud
  followup: default
- batch:
  - controller: CloudCtl
    workers:
    - set: cloud
    topology_tolerance: same
  followup: default
"""

ZONES = ("edge", "cloud")
TAGS = ("interactive", "batch", None)
#: Engine ticks ``serve`` runs before it gives up on unfinished requests.
MAX_TICKS = 10_000

#: One prompt with its policy tag.
RequestSpec = Tuple[np.ndarray, Optional[str]]


def build_engine(
    cfg: ModelConfig,
    params,
    *,
    replicas_per_zone: int = 1,
    slots: int = 8,
    max_len: int = 2048,
    devices: Optional[Sequence[jax.Device]] = None,
    script: str = DEFAULT_SCRIPT,
    distribution: DistributionPolicy = DistributionPolicy.SHARED,
) -> ServingEngine:
    """An engine with an ``EdgeCtl``/``CloudCtl`` controller pair and
    ``replicas_per_zone`` replicas named ``<zone>-<i>`` in each zone.

    Replicas take ``devices`` in turn (edge first); None puts every
    replica on the first device.
    """
    engine = ServingEngine(distribution=distribution, tapp_script=script)
    engine.add_controller("EdgeCtl", zone="edge")
    engine.add_controller("CloudCtl", zone="cloud")
    for zone in ZONES:
        for i in range(replicas_per_zone):
            device = (
                None if devices is None
                else devices[len(engine.replicas) % len(devices)]
            )
            engine.add_replica(
                Replica(f"{zone}-{i}", cfg, params, zone=zone, sets=[zone],
                        slots=slots, max_len=max_len, device=device)
            )
    return engine


def pinned_zones(engine: ServingEngine) -> Dict[str, str]:
    """The tags of ``TAGS`` that the engine's policy confines to one zone:
    every replica the static analyzer finds the tag can ever be placed on
    lies in that zone."""
    analysis = engine.platform.verify_policy()
    pinned = {}
    for tag in TAGS:
        if tag is None:
            continue
        zones = {engine.replicas[name].zone
                 for name in analysis.selectable(tag) or ()}
        if len(zones) == 1:
            pinned[tag] = zones.pop()
    return pinned


def request_mix(
    n: int, prompt_lens: Sequence[int], *, vocab_size: int
) -> List[RequestSpec]:
    """``n`` random prompts (seed 0) with lengths drawn from
    ``prompt_lens``, tagged ``interactive``, ``batch`` and untagged in
    turn."""
    rng = np.random.default_rng(0)
    return [
        (
            rng.integers(1, vocab_size, size=int(rng.choice(prompt_lens)),
                         dtype=np.int32),
            TAGS[i % len(TAGS)],
        )
        for i in range(n)
    ]


def serve(
    engine: ServingEngine,
    model_id: str,
    specs: Sequence[RequestSpec],
    *,
    max_new_tokens: int,
) -> List[Request]:
    """Submit every request, in order, and run the engine until all are
    finished or ``MAX_TICKS`` pass; returns the requests."""
    requests = [
        engine.submit(model_id, tokens, tag=tag, max_new_tokens=max_new_tokens)
        for tokens, tag in specs
    ]
    engine.run_until_done(max_ticks=MAX_TICKS)
    return requests


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm_135m",
                    help=f"one of {ARCH_IDS}")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small same-family config (CPU only)")
    ap.add_argument("--script", default=None, help="tAPP script path")
    ap.add_argument("--replicas-per-zone", type=int, default=1)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=2048)
    ap.add_argument("--prompt-lens", type=int, nargs="+",
                    default=[16, 256, 1024],
                    help="prompt lengths to draw from")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--distribution", default="shared",
                    choices=[p.value for p in DistributionPolicy])
    args = ap.parse_args()
    if args.reduced and jax.default_backend() != "cpu":
        ap.error("--reduced is for CPU runs only")
    if max(args.prompt_lens) + args.max_new_tokens > args.max_len:
        ap.error("--max-len must hold the longest prompt and its new tokens")

    use_compile_cache()
    script = DEFAULT_SCRIPT
    if args.script:
        with open(args.script) as fh:
            script = fh.read()

    cfg = smoke_config(args.arch) if args.reduced else get_config(args.arch)
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    engine = build_engine(
        cfg, params,
        replicas_per_zone=args.replicas_per_zone,
        slots=args.slots, max_len=args.max_len, devices=jax.devices(),
        script=script,
        distribution=DistributionPolicy.parse(args.distribution),
    )
    specs = request_mix(args.requests, args.prompt_lens,
                        vocab_size=cfg.vocab_size)
    reqs = serve(engine, cfg.name, specs, max_new_tokens=args.max_new_tokens)

    done = [r for r in reqs if r.state == "done"]
    print(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"requests={len(reqs)} done={len(done)}")
    if done:
        lat = sorted(r.finished_tick - r.submitted_tick for r in done)
        print(f"latency ticks: mean={statistics.fmean(lat):.1f} "
              f"p50={lat[len(lat) // 2]} max={lat[-1]}")
        for label, stamp in (("wait", "admitted_s"), ("ttft", "first_token_s"),
                             ("latency", "finished_s")):
            ms = sorted(1e3 * (getattr(r, stamp) - r.submitted_s) for r in done)
            print(f"{label} ms: p50={ms[len(ms) // 2]:.1f} max={ms[-1]:.1f}")
    by_tag = {}
    for r in done:
        by_tag.setdefault(r.tag or "untagged", []).append(r.replica)
    for tag, replicas in sorted(by_tag.items()):
        zones = {engine.replicas[name].zone for name in replicas}
        print(f"  {tag:>12}: zones={sorted(zones)} ({len(replicas)} reqs)")
    print(f"gateway: {engine.gateway.stats}; stragglers flagged: "
          f"{engine.stragglers_flagged}")


if __name__ == "__main__":
    main()
