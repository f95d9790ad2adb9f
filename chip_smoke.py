"""Chip smoke: serve smollm-135m at its published widths through the tAPP
path on a TPU, and check what comes out.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas vs one device

One chip runs three phases in one process:

  serve      an edge/cloud engine built by ``repro.launch.serve`` (two
             replicas, 8 slots, 2048 positions each) serves a tagged
             request mix; every request finishes with all its tokens, the
             tags the policy pins to a zone run there, and one prompt sent
             to both replicas gives equal tokens on each;
  reference  a replica's own jitted prefill and decode logits against a
             float32 ``lm.forward`` at ``highest`` matmul precision;
  kernel     the Pallas flash-attention kernel compiled for the chip (not
             interpreted) at smollm's head shapes, against ``ref_attention``.

``--four-chips`` runs only the four-replica layouts: each replica on its own
device and serving, then all four on device 0, with equal tokens required.

The script refuses to run without a TPU. Weights come from a seed. The last
line of output is one JSON object naming the device; any failed check
raises before it is printed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

_SRC = str(pathlib.Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.configs import get_config  # noqa: E402
from repro.kernels.flash_attention import flash_attention_bhsd  # noqa: E402
from repro.kernels.ref import ref_attention  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    pinned_zones,
    request_mix,
    serve,
)
from repro.models import Model, lm  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.runtime.serve_engine import Replica, Request, ServingEngine  # noqa: E402

ARCH = "smollm_135m"
SLOTS = 8
MAX_LEN = 2048
PROMPT_LENS = (16, 256, 1024)
N_REQUESTS = 16
#: The four-replica layouts get three requests per slot of a replica, so a
#: third of them are ``interactive``: more than the 75% of ``edge-0``'s
#: slots that the shipped policy lets them fill, and the rest spill onto
#: ``edge-1``. Every replica then serves.
FOUR_CHIP_REQUESTS = 3 * SLOTS
MAX_NEW_TOKENS = 16
REFERENCE_PROMPT_LEN = 256
KERNEL_SEQ = 512

#: Bound on max|replica - reference| / max|reference| over the vocabulary.
#: ``test_decode_matches_forward`` holds float32 against float32 to 1e-4;
#: the replica computes in bfloat16, which rounds its residual stream after
#: each of 30 layers' attention and FFN. At smollm-135m's widths with a
#: 256-token prompt a sound replica is off by 2.2e-2 (prefill) and 2.4e-2
#: (decode) on the CPU, 2.2e-2 and 2.0e-2 on a v5e. Planted wrong answers
#: at the same widths are off by far more: a decode step one position early
#: by 0.25, a decode step against a zeroed cache by 1.35, the prefill
#: logits of the position before by 0.66. The bound sits between the two.
REFERENCE_BOUND = 5e-2
#: Flash attention in bfloat16 against the float32 oracle: the tolerance
#: ``tests/test_kernels.py`` uses for bfloat16.
KERNEL_TOL = 2e-2


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class CompileLog:
    """Backend compile seconds per jitted function, from JAX's monitoring
    events."""

    def __init__(self) -> None:
        self.entries: List[tuple] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.entries.append((kwargs.get("fun_name", "?"), duration))

    def report(self, phase: str) -> None:
        """Print seconds and compile counts per function since the last
        report; functions under half a second in all are summed."""
        totals: Dict[str, List[float]] = {}
        for name, secs in self.entries:
            totals.setdefault(name, []).append(secs)
        small = [s for secs in totals.values() if sum(secs) < 0.5 for s in secs]
        parts = [f"{name}={sum(secs):.2f} (x{len(secs)})"
                 for name, secs in totals.items() if sum(secs) >= 0.5]
        if small:
            parts.append(f"others={sum(small):.2f} (x{len(small)})")
        if parts:
            print(f"[{phase}] backend compile s: {', '.join(parts)}")
        self.entries = []


def _relative_error(got: jax.Array, want: jax.Array) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def _check_served(engine: ServingEngine, requests: Sequence[Request],
                  max_new_tokens: int, label: str) -> None:
    """Every request done with all its tokens; the tags the policy pins to
    a zone ran there."""
    done = [r for r in requests if r.state == "done"]
    tokens = sum(len(r.output) for r in done)
    print(f"[{label}] requests={len(requests)} done={len(done)} tokens={tokens}")
    check(len(done) == len(requests),
          f"{label}: {len(requests) - len(done)} requests not done")
    short = [r.request_id for r in done if len(r.output) != max_new_tokens]
    check(not short, f"{label}: requests {short} have too few tokens")
    by_tag: Dict[str, List[str]] = {}
    for r in requests:
        by_tag.setdefault(r.tag or "untagged", []).append(r.replica)
    for tag, replicas in sorted(by_tag.items()):
        counts = {name: replicas.count(name) for name in sorted(set(replicas))}
        print(f"[{label}] placements {tag}: {counts}")
    pinned = pinned_zones(engine)
    print(f"[{label}] zones the policy pins: {pinned}")
    check(bool(pinned), f"{label}: the policy pins no tag to a zone")
    for r in requests:
        zone = pinned.get(r.tag)
        if zone is not None:
            got = engine.replicas[r.replica].zone
            check(got == zone,
                  f"{label}: {r.tag!r} request {r.request_id} ran in {got}")


def serve_phase(
    cfg: ModelConfig,
    params,
    *,
    slots: int = SLOTS,
    max_len: int = MAX_LEN,
    prompt_lens: Sequence[int] = PROMPT_LENS,
    n_requests: int = N_REQUESTS,
    max_new_tokens: int = MAX_NEW_TOKENS,
) -> ServingEngine:
    """Serve the mix on ``edge-0`` and ``cloud-0``; returns the engine.

    The first two requests share a prompt: one is ``interactive``, placed
    on the empty edge replica, and one is ``batch``, which the policy pins
    to the cloud. Their tokens must be equal.
    """
    engine = build_engine(cfg, params, replicas_per_zone=1, slots=slots,
                          max_len=max_len)
    specs = request_mix(n_requests - 1, prompt_lens,
                        vocab_size=cfg.vocab_size)
    shared = specs[0][0]
    specs = [(shared, "interactive"), (shared, "batch")] + specs[1:]
    t0 = time.perf_counter()
    requests = serve(engine, cfg.name, specs, max_new_tokens=max_new_tokens)
    print(f"[serve] wall s (compiles included): {time.perf_counter() - t0:.2f}")
    _check_served(engine, requests, max_new_tokens, "serve")
    a, b = requests[0], requests[1]
    print(f"[serve] shared prompt ({len(shared)} tokens): "
          f"{a.replica} and {b.replica}")
    check(a.replica != b.replica,
          f"the shared prompt ran twice on {a.replica}")
    check(a.output == b.output,
          f"shared prompt: {a.replica} gave {a.output}, "
          f"{b.replica} gave {b.output}")
    return engine


def reference_phase(
    replica: Replica, *, prompt_len: int = REFERENCE_PROMPT_LEN
) -> Dict[str, float]:
    """An idle replica's jitted prefill and one decode step against the
    float32 forward pass at ``highest`` precision."""
    check(not replica.active, f"{replica.name} still has active slots")
    cfg = replica.cfg
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, cfg.vocab_size, size=prompt_len + 1,
                          dtype=np.int32)
    got_prefill = replica.prefill(0, tokens[:-1])
    step_tokens = np.zeros((replica.slots,), np.int32)
    step_positions = np.zeros((replica.slots,), np.int32)
    step_tokens[0], step_positions[0] = tokens[-1], prompt_len
    got_decode = replica.decode(step_tokens, step_positions)[0]

    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")

    @jax.jit
    def reference_forward(params, tokens):
        return lm.forward(cfg32, params, tokens)

    with jax.default_matmul_precision("highest"):
        ref_logits, _ = reference_forward(replica.params,
                                          jnp.asarray(tokens[None, :]))
    errors = {
        "prefill": _relative_error(got_prefill, ref_logits[0, -2]),
        "decode": _relative_error(got_decode, ref_logits[0, -1]),
    }
    for name, err in errors.items():
        print(f"[reference] {name} rel err={err:.6g} bound={REFERENCE_BOUND:g}")
    for name, err in errors.items():
        check(err <= REFERENCE_BOUND,
              f"{name} logits off the reference by {err:.6g}")
    return errors


def kernel_phase(cfg: ModelConfig) -> float:
    """Flash attention compiled for the chip at the config's head shapes."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    shape_q = (1, cfg.n_heads, KERNEL_SEQ, cfg.head_dim)
    shape_kv = (1, cfg.n_kv_heads, KERNEL_SEQ, cfg.head_dim)
    q = jax.random.normal(ks[0], shape_q).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], shape_kv).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], shape_kv).astype(jnp.bfloat16)
    compiled = flash_attention_bhsd.lower(
        q, k, v, causal=True, interpret=False
    ).compile()
    lowered_to_kernel = "tpu_custom_call" in compiled.as_text()
    print(f"[kernel] flash_attention q={shape_q} kv={shape_kv} "
          f"tpu_custom_call={lowered_to_kernel}")
    check(lowered_to_kernel, "flash attention did not compile to a kernel")
    out = np.asarray(compiled(q, k, v), np.float32)
    want = np.asarray(ref_attention(q, k, v, causal=True), np.float32)
    err = float(np.max(np.abs(out - want)))
    excess = float(np.max(np.abs(out - want) - KERNEL_TOL * (1 + np.abs(want))))
    print(f"[kernel] max abs err={err:.6g} tol={KERNEL_TOL:g} (atol and rtol)")
    check(excess <= 0, f"flash attention off ref_attention by {err:.6g}")
    return err


def four_chip_phase(
    cfg: ModelConfig,
    params,
    devices: Sequence[jax.Device],
    *,
    slots: int = SLOTS,
    max_len: int = MAX_LEN,
    prompt_lens: Sequence[int] = PROMPT_LENS,
    n_requests: int = FOUR_CHIP_REQUESTS,
    max_new_tokens: int = MAX_NEW_TOKENS,
) -> None:
    """Four replicas (2 edge, 2 cloud) one per device, each of which must
    serve, then all on the first device: same requests, equal tokens."""
    check(len(devices) >= 4, f"four devices needed, found {len(devices)}")
    specs = request_mix(n_requests, prompt_lens, vocab_size=cfg.vocab_size)
    outputs = {}
    for layout, devs in (("four-devices", devices[:4]),
                         ("one-device", devices[:1])):
        engine = build_engine(cfg, params, replicas_per_zone=2, slots=slots,
                              max_len=max_len, devices=devs)
        for replica in engine.replicas.values():
            print(f"[{layout}] {replica.name} on {replica.device}")
        t0 = time.perf_counter()
        requests = serve(engine, cfg.name, specs, max_new_tokens=max_new_tokens)
        print(f"[{layout}] wall s (compiles included): "
              f"{time.perf_counter() - t0:.2f}")
        _check_served(engine, requests, max_new_tokens, layout)
        if layout == "four-devices":
            placed = [r.device for r in engine.replicas.values()]
            check(len(set(placed)) == 4, f"replicas share devices: {placed}")
            idle = sorted(set(engine.replicas) - {r.replica for r in requests})
            check(not idle, f"replicas {idle} served no request")
            for replica in engine.replicas.values():
                leaves = jax.tree.leaves((replica.params, replica.cache))
                held = {d for leaf in leaves for d in leaf.devices()}
                check(held == {replica.device},
                      f"{replica.name} arrays on {held}, not {replica.device}")
        outputs[layout] = [r.output for r in requests]
        del engine
    differ = [i for i, (a, b) in enumerate(zip(outputs["four-devices"],
                                               outputs["one-device"]))
              if a != b]
    print(f"[layouts] requests with equal tokens: "
          f"{len(specs) - len(differ)}/{len(specs)}")
    check(not differ, f"requests {differ} differ between layouts")


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica layouts")
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {device.platform!r}",
              file=sys.stderr)
        return 1

    cache_dir = use_compile_cache()
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    cfg = get_config(ARCH)
    print(f"device: {device.platform} {device.device_kind} x{len(jax.devices())}"
          f"; compile cache: {cache_dir}")
    print(f"config: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"params={cfg.param_count()}")
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    compiles.report("init")

    if args.four_chips:
        four_chip_phase(cfg, params, jax.devices())
        compiles.report("layouts")
    else:
        engine = serve_phase(cfg, params)
        compiles.report("serve")
        reference_phase(engine.replicas["edge-0"])
        compiles.report("reference")
        kernel_phase(cfg)
        compiles.report("kernel")

    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"memory {d}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
