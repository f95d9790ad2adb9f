"""``chip_smoke.py`` on the CPU: its phases at reduced size pass through the
shared serving path, and its entry point refuses to run without a TPU."""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from repro.configs import smoke_config
from repro.models import Model

ROOT = pathlib.Path(__file__).resolve().parents[1]
SMALL = dict(slots=4, max_len=64, prompt_lens=(4, 16, 32), n_requests=10,
             max_new_tokens=5)


@pytest.fixture(scope="module")
def served():
    cfg = smoke_config("smollm_135m")
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    return chip_smoke.serve_phase(cfg, params, **SMALL)


def test_serve_phase_pins_batch_to_cloud(served):
    requests = served.done
    assert len(requests) == SMALL["n_requests"]
    batch = [r for r in requests if r.tag == "batch"]
    assert batch
    assert {served.replicas[r.replica].zone for r in batch} == {"cloud"}


def test_reference_phase_within_bound(served):
    errors = chip_smoke.reference_phase(served.replicas["edge-0"],
                                        prompt_len=32)
    assert set(errors) == {"prefill", "decode"}
    assert all(0 < e <= chip_smoke.REFERENCE_BOUND for e in errors.values())


def _decode_one_position_early(replica, sound):
    return lambda tokens, positions: sound(tokens, np.maximum(positions - 1, 0))


def _decode_against_zeroed_cache(replica, sound):
    def decode(tokens, positions):
        replica.cache = jax.tree.map(jnp.zeros_like, replica.cache)
        return sound(tokens, positions)
    return decode


@pytest.mark.parametrize(
    "fault", [_decode_one_position_early, _decode_against_zeroed_cache]
)
def test_reference_phase_catches_a_wrong_decode(served, monkeypatch, fault):
    replica = served.replicas["edge-0"]
    monkeypatch.setattr(replica, "decode", fault(replica, replica.decode))
    with pytest.raises(chip_smoke.SmokeFailure, match="decode logits"):
        chip_smoke.reference_phase(replica, prompt_len=32)


def test_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")


_FOUR_DEVICES = textwrap.dedent(
    """
    import jax
    import chip_smoke
    from repro.configs import smoke_config
    from repro.models import Model

    cfg = smoke_config("smollm_135m")
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    chip_smoke.four_chip_phase(
        cfg, params, jax.devices(), slots=2, max_len=48,
        prompt_lens=(4, 16), n_requests=12, max_new_tokens=4,
    )
    print("FOUR_OK")
    """
)


def _run(args, *, cwd, extra_env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300,
    )


def test_four_replica_layouts_on_virtual_devices():
    proc = _run(
        ["-c", _FOUR_DEVICES], cwd=ROOT,
        extra_env={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PYTHONPATH": f"{ROOT / 'src'}{os.pathsep}{ROOT}",
        },
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "FOUR_OK" in proc.stdout
    assert "requests with equal tokens: 12/12" in proc.stdout


def test_entry_point_refuses_cpu():
    proc = _run(["chip_smoke.py"], cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr

