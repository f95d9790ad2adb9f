"""The Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers one kernel with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology and checks that the compiled
program holds the Mosaic kernel (``tpu_custom_call``). This catches what
interpret mode cannot, such as primitives the TPU lowering lacks, tiles
out of alignment, and more VMEM than a kernel may use.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd_scan_bhsd


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # Keep the TPU compiler from writing logs outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """Programs compiled for a described chip are written to the
    persistent cache but cannot be read back without one."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [16, 512, 2048])
def test_flash_attention_smollm_prefill(one_chip, seq):
    cfg = get_config("smollm_135m")
    q = _spec(one_chip, (1, cfg.n_heads, seq, cfg.head_dim), jnp.bfloat16)
    kv = _spec(one_chip, (1, cfg.n_kv_heads, seq, cfg.head_dim), jnp.bfloat16)
    compiled = flash_attention_bhsd.lower(
        q, kv, kv, causal=True, interpret=False
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("proj", ["up", "down"])
def test_gmm_phi35_moe(one_chip, proj):
    """Expert matmuls of phi3.5-moe over a 256-row capacity buffer."""
    cfg = get_config("phi3_5_moe_42b")
    k, n = (cfg.d_model, cfg.d_ff) if proj == "up" else (cfg.d_ff, cfg.d_model)
    x = _spec(one_chip, (cfg.moe_experts, 256, k), jnp.bfloat16)
    w = _spec(one_chip, (cfg.moe_experts, k, n), jnp.bfloat16)
    compiled = gmm.lower(x, w, interpret=False).compile()
    _assert_kernel(compiled)


def test_ssd_scan_mamba2(one_chip):
    cfg = get_config("mamba2_2_7b")
    heads = cfg.ssm_expand * cfg.d_model // cfg.ssm_headdim
    bsz, seq, groups = 1, 2048, cfg.ssm_groups
    compiled = ssd_scan_bhsd.lower(
        _spec(one_chip, (bsz, heads, seq, cfg.ssm_headdim), jnp.float32),
        _spec(one_chip, (bsz, heads, 1, seq), jnp.float32),
        _spec(one_chip, (bsz, groups, seq, cfg.ssm_state), jnp.float32),
        _spec(one_chip, (bsz, groups, seq, cfg.ssm_state), jnp.float32),
        chunk=cfg.ssm_chunk,
        interpret=False,
    ).compile()
    _assert_kernel(compiled)
