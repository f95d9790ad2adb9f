"""Programs compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers a program for a described (not attached)
``v5e:2x2`` topology. The Pallas kernels, lowered with ``interpret=False``,
must hold the Mosaic kernel (``tpu_custom_call``). This catches what
interpret mode cannot, such as primitives the TPU lowering lacks, tiles
out of alignment, and more VMEM than a kernel may use. The served decode
step must read and write its K/V cache as stored: the compiled program may
hold no buffer of one layer's cache (a relayout copy or a slice of it).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd_scan_bhsd
from repro.models.api import Model


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


# Ops that name a buffer without writing one.
_NO_BUFFER = ("bitcast", "get-tuple-element", "parameter", "tuple")


def _buffers(hlo_text):
    """(name, op, element count) of every instruction outside fused
    computations: the buffers the program writes."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo_text))
    comp, out = None, []
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{\s*$", line)
        if head:
            comp = head.group(1)
        elif line.startswith("}"):
            comp = None
        elif comp is not None and comp not in fused:
            inst = re.match(
                r"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(",
                line,
            )
            if inst and inst.group(3) not in _NO_BUFFER:
                dims = [int(d) for d in inst.group(2).split(",") if d]
                out.append((inst.group(1), inst.group(3), math.prod(dims)))
    return out


# The served replica (smollm-135m, 64 slots x 2048 positions), a wider GQA
# decoder, and the encoder-decoder whose self K/V take the same path. Only
# whisper's cross K/V, which decode hands back as they came in, are copied
# whole into the (undonated) output.
@pytest.mark.parametrize(
    "arch,slots,max_len,whole_copies",
    [("smollm_135m", 64, 2048, 0), ("qwen1_5_0_5b", 16, 2048, 0),
     ("whisper_small", 16, 448, 2)],
)
def test_decode_keeps_kv_cache_as_stored(
    one_chip, arch, slots, max_len, whole_copies
):
    model = Model(get_config(arch))

    def on_chip(tree):
        return jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree
        )

    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_cache(slots, max_len, enc_len=max_len)
    ))
    step = _spec(one_chip, (slots,), jnp.int32)
    compiled = jax.jit(model.decode).lower(params, cache, step, step).compile()
    buffers = _buffers(compiled.as_text())
    leaves = jax.tree.leaves(cache)
    one_layer = {math.prod(leaf.shape[1:]) for leaf in leaves}
    whole = {math.prod(leaf.shape) for leaf in leaves}
    assert [b for b in buffers if b[2] in one_layer] == []
    copies = [b for b in buffers if b[1] == "copy" and b[2] in whole]
    assert len(copies) == whole_copies, copies


def test_granite_decode_updates_its_state_in_place(one_chip):
    """The granite-4.0-h-small cut's decode step (10 layers, 9 of 72 experts
    held, 32 slots x 4096) compiles for the chip, its grouped expert matmul
    included, and needs no temporary buffer the size of one layer's Mamba-2
    state (134 MB) or K/V (268 MB): each is read and written once."""
    from bench import harness
    from bench.families import granite

    c = harness.load_config("granite-4.0-h-small")
    model = Model(granite.program_config(c))
    slots, max_len = c["replica"]["slots"], c["replica"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: granite.program_tree(
        granite.make_weights(c, 1))))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(slots, max_len)))
    step = _spec(one_chip, (slots,), jnp.int32)
    compiled = jax.jit(model.decode).lower(params, cache, step, step).compile()
    state = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
             if str(path[-1].key) in ("ssm", "k", "v")]
    smallest = min(leaf.size * leaf.dtype.itemsize for leaf in state)
    assert compiled.memory_analysis().temp_size_in_bytes < smallest
