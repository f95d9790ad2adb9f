"""Programs compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers a program for a described (not attached)
``v5e:2x2`` topology. The Pallas kernels, lowered with ``interpret=False``,
must hold the Mosaic kernel (``tpu_custom_call``). This catches what
interpret mode cannot, such as primitives the TPU lowering lacks, tiles
out of alignment, and more VMEM than a kernel may use. The served decode
step, jitted as a replica jits it (the cache donated), must update its K/V
cache in place: every cache leaf is written into its input buffer, and the
compiled program may hold no buffer of one layer's cache or of a whole
leaf (a relayout copy, a slice of it, or a second cache).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd_scan_bhsd
from repro.models.api import Model
from repro.models.layers.attention import write_kv
from repro.runtime.serve_engine import jit_decode


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


@pytest.mark.parametrize(
    "dtype,head_dim",
    [(jnp.bfloat16, 64), (jnp.int8, 64), (jnp.float32, 1)],
    ids=["bf16", "int8_q", "int8_scale"],
)
def test_kv_write_smollm_decode(one_chip, dtype, head_dim):
    """The decode token write over smollm-135m's stacked K and V (30
    layers, 64 slots x 2048), and over the int8 variant's q and scale
    leaves, writes into the cache it is given: it makes no buffer of one
    layer's or of a whole leaf, and needs less temporary memory than one
    layer's leaf holds."""
    cfg = get_config("smollm_135m")
    shape = (cfg.n_layers, 64, cfg.n_kv_heads, head_dim, 2048)
    cache = {kv: _spec(one_chip, shape, dtype) for kv in "kv"}
    compiled = jax.jit(write_kv, donate_argnums=(0,)).lower(
        cache, {kv: _spec(one_chip, shape[:-1], dtype) for kv in "kv"},
        _spec(one_chip, (64,), jnp.int32),
    ).compile()
    stats = compiled.memory_analysis()
    leaf_bytes = math.prod(shape) * jnp.dtype(dtype).itemsize
    assert stats.alias_size_in_bytes == len(cache) * leaf_bytes
    one_layer = math.prod(shape[1:])
    assert stats.temp_size_in_bytes < one_layer * jnp.dtype(dtype).itemsize
    sizes = {one_layer, math.prod(shape)}
    assert [b for b in _buffers(compiled.as_text()) if b[2] in sizes] == []


# Ops that name a buffer without writing one.
_NO_BUFFER = ("bitcast", "get-tuple-element", "parameter", "tuple")


def _buffers(hlo_text):
    """(name, op, element count) of every instruction outside fused
    computations: the buffers the program writes. A dynamic-update-slice
    writes into its operand's buffer, so it is left out: where that buffer
    cannot be written in place, the compiler copies it first, and the copy
    is an instruction of its own."""
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
            if inst and inst.group(3) not in _NO_BUFFER + ("dynamic-update-slice",):
                dims = [int(d) for d in inst.group(2).split(",") if d]
                out.append((inst.group(1), inst.group(3), math.prod(dims)))
    return out


def _aliased_outputs(hlo_text):
    """{output index: parameter number} of the module's input/output
    aliases (the donated arguments the outputs are written into)."""
    head = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo_text)
    pairs = re.findall(r"\{(\d+)\}: \((\d+),", head.group(1)) if head else []
    return {int(o): int(p) for o, p in pairs}


def _decode_writes_kv_in_place(compiled, cache):
    """The served decode step, compiled, updates its K/V cache in place:
    every cache leaf of the output is written into its donated input, and
    no buffer the size of one layer's K/V or of a whole K/V leaf is made
    (no relayout copy, no slice, no second cache)."""
    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    # Output 0 is the logits; output i + 1 is cache leaf i, and the cache
    # leaves are consecutive parameters (unused weights are left out).
    aliases = _aliased_outputs(text)
    first = aliases.get(1, -1)
    assert {i + 1: first + i for i in range(len(leaves))} == aliases, aliases
    kv = [leaf for path, leaf in leaves
          if any(str(k.key).split("_")[-1] in ("k", "v") for k in path)]
    assert kv
    sizes = {n for leaf in kv
             for n in (math.prod(leaf.shape[1:]), math.prod(leaf.shape))}
    assert [b for b in _buffers(text) if b[2] in sizes] == []


def _on_chip(sharding, tree):
    return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype), tree)


# The served replica (smollm-135m, 64 slots x 2048 positions), a wider GQA
# decoder, and the encoder-decoder whose self K/V take the same path and
# whose cross K/V, handed back as they came in, stay in their buffers: no
# configuration copies a whole cache leaf.
@pytest.mark.parametrize(
    "arch,slots,max_len,whole_copies",
    [("smollm_135m", 64, 2048, 0), ("qwen1_5_0_5b", 16, 2048, 0),
     ("whisper_small", 16, 448, 0)],
)
def test_decode_keeps_kv_cache_as_stored(
    one_chip, arch, slots, max_len, whole_copies
):
    model = Model(get_config(arch))
    params = _on_chip(
        one_chip, jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    )
    cache = _on_chip(one_chip, jax.eval_shape(
        lambda: model.init_cache(slots, max_len, enc_len=max_len)
    ))
    step = _spec(one_chip, (slots,), jnp.int32)
    compiled = jit_decode(model).lower(params, cache, step, step).compile()
    _decode_writes_kv_in_place(compiled, cache)
    whole = {leaf.size for leaf in jax.tree.leaves(cache)}
    copies = [b for b in _buffers(compiled.as_text())
              if b[1] == "copy" and b[2] in whole]
    assert len(copies) == whole_copies, copies


def test_sharded_decode_writes_each_shard_in_place(topo):
    """The dry-run's decode over a v5e:2x2 mesh, smollm-135m's cache (64
    slots x 2048) with slots over "data" and positions over "model": each
    chip writes the token K/V into its own shard of the donated cache. No
    collective moves as much as one layer's shard of K/V, and no buffer
    of that size or of a whole shard is made."""
    from repro.launch.dryrun import _lower_decode
    from repro.models.api import ShapeSpec
    from repro.sharding.ctx import activation_sharding
    from repro.sharding.specs import ShardingPolicy

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    policy = ShardingPolicy().for_mesh(mesh)
    cfg = get_config("smollm_135m")
    model = Model(cfg)
    shape = ShapeSpec("decode_2k", "decode", 2048, 64)
    with mesh, activation_sharding(mesh, policy.dp_axes, policy.tp_axis):
        compiled = _lower_decode(cfg, model, shape, mesh, policy).compile()
    text = compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes > 0
    leaf = jax.tree.leaves(model.cache_specs(shape))[0]
    shard = (leaf.shape[0], leaf.shape[1] // 2, *leaf.shape[2:4],
             leaf.shape[4] // 2)
    one_layer = math.prod(shard[1:])
    sizes = {one_layer, math.prod(shard)}
    assert [b for b in _buffers(text) if b[2] in sizes] == []
    moved = [b for b in _buffers(text)
             if b[1].startswith(("all-", "collective-")) and b[2] >= one_layer]
    assert moved == []


def test_granite_decode_updates_its_state_in_place(one_chip):
    """The granite-4.0-h-small cut's decode step (10 layers, 9 of 72 experts
    held, 32 slots x 4096) compiles for the chip, its grouped expert matmul
    included, writes its K/V and Mamba-2 state into the donated cache, and
    needs no temporary buffer the size of one layer's Mamba-2 state
    (134 MB) or K/V (268 MB): each is read and written once."""
    from bench import harness
    from bench.families import granite

    c = harness.load_config("granite-4.0-h-small")
    model = Model(granite.program_config(c))
    slots, max_len = c["replica"]["slots"], c["replica"]["max_len"]
    params = _on_chip(one_chip, jax.eval_shape(lambda: granite.program_tree(
        granite.make_weights(c, 1))))
    cache = _on_chip(
        one_chip, jax.eval_shape(lambda: model.init_cache(slots, max_len))
    )
    step = _spec(one_chip, (slots,), jnp.int32)
    compiled = jit_decode(model).lower(params, cache, step, step).compile()
    _decode_writes_kv_in_place(compiled, cache)
    state = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
             if str(path[-1].key) in ("ssm", "k", "v")]
    smallest = min(leaf.size * leaf.dtype.itemsize for leaf in state)
    assert compiled.memory_analysis().temp_size_in_bytes < smallest
