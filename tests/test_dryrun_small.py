"""Small-mesh dry-run: lower+compile the sharded steps in a subprocess
with 8 host devices (the production dry-run uses 512; same code path).
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax
    from repro.configs import smoke_config
    from repro.launch.steps import (
        abstract_train_state, make_train_step, make_decode_step,
        train_state_shardings,
    )
    from repro.launch.mesh import make_debug_mesh
    from repro.models.api import Model, ShapeSpec
    from repro.optim.adamw import AdamWConfig
    from repro.sharding.ctx import activation_sharding
    from repro.sharding.specs import (
        ShardingPolicy, batch_shardings, cache_shardings, param_shardings,
    )
    from repro.roofline.analysis import normalize_cost, roofline_terms

    arch = {arch!r}
    cfg = dataclasses.replace(
        smoke_config(arch), d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
    )
    mesh = make_debug_mesh((2, 4), ("data", "model"))
    policy = ShardingPolicy(fsdp_min_params=0).for_mesh(mesh)
    model = Model(cfg)
    shape = ShapeSpec("small", "train", 32, 8)
    results = {{}}
    with mesh, activation_sharding(mesh, policy.dp_axes, policy.tp_axis):
        # train step
        step = make_train_step(cfg, AdamWConfig())
        state = abstract_train_state(cfg)
        st_sh = train_state_shardings(cfg, policy, mesh, state)
        batch = model.input_specs(shape)
        b_sh = batch_shardings(cfg, policy, mesh, shape, batch)
        lowered = jax.jit(step, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None)).lower(state, batch)
        compiled = lowered.compile()
        cost = normalize_cost(compiled.cost_analysis())
        results["train_flops"] = cost.get("flops", 0)
        terms = roofline_terms(
            cost=cost, hlo_text=compiled.as_text(),
            n_chips=8, model_flops_total=1.0,
        )
        results["train_collective_wire"] = terms.wire_bytes_per_device
        # decode step
        dshape = ShapeSpec("smalldec", "decode", 64, 8)
        dec = make_decode_step(cfg)
        params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        p_sh = param_shardings(cfg, policy, mesh, params)
        cache = model.cache_specs(dshape)
        c_sh = cache_shardings(cfg, policy, mesh, cache)
        ins = model.input_specs(dshape)
        i_sh = batch_shardings(cfg, policy, mesh, dshape, ins)
        dl = jax.jit(dec, in_shardings=(p_sh, c_sh, i_sh["token"],
                                        i_sh["position"]),
                     out_shardings=(None, c_sh)).lower(
            params, cache, ins["token"], ins["position"])
        dl.compile()
        results["decode_ok"] = True
    print("RESULT:" + json.dumps(results))
    """
)


def _run(arch: str) -> dict:
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise AssertionError(f"no RESULT line in: {proc.stdout[-500:]}")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["smollm_135m", "phi3_5_moe_42b", "mamba2_2_7b"])
def test_sharded_lower_compile(arch):
    results = _run(arch)
    assert results["decode_ok"]
    assert results["train_flops"] > 0
    # a sharded train step must move bytes over the mesh
    assert results["train_collective_wire"] > 0


_DECODE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import smoke_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models.api import Model
    from repro.sharding.ctx import activation_sharding
    from repro.sharding.specs import (
        ShardingPolicy, cache_shardings, param_shardings,
    )

    cfg = dataclasses.replace(
        smoke_config("smollm_135m"), d_model=128, n_heads=4, n_kv_heads=2,
        head_dim=32, compute_dtype="float32",
    )
    mesh = make_debug_mesh((2, 4), ("data", "model"))
    policy = ShardingPolicy(fsdp_min_params=0).for_mesh(mesh)
    model = Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    results = {{}}
    for max_len in (64, 512):
        # Positions on either side of each model-axis shard boundary.
        edge = max_len // 4
        position = jnp.asarray(
            [0, edge - 1, edge, 2 * edge - 1, 2 * edge, 3 * edge,
             max_len - 1, 5], jnp.int32)
        shapes = jax.eval_shape(lambda: model.init_cache(8, max_len))
        keys = iter(jax.random.split(jax.random.PRNGKey(1), 16))
        cache = jax.tree.map(
            lambda s: jax.random.normal(next(keys), s.shape).astype(s.dtype),
            shapes)
        token = jnp.arange(8, dtype=jnp.int32)
        want_logits, want = jax.jit(model.decode)(params, cache, token, position)
        c_sh = cache_shardings(cfg, policy, mesh, shapes)
        p_sh = param_shardings(cfg, policy, mesh, params)
        with mesh, activation_sharding(mesh, policy.dp_axes, policy.tp_axis):
            step = jax.jit(model.decode, in_shardings=(p_sh, c_sh, None, None),
                           out_shardings=(None, c_sh), donate_argnums=(1,))
            given = jax.device_put(cache, c_sh)
            logits, got = step(params, given, token, position)
        new = (np.arange(max_len)[None, :] == np.asarray(position)[:, None])
        new = new[None, :, None, None, :]
        results[max_len] = {{
            "sharded": [str(leaf.sharding.spec) for leaf in jax.tree.leaves(got)],
            "donated": all(leaf.is_deleted() for leaf in jax.tree.leaves(given)),
            # Every position but the new ones, bit for bit; the new ones
            # as the two programs' projections round them.
            "kept_equal": all(
                bool(np.array_equal(np.where(new, 0, a), np.where(new, 0, b)))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))),
            "written_gap": max(
                float(np.max(np.abs(np.where(new, a - b, 0))))
                for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))),
            "logit_gap": float(jnp.max(jnp.abs(logits - want_logits))),
        }}
    print("RESULT:" + json.dumps(results))
    """
)


def test_sharded_decode_writes_the_cache_an_unsharded_one_writes():
    """Decode over a cache sharded as the dry-run shards it (slots over
    "data", positions over "model", on 8 host devices), with slots either
    side of each shard boundary, writes the same cache as an unsharded
    decode: each slot's token K/V at its position and every other position
    as it was. The logits agree too."""
    proc = subprocess.run(
        [sys.executable, "-c", _DECODE_SCRIPT.format()],
        capture_output=True, text=True, timeout=420,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [s for s in proc.stdout.splitlines() if s.startswith("RESULT:")]
    results = json.loads(line[-1][len("RESULT:"):])
    for max_len, r in results.items():
        assert all("model" in spec for spec in r["sharded"]), (max_len, r)
        assert r["donated"] and r["kept_equal"], (max_len, r)
        assert r["written_gap"] < 1e-5, (max_len, r)
        assert r["logit_gap"] < 1e-4, (max_len, r)
