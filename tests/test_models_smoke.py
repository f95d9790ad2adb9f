"""Per-architecture smoke tests (reduced same-family configs, CPU).

For each of the 10 assigned archs: instantiate the reduced config, run one
forward/loss + one train step, assert output shapes and no NaNs; check
prefill+decode consistency against the full forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.models import Model, SHAPES, shape_applicable
from repro.models import encdec as ed_mod
from repro.models import lm as lm_mod
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

RNG = jax.random.PRNGKey(0)


def _batch(cfg, bsz=2, seq=16, rng=RNG):
    toks = jax.random.randint(rng, (bsz, seq), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            rng, (bsz, seq, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestArchSmoke:
    def test_loss_finite_and_shapes(self, arch):
        cfg = smoke_config(arch)
        model = Model(cfg)
        params = model.init_params(RNG)
        batch = _batch(cfg)
        loss, metrics = model.loss(params, batch)
        assert loss.shape == ()
        assert bool(jnp.isfinite(loss))
        assert bool(jnp.isfinite(metrics["ce"]))

    def test_one_train_step(self, arch):
        cfg = smoke_config(arch)
        model = Model(cfg)
        params = model.init_params(RNG)
        opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        opt = adamw_init(opt_cfg, params)
        batch = _batch(cfg)

        (loss, _), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True
        )(params)
        new_params, new_opt, metrics = adamw_update(opt_cfg, grads, opt, params)
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        # params actually changed
        delta = jax.tree.reduce(
            lambda a, leaf: a + float(jnp.sum(jnp.abs(leaf))),
            jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - b.astype(jnp.float32)),
                new_params, params,
            ),
            0.0,
        )
        assert delta > 0.0
        # no NaNs introduced
        for leaf in jax.tree.leaves(new_params):
            assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))

    def test_decode_matches_forward(self, arch):
        # MoE dispatch is dropless, so batched prefill routing and
        # single-token decode routing give each token the same experts.
        cfg = dataclasses.replace(smoke_config(arch), compute_dtype="float32")
        model = Model(cfg)
        params = model.init_params(RNG)
        bsz, s = 2, 12
        toks = jax.random.randint(RNG, (bsz, s + 1), 0, cfg.vocab_size)
        if cfg.family == "encdec":
            frames = jax.random.normal(RNG, (bsz, s, cfg.d_model), jnp.float32)
            enc = ed_mod.encode(cfg, params, frames)
            full = ed_mod.decode_full(cfg, params, toks, enc)[:, -1, :]
            cache = model.init_cache(bsz, 32, enc_len=s)
            _, cache = model.prefill(
                params, {"frames": frames, "tokens": toks[:, :s]}, cache
            )
        else:
            logits, _ = lm_mod.forward(cfg, params, toks)
            full = logits[:, -1, :]
            cache = model.init_cache(bsz, 32)
            _, cache = model.prefill(params, {"tokens": toks[:, :s]}, cache)
        step, _ = model.decode(
            params, cache, toks[:, s], jnp.full((bsz,), s, jnp.int32)
        )
        err = float(jnp.max(jnp.abs(full - step[:, 0, :])))
        scale = float(jnp.max(jnp.abs(full))) + 1e-9
        assert err / scale < 1e-4, (arch, err, scale)

    def test_shape_applicability(self, arch):
        cfg = get_config(arch)
        long_ok = shape_applicable(cfg, SHAPES["long_500k"])
        assert long_ok == (cfg.family in ("ssm", "hybrid"))
        for name in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(cfg, SHAPES[name])


class TestParamAccounting:
    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_param_breakdown_matches_init(self, arch):
        cfg = smoke_config(arch)
        model = Model(cfg)
        params = model.init_params(RNG)
        actual = sum(leaf.size for leaf in jax.tree.leaves(params))
        expected = cfg.param_count()
        # breakdown is analytic; allow small bookkeeping slack (pos tables,
        # per-layer norm extras) but catch order-of-magnitude errors.
        assert abs(actual - expected) / expected < 0.35, (arch, actual, expected)

    def test_full_config_param_counts(self):
        # Billions-scale sanity vs the assignment's named sizes.
        expect = {
            "qwen1_5_0_5b": 0.46, "nemotron_4_15b": 15.6, "qwen3_14b": 14.8,
            "smollm_135m": 0.135, "chameleon_34b": 34.3,
            "jamba_1_5_large_398b": 398.0, "whisper_small": 0.29,
            "grok_1_314b": 316.0, "phi3_5_moe_42b": 41.9, "mamba2_2_7b": 2.7,
            "granite_4_0_h_small": 32.2,
        }
        for arch, billions in expect.items():
            n = get_config(arch).param_count() / 1e9
            assert abs(n - billions) / billions < 0.10, (arch, n)


class TestMoEDispatch:
    def test_moe_output_is_gate_weighted_combination(self):
        from repro.models.layers.moe import apply_moe, init_moe

        cfg = smoke_config("phi3_5_moe_42b")
        params = init_moe(cfg, RNG)
        x = jax.random.normal(RNG, (4, 8, cfg.d_model), jnp.float32)
        out, aux = apply_moe(cfg, params, x)
        assert out.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(out)))
        assert float(aux) > 0.0

    def test_moe_capacity_drops_are_bounded(self):
        """Dispatch is dropless, so the drops are bounded by zero: every
        token reaches all of its top-k experts, however unevenly the batch
        routes. With every token sent to the same experts the layer equals
        the gate-weighted sum of those experts' FFNs."""
        from repro.models.layers import basic
        from repro.models.layers.moe import apply_moe, init_moe, route

        cfg = dataclasses.replace(smoke_config("grok_1_314b"),
                                  compute_dtype="float32")
        params = init_moe(cfg, RNG)
        # A router that prefers experts 0 and 1 for every token.
        params["router"] = jnp.zeros_like(params["router"]).at[:, :2].set(1.0)
        x = jnp.abs(jax.random.normal(RNG, (64, cfg.d_model), jnp.float32))
        out, _ = apply_moe(cfg, params, x)
        ids, gates, _ = route(cfg, params, x)
        assert bool(jnp.all(jnp.sort(ids, axis=-1) == jnp.array([0, 1])))
        want = sum(
            gates[:, j:j + 1] * basic.apply_ffn(
                cfg, {n: params[n][e] for n in ("w_gate", "w_up", "w_down")}, x)
            for j, e in enumerate(ids[0])
        )
        assert float(jnp.max(jnp.abs(out - want))) < 1e-4


class TestSSD:
    def test_chunked_matches_quadratic_reference(self):
        import numpy as np

        from repro.kernels.ref import ref_ssd
        from repro.models.layers.ssm import ssd_chunked

        k = jax.random.PRNGKey(3)
        b, s, h, p, g, n = 2, 64, 4, 8, 2, 16
        ks = jax.random.split(k, 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)))
        B_ = jax.random.normal(ks[3], (b, s, g, n))
        C_ = jax.random.normal(ks[4], (b, s, g, n))
        y, _ = ssd_chunked(x, dt, a, B_, C_, chunk=16)
        xdt = (x * dt[..., None]).transpose(0, 2, 1, 3)
        da = (dt * a[None, None, :]).transpose(0, 2, 1)
        y_ref = ref_ssd(
            xdt, da, B_.transpose(0, 2, 1, 3), C_.transpose(0, 2, 1, 3)
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_state_carry_across_calls(self):
        from repro.models.layers.ssm import ssd_chunked

        k = jax.random.PRNGKey(4)
        b, s, h, p, n = 1, 32, 2, 4, 8
        ks = jax.random.split(k, 5)
        x = jax.random.normal(ks[0], (b, s, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a = -jnp.exp(jax.random.normal(ks[2], (h,)))
        B_ = jax.random.normal(ks[3], (b, s, 1, n))
        C_ = jax.random.normal(ks[4], (b, s, 1, n))
        y_full, st_full = ssd_chunked(x, dt, a, B_, C_, chunk=8)
        h1 = s // 2
        y1, st1 = ssd_chunked(x[:, :h1], dt[:, :h1], a, B_[:, :h1], C_[:, :h1], 8)
        y2, st2 = ssd_chunked(
            x[:, h1:], dt[:, h1:], a, B_[:, h1:], C_[:, h1:], 8,
            initial_state=st1,
        )
        import numpy as np

        np.testing.assert_allclose(np.asarray(y_full[:, h1:]), np.asarray(y2),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st_full), np.asarray(st2),
                                   rtol=1e-4, atol=1e-4)


# One family each: plain GQA decoder, attention + mamba + MoE hybrid,
# encoder-decoder (its learned position table widened from the smoke
# config's 128 to the test's 256 positions), and the int8-quantised KV cache.
_MIXED_POSITION_CASES = {
    "lm": ("smollm_135m", {}),
    "hybrid": ("jamba_1_5_large_398b", {}),
    "encdec": ("whisper_small", {"max_position": 256}),
    "int8_kv": ("smollm_135m", {"kv_cache_dtype": "int8"}),
}


def _self_kv(cache):
    """The self-attention K/V of a cache, by name: each ``[L,B,KV,Dh,T]``,
    or an int8 ``{"q", "scale"}`` pair of such leaves."""
    if "self_k" in cache:
        return {name: cache[name] for name in ("self_k", "self_v")}
    return {f"{pos}.{kv}": c[kv] for pos, c in cache.items() if "k" in c
            for kv in ("k", "v")}


@pytest.mark.parametrize("case", sorted(_MIXED_POSITION_CASES))
def test_decode_slots_at_mixed_positions_match_forward(case):
    """One batched decode step over slots at positions 0, either side of a
    128-position tile boundary and ``max_len - 2``, each filled the way a
    replica admits a request (batch-1 prefill merged into its slot), gives
    each slot the logits of a full forward pass over that slot's tokens. It
    writes each slot's new K/V, as a prefill through the new token projects
    them, at the slot's position and leaves every other cache position
    bit-equal."""
    from repro.models.layers.attention import dequant_kv

    arch, extra = _MIXED_POSITION_CASES[case]
    cfg = dataclasses.replace(smoke_config(arch), compute_dtype="float32", **extra)
    model = Model(cfg)
    params = model.init_params(RNG)
    max_len, enc_len = 256, 8
    positions = [0, max_len // 2 - 1, max_len // 2, max_len - 2]
    keys = jax.random.split(jax.random.PRNGKey(1), 2 * len(positions))
    cache = model.init_cache(len(positions), max_len, enc_len=enc_len)
    expected, last, written = [], [], []
    for slot, pos in enumerate(positions):
        toks = jax.random.randint(keys[2 * slot], (1, pos + 1), 0, cfg.vocab_size)
        prompt = {"tokens": toks[:, :pos]}
        through = {"tokens": toks}
        if cfg.family == "encdec":
            frames = jax.random.normal(
                keys[2 * slot + 1], (1, enc_len, cfg.d_model), jnp.float32
            )
            enc = ed_mod.encode(cfg, params, frames)
            expected.append(ed_mod.decode_full(cfg, params, toks, enc)[0, -1])
            # The cross K/V come with a prompt; at position 0 its one token
            # is written again by the decode step.
            prompt = {"frames": frames, "tokens": toks[:, : max(pos, 1)]}
            through["frames"] = frames
        else:
            expected.append(lm_mod.forward(cfg, params, toks)[0][0, -1])
        if prompt["tokens"].shape[1]:
            one = model.init_cache(1, max_len, enc_len=enc_len)
            _, one = model.prefill(params, prompt, one)
            cache = jax.tree.map(
                lambda big, o, s=slot: big.at[:, s].set(o[:, 0]), cache, one
            )
        _, one = model.prefill(
            params, through, model.init_cache(1, max_len, enc_len=enc_len)
        )
        written.append(_self_kv(one))
        last.append(toks[0, pos])
    step, new_cache = model.decode(
        params, cache, jnp.stack(last), jnp.asarray(positions, jnp.int32)
    )
    assert jax.tree.map(jnp.shape, new_cache) == jax.tree.map(jnp.shape, cache)
    # int8 K/V round each value to 1/127 of its row's absmax.
    tol = 2e-2 if cfg.kv_cache_dtype == "int8" else 1e-4
    for slot, want in enumerate(expected):
        err = float(jnp.max(jnp.abs(step[slot, 0] - want)))
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        assert err / scale < tol, (case, positions[slot], err, scale)

    new_pos = (jnp.arange(max_len)[None, :]
               == jnp.asarray(positions)[:, None])[None, :, None, None, :]
    before, after = _self_kv(cache), _self_kv(new_cache)
    for name in before:
        kept = jax.tree.map(
            lambda a, b: bool(jnp.array_equal(jnp.where(new_pos, 0, a),
                                              jnp.where(new_pos, 0, b))),
            before[name], after[name],
        )
        assert all(jax.tree.leaves(kept)), (case, name)
        got = dequant_kv(after[name], jnp.float32)
        for slot, pos in enumerate(positions):
            want = dequant_kv(written[slot][name], jnp.float32)[:, 0, ..., pos]
            err = float(jnp.max(jnp.abs(got[:, slot, ..., pos] - want)))
            scale = float(jnp.max(jnp.abs(want))) + 1e-9
            assert err / scale < tol, (case, name, pos, err, scale)
    if cfg.family == "encdec":
        for name in ("cross_k", "cross_v"):
            assert bool(jnp.array_equal(cache[name], new_cache[name]))


class TestKvWrite:
    @pytest.mark.parametrize(
        "dtype,n_layers,slots,kvh,dh,t,pos",
        [
            # 128-position tiles, slots on either side of a tile boundary.
            (jnp.bfloat16, 2, 4, 2, 16, 256, (0, 127, 128, 255)),
            (jnp.int8, 3, 3, 2, 32, 384, (129, 0, 383)),
            # The int8 cache's scale leaf: head_dim 1.
            (jnp.float32, 2, 3, 4, 1, 128, (5, 127, 64)),
            # A position axis shorter than a tile is one tile.
            (jnp.float32, 1, 2, 3, 8, 48, (47, 1)),
            # One that is no multiple of 128: its last tile overlaps the one
            # before it.
            (jnp.bfloat16, 3, 3, 8, 128, 200, (199, 71, 128)),
        ],
    )
    def test_matches_scatter(self, dtype, n_layers, slots, kvh, dh, t, pos):
        """``write_kv`` puts slot b's token K/V at ``pos[b]`` of every leaf
        it is given, as a plain scatter does, into the cache a donating jit
        hands it."""
        from repro.models.layers.attention import write_kv

        ks = jax.random.split(RNG, 4)
        shape = (n_layers, slots, kvh, dh, t)
        cache = {kv: (jax.random.normal(k, shape) * 50).astype(dtype)
                 for kv, k in zip("kv", ks[:2])}
        new = {kv: (jax.random.normal(k, shape[:-1]) * 50).astype(dtype)
               for kv, k in zip("kv", ks[2:])}
        pos = jnp.asarray(pos, jnp.int32)
        slot = jnp.arange(slots)
        expect = {kv: cache[kv].at[:, slot, :, :, pos].set(
            new[kv].transpose(1, 0, 2, 3)) for kv in cache}
        given = jax.tree.map(jnp.copy, cache)
        out = jax.jit(write_kv, donate_argnums=(0,))(given, new, pos)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(given))
        for kv in cache:
            assert bool(jnp.array_equal(out[kv], expect[kv])), kv
