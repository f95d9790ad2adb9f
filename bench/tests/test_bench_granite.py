"""The Granite family: its weights fit the program and count what the
program counts, its work counts are pinned and split by mechanism, a
traced CPU rehearsal of a Granite cell through the family and its float32
reference comes out correct, and each fault the hybrid path can have comes
out not correct."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import _bench_granite as gt
import _bench_tiny as tiny
from bench import harness
from bench.families import granite
from repro.models.layers import moe
from repro.runtime import serve_engine

CUT = harness.load_config("granite-4.0-h-small")


def test_weight_count_is_the_programs_param_count():
    cfg = granite.program_config(CUT)
    assert granite.weight_count(CUT) == cfg.param_count() == 2_414_692_992
    parts = granite.part_weights(CUT)
    # Nine Mamba-2 mixers, one attention layer, ten expert layers, the head.
    assert parts["mamba"] == 9 * 102_282_624 + 9 * 8448
    assert parts["attn"] == 41_943_040 + 4096
    assert parts["head"] == 100352 * 4096 + 4096
    assert cfg.moe_held == 9 and cfg.moe_experts == 72 and cfg.n_layers == 10


def test_weights_fit_the_program_at_a_small_size():
    c = gt.tiny_granite()
    cfg = granite.program_config(c)
    tree = granite.program_tree(jax.eval_shape(lambda: granite.make_weights(c, 3)))
    from repro.models import Model

    want = jax.eval_shape(Model(cfg).init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), tree) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), want)


#: (FLOPs, bytes) of a 1024-token prefill and of a decode step of 32 slots
#: at positions 1000..1031, at the cut's published widths.
PINNED = {
    "prefill": (2652570124288.0, 4872642816.0),
    "decode": (109438042112.0, 7449777408.0),
}


@pytest.mark.parametrize("kind", sorted(PINNED))
def test_work_is_pinned_and_its_parts_sum_to_it(kind):
    work = (granite.prefill_work(CUT, 1024) if kind == "prefill"
            else granite.decode_work(CUT, list(range(1000, 1032))))
    assert work == pytest.approx(PINNED[kind], rel=1e-9)
    assert set(work.parts) == {"mamba", "attn", "moe", "head"}
    for i in (0, 1):
        assert sum(p[i] for p in work.parts.values()) == pytest.approx(work[i])


def test_decode_bytes_are_weights_state_and_live_kv():
    """A decode step of 32 slots reads every weight once (4.83 GB), reads
    and writes each slot's float32 Mamba-2 state (9 layers x 4.19 MB of
    state and 0.10 MB of conv window), and reads K/V over live positions."""
    positions = [1000] * 32
    work = granite.decode_work(CUT, positions)
    state = 4 * (128 * 64 * 128 + 3 * 8448)
    kv = 2 * 8 * 128 * 2
    want = 2 * 2_414_692_992 + 9 * 2 * 32 * state + kv * (32 * 1000 + 32) \
        + 4 * 32 * 100352
    assert work[1] == want
    assert granite.decode_work(CUT, []) == (0.0, 0.0)


@pytest.fixture(scope="module")
def sound():
    return tiny.run(c=gt.tiny_granite(), mix=tiny.tiny_mix("azure-conv-tail"),
                    control=True)


def test_a_granite_cell_is_correct_and_its_control_is_not(sound):
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    limit = sound["checks"]["logit_gap"]["limit"]
    assert sound["control"]["checks"]["logit_gap"]["value"] > limit > \
        sound["checks"]["logit_gap"]["value"]
    assert sound["control"]["correct"] is False


def test_a_traced_granite_cell_reports_every_metric_it_can(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    result = tiny.run(c=gt.tiny_granite(), mix=tiny.tiny_mix("azure-conv-tail"),
                      trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert {"admit_ms", "decode_step_ms", "mfu", "prefill_mfu", "decode_mfu",
            "sched_us_per_decision"} <= set(got)
    assert all(m["value"] > 0 for m in got.values())


def capacity_dropping(monkeypatch):
    """Switch-style dispatch: each held expert takes at most k * T / E
    tokens of a call, in token order, and drops the rest."""
    def group(cfg, params, x2d):
        t = x2d.shape[0]
        ids, gates, aux = moe.route(cfg, params, x2d)
        cap = max(1, t * cfg.moe_top_k // cfg.moe_experts)
        out = jnp.zeros_like(x2d)
        for e in range(cfg.experts_held):
            hit = ids == cfg.moe_first + e                          # [T, k]
            kept = hit & (jnp.cumsum(hit.any(-1)) <= cap)[:, None]
            weight = jnp.sum(jnp.where(kept, gates, 0.0), axis=-1)
            w = {n: params[n][e] for n in ("w_gate", "w_up", "w_down")}
            out = out + weight[:, None].astype(x2d.dtype) * moe.apply_ffn(cfg, w, x2d)
        return out, aux
    monkeypatch.setattr(moe, "_moe_groups", lambda cfg, params, xg: jax.vmap(
        lambda x2d: group(cfg, params, x2d))(xg))


def shared_expert_skipped(monkeypatch):
    """The shared expert adds nothing."""
    monkeypatch.setattr(moe, "apply_ffn", lambda cfg, p, x: jnp.zeros_like(x))


def rope_applied(monkeypatch):
    """The attention layer rotates Q and K (the block is NoPE)."""
    sound = granite.program_config
    monkeypatch.setattr(granite, "program_config",
                        lambda c: dataclasses.replace(sound(c), pos_embedding="rope"))


def ssm_state_zeroed_at_admission(monkeypatch):
    """The slot merge carries the K/V and conv windows but not the Mamba-2
    state, which starts from zero."""
    sound = serve_engine.Replica.prefill

    def prefill(self, slot, tokens):
        out = sound(self, slot, tokens)
        self.cache = {k: dict(v, ssm=v["ssm"].at[:, slot].set(0.0)) if "ssm" in v else v
                      for k, v in self.cache.items()}
        return out
    monkeypatch.setattr(serve_engine.Replica, "prefill", prefill)


@pytest.mark.parametrize("fault", [capacity_dropping, shared_expert_skipped,
                                   rope_applied, ssm_state_zeroed_at_admission])
def test_fault_makes_a_granite_run_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = tiny.run(seed=2**33 + 7, c=gt.tiny_granite(),
                      mix=tiny.tiny_mix("azure-conv-closed"))
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > result["checks"]["logit_gap"]["limit"]
