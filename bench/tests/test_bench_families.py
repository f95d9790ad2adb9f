"""The harness reaches everything that knows a model's shape through the
family its configuration names: the Llama family records the same work as
before it moved into ``bench/families/llama.py``, the harness names no key
of a Llama configuration, and a family that only the tests define runs a
whole traced cell."""
import pathlib
import re
import sys
import types

import jax
import numpy as np
import pytest

import _bench_hybrid_family as hybrid
import _bench_tiny as tiny
from bench import harness
from repro.runtime.serve_engine import Request

BENCH = pathlib.Path(harness.__file__).resolve().parent

#: ``Call.work`` of three admissions (prompts of 8, 24 and 32 tokens) and
#: three decode steps on one replica of the tiny configurations, as the
#: parent commit's ``bench/counts.py`` gave them.
PARENT_WORK = {
    "smollm-135m": [
        ("prefill", 0, (9641984.0, 1323264.0)), ("prefill", 0, (29057024.0, 1339648.0)),
        ("prefill", 0, (38961152.0, 1347840.0)), ("decode", 0, (4069376.0, 1387776.0)),
        ("decode", 0, (4075520.0, 1390848.0)), ("decode", 0, (4081664.0, 1393920.0))],
    "qwen1.5-0.5b": [
        ("prefill", 0, (9641984.0, 1325312.0)), ("prefill", 0, (29057024.0, 1341696.0)),
        ("prefill", 0, (38961152.0, 1349888.0)), ("decode", 0, (4069376.0, 1389824.0)),
        ("decode", 0, (4075520.0, 1392896.0)), ("decode", 0, (4081664.0, 1395968.0))],
}


@pytest.mark.parametrize("name", sorted(PARENT_WORK))
def test_spans_record_the_same_work_as_before(name):
    c = tiny.tiny_config(name)
    cfg, engine = harness.build(c, 2**33 + 5, jax.devices()[:1])
    spans = harness.Spans(c)
    spans.install(engine)
    spans.tracing = True
    replica = engine.replicas["edge-0"]
    rng = np.random.default_rng(0)
    for i, n in enumerate((8, 24, 32)):
        tokens = rng.integers(1, 500, n, dtype=np.int32)
        assert replica.admit(Request(i, cfg.name, tokens, max_new_tokens=50), None)
    for _ in range(3):
        replica.step()
    assert [(x.kind, x.device, x.work) for x in spans.traced] == PARENT_WORK[name]


def test_harness_names_no_key_of_a_model():
    keys = re.compile(r"num_key_value_heads|intermediate_size|rope_theta|rms_norm_eps"
                      r"|attention_bias|qkv_bias|hidden_size|num_hidden_layers")
    for path in (BENCH / "harness.py", BENCH / "counts.py"):
        assert not keys.search(path.read_text()), path


def test_family_and_reference_are_found_by_one_name():
    c = harness.load_config("smollm-135m")
    fam = harness.family(c)
    assert fam.__name__ == f"bench.families.{c['reference']}"
    assert (BENCH / "reference" / f"{c['reference']}.py").exists()
    assert fam.model_keys(c)["num_key_value_heads"] == 3


@pytest.fixture
def hybrid_family(monkeypatch):
    for package in ("families", "reference"):
        monkeypatch.setitem(sys.modules, f"bench.{package}.{hybrid.NAME}", hybrid)
    return dict(tiny.tiny_config(), name="hybrid-smoke", reference=hybrid.NAME)


def test_a_family_only_the_tests_know_runs_a_traced_cell(hybrid_family, tmp_path,
                                                         monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    result = tiny.run(c=hybrid_family, trace=True)
    assert result["correct"] is True
    assert result["checks"]["logit_gap"]["value"] <= result["checks"]["logit_gap"]["limit"]
    got = result["metrics"]
    assert {"mfu", "prefill_mfu", "decode_mfu", "admit_ms", "decode_step_ms"} <= set(got)
    assert all(m["value"] > 0 for m in got.values())


def test_no_file_of_the_benchmark_names_the_test_family():
    for path in BENCH.rglob("*"):
        if path.is_file() and "tests" not in path.relative_to(BENCH).parts \
                and ".cache" not in path.parts and path.suffix in (".py", ".json"):
            assert hybrid.NAME not in path.read_text(), path


def _record(replica, length):
    request = types.SimpleNamespace(state="done", output=[1] * 3, replica=replica)
    spec = types.SimpleNamespace(prompt_len=length, tokens=np.ones(length, np.int32))
    return types.SimpleNamespace(request=request, spec=spec)


def test_sample_takes_the_longest_and_every_replica():
    """Four replicas, each with prompts of its own length; the longest
    request is on ``cloud-1``. Every seed's sample of six holds it and one
    request of each other replica."""
    lengths = {"edge-0": 11, "edge-1": 12, "cloud-0": 13, "cloud-1": 14}
    records = [_record(name, n) for name, n in lengths.items() for _ in range(30)]
    records.append(_record("cloud-1", 500))
    for seed in range(20):
        chosen = [len(s.prompt) for s in harness.sample(records, 6, seed)]
        assert len(chosen) == 6 and chosen[0] == 500
        assert {11, 12, 13} <= set(chosen[1:4])
    # Fewer finished requests than asked for: every one of them.
    assert len(harness.sample(records[:3], 6, 1)) == 3
