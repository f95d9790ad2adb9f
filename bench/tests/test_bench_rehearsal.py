"""CPU rehearsal of a benchmark run: the harness's functions drive one
cell's loop at a tiny size and the result line has the contract's shape."""
import json

import pytest

import _bench_tiny as tiny

END_TO_END = {"ttft_p95_ms", "itl_p95_ms", "latency_p95_ms", "output_tok_s", "setup_s"}


@pytest.fixture(scope="module")
def sound():
    return tiny.run(control=True)


def test_open_loop_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert set(sound["metrics"]) == END_TO_END
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    assert sound["device"]["count"] == 1
    assert list(sound)[-1] == "checks"
    checks = sound["checks"]
    assert checks["misplaced"]["value"] == 0 and checks["unfinished"]["value"] == 0
    assert 0 <= checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    json.dumps(sound)


def test_control_reads_above_the_limit(sound):
    """fp8 matrix products in the reference's place fail the limit that
    sound bfloat16 runs meet, and the run's own checks call them not
    correct."""
    limit = sound["checks"]["logit_gap"]["limit"]
    control = sound["control"]
    assert control["checks"]["logit_gap"]["value"] > limit > \
        sound["checks"]["logit_gap"]["value"]
    assert control["correct"] is False


def test_closed_loop_run_is_correct():
    result = tiny.run(mix=tiny.tiny_mix("azure-conv-closed"))
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0


def test_traced_run_reports_span_metrics():
    result = tiny.run(trace=True)
    assert result["correct"] is True
    got = set(result["metrics"])
    assert {"sched_us_per_decision", "admit_ms", "engine_host_ms_per_tick",
            "decode_step_ms", "mfu", "prefill_mfu", "decode_mfu"} <= got
    assert not got & END_TO_END
    assert all(0 < m["value"] for m in result["metrics"].values())
    assert result["metrics"]["mfu"]["value"] < 100


def test_qwen_layout_runs_with_bias():
    c = tiny.tiny_config("qwen1.5-0.5b")
    result = tiny.run(c=c, mix=tiny.tiny_mix("azure-conv"))
    assert result["correct"] is True


def test_command_refuses_a_cpu():
    """Without a TPU the command prints no result and exits non-zero."""
    import os
    import pathlib
    import subprocess
    import sys

    root = pathlib.Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-chat", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr
