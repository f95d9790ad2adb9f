"""Spans of the served path: a tiny engine served under a CPU profiler
session writes each ``serve.*`` span, nested as the calls nest, with the
arguments the engine had at hand; with no session a span is the shared
null context. Each request stamps its own times, which the launcher
reports."""
import contextlib
import glob
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import smoke_config
from repro.launch.serve import build_engine
from repro.models import Model
from repro.runtime.spans import span

NAMES = ("serve.tick", "serve.admit", "serve.step", "serve.sync")
#: The span each span sits directly inside.
PARENTS = {
    "serve.admit": {"serve.tick"},
    "serve.step": {"serve.tick"},
    "serve.sync": {"serve.admit", "serve.step"},
}


def _read_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith("serve.")]
    return out


def _parent(child, spans):
    """The innermost span that holds ``child``."""
    holders = [s for s in spans if s is not child
               and s[1] <= child[1] and child[2] <= s[2]]
    return min(holders, key=lambda s: s[2] - s[1])[0] if holders else None


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    cfg = smoke_config("smollm_135m")
    params = Model(cfg).init_params(jax.random.PRNGKey(0))
    engine = build_engine(cfg, params, slots=2, max_len=48)
    rng = np.random.default_rng(0)

    def prompt():
        return rng.integers(1, cfg.vocab_size, size=8, dtype=np.int32)

    # Compile every program before the session opens.
    engine.submit(cfg.name, prompt(), max_new_tokens=3)
    engine.run_until_done(max_ticks=20)

    steps = {name: 0 for name in engine.replicas}
    for replica in engine.replicas.values():
        def counted(step=replica.step, replica=replica):
            if replica.active:
                steps[replica.name] += 1
            return step()
        replica.step = counted
    ticks = engine.tick

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        # Five requests on four slots: one waits a tick for a free slot.
        requests = [engine.submit(cfg.name, prompt(), tag=tag, max_new_tokens=4)
                    for tag in ("interactive", "batch", None, "interactive", None)]
        engine.run_until_done(max_ticks=50)
    finally:
        jax.profiler.stop_trace()
    devices = {name: r.device.id for name, r in engine.replicas.items()}
    return requests, steps, devices, engine.tick - ticks, _read_spans(trace_dir)


def test_every_span_is_written(served):
    *_, spans = served
    assert {s[0] for s in spans} == set(NAMES)


def test_spans_nest_as_the_calls_do(served):
    *_, spans = served
    for s in spans:
        if s[0] in PARENTS:
            assert _parent(s, spans) in PARENTS[s[0]], s
    assert all(_parent(s, spans) is None for s in spans if s[0] == "serve.tick")


def test_one_tick_span_per_engine_tick(served):
    *_, ticks, spans = served
    assert sum(s[0] == "serve.tick" for s in spans) == ticks


def test_admissions_name_their_requests(served):
    requests, *_, spans = served
    assert all(r.state == "done" for r in requests)
    admits = [s for s in spans if s[0] == "serve.admit"]
    ids = sorted(r.request_id for r in requests)
    assert sorted(s[3]["request"] for s in admits) == ids
    admit_syncs = [s for s in spans if s[0] == "serve.sync" and s[3]["kind"] == "admit"]
    assert sorted(s[3]["request"] for s in admit_syncs) == ids


def test_steps_and_admissions_name_their_replicas_device(served):
    requests, steps, devices, _, spans = served
    by_request = {r.request_id: r.replica for r in requests}
    for s in spans:
        if s[0] == "serve.admit":
            assert s[3]["device"] == devices[by_request[s[3]["request"]]]
    step_devices = sorted(s[3]["device"] for s in spans if s[0] == "serve.step")
    assert step_devices == sorted(devices[name] for name, n in steps.items()
                                  for _ in range(n))


def test_one_sync_per_decode_step_and_admission(served):
    requests, steps, _, _, spans = served
    syncs = [s for s in spans if s[0] == "serve.sync"]
    decode_steps = sum(steps.values())
    assert len(syncs) == decode_steps + len(requests)
    assert sum(s[3]["kind"] == "decode" for s in syncs) == decode_steps


def test_requests_stamp_each_change_of_state(served):
    requests, *_ = served
    for r in requests:
        assert r.submitted_s <= r.admitted_s <= r.first_token_s <= r.finished_s


def test_span_is_the_shared_null_context_when_not_profiling():
    assert not TraceAnnotation.is_enabled()
    off = span("serve.tick")
    assert isinstance(off, contextlib.nullcontext)
    assert span("serve.step", device=0) is off
    with off:
        pass


def test_launcher_reports_times_from_the_request_stamps(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--reduced", "--max-len", "32",
         "--prompt-lens", "4", "8", "--requests", "4", "--max-new-tokens", "3",
         "--slots", "2"],
        env=env, capture_output=True, text=True, timeout=300, check=True).stdout
    assert "done=4" in out
    for label in ("wait", "ttft", "latency"):
        line = next(l for l in out.splitlines() if l.startswith(f"{label} ms: "))
        p50, most = (float(x.split("=")[1]) for x in line.split(": ")[1].split())
        assert 0 <= p50 <= most
