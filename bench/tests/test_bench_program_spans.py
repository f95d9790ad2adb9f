"""Readers of the program's own spans, on hand-built windows and on a traced
CPU rehearsal: syncs per ``serve.tick``, and a device's idle inside its own
``serve.step`` and ``serve.admit`` spans, idle that straddles a span's edge
counted only inside."""
import importlib.util
import os
import types

import pytest

import _bench_tiny as tiny
from bench import harness
from bench import program_spans as ps

MS = 1_000_000  # ns


def reader(name):
    """The metric module ``bench/metrics/<name>.py``, for its ``of``."""
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", harness.METRIC_DIR / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def span(name, start_ms, end_ms, **args):
    return ps.Span(name, start_ms * MS, end_ms * MS, args)


def window(spans, ops=None):
    return ps.ProgramSpans((0, 20 * MS), spans, ops or {})


TICKS = [span("serve.tick", 0, 10), span("serve.tick", 10, 20)]


def test_syncs_are_counted_per_tick():
    got = reader("syncs_per_tick").of(window(TICKS + [
        span("serve.sync", 1, 2, kind="admit", request=3),
        span("serve.sync", 5, 6, kind="decode"),
        span("serve.sync", 15, 16, kind="decode")]))
    assert got == pytest.approx(1.5)


def test_idle_inside_a_span_takes_only_the_part_inside():
    """Device 0 idles 4–8 ms and 12–14 ms. A step spans 6–12 ms and
    another 13–16 ms, so 2 ms and 1 ms of that idle lie inside steps.
    Device 1 never idles."""
    ops = {0: [("op", 0, 4 * MS), ("op", 8 * MS, 12 * MS), ("op", 14 * MS, 20 * MS)],
           1: [("op", 0, 20 * MS)]}
    steps = [span("serve.step", 6, 12, device=0), span("serve.step", 13, 16, device=0)]
    admits = [span("serve.admit", 2, 5, request=1, device=0)]
    w = window(TICKS + steps + admits, ops)
    # Device 0: (2 + 1) ms of 20 inside steps; device 1: 0; mean 7.5%.
    assert reader("idle_in_step_share").of(w) == pytest.approx(7.5)
    # Device 0: 4–5 ms inside the admission; mean over devices 2.5%.
    assert reader("idle_in_admit_share").of(w) == pytest.approx(2.5)


def test_idle_counts_only_under_the_devices_own_spans():
    """Two replicas on two devices step one after another: each device idles
    while the other one's step runs. None of that idle lies inside a step of
    the idle device, so none of it is idle in a step."""
    ops = {0: [("op", 0, 10 * MS)], 1: [("op", 10 * MS, 20 * MS)]}
    steps = [span("serve.step", 0, 10, device=0), span("serve.step", 10, 20, device=1)]
    w = window(TICKS + steps, ops)
    assert reader("idle_in_step_share").of(w) == pytest.approx(0.0)
    # With both steps put on device 0, device 0's idle 10–20 ms counts:
    # 50% on device 0, 0% on device 1.
    w = window(TICKS + [span("serve.step", 0, 10, device=0),
                        span("serve.step", 10, 20, device=0)], ops)
    assert reader("idle_in_step_share").of(w) == pytest.approx(25.0)


def test_overlap_of_interval_lists():
    assert ps.overlap_ns([(0, 4), (6, 10)], [(2, 7), (9, 12)]) == 2 + 1 + 1
    assert ps.overlap_ns([], [(0, 1)]) == 0


def test_spans_are_clipped_to_the_window_with_their_arguments():
    got = ps.clip_spans([span("serve.admit", -1, 1, request=5, device=0),
                         span("serve.tick", 25, 30)], 0, 20 * MS)
    assert got == [ps.Span("serve.admit", 0, 1 * MS, {"request": 5, "device": 0})]


def test_each_trace_file_is_read_once(tmp_path, monkeypatch):
    """The readers of one run share one parse; a newer file is read anew."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    reads = []
    monkeypatch.setattr(ps, "_read", lambda path, devices: reads.append(path) or path)
    d = types.SimpleNamespace(trace_window_s=4.0, trace=None)
    (tmp_path / "a.xplane.pb").write_bytes(b"a")
    assert ps.load(d) == ps.load(d) == str(tmp_path / "a.xplane.pb")
    assert len(reads) == 1
    (tmp_path / "b.xplane.pb").write_bytes(b"bb")
    os.utime(tmp_path / "b.xplane.pb", (1e10, 1e10))
    assert ps.load(d) == str(tmp_path / "b.xplane.pb") and len(reads) == 2


@pytest.mark.parametrize("name", ["syncs_per_tick", "idle_in_step_share",
                                  "idle_in_admit_share"])
def test_readers_are_silent_without_their_spans(name):
    """A window with no span of the reader's kind, and a run that was not
    traced, read as nothing."""
    assert reader(name).of(window([])) is None
    untraced = types.SimpleNamespace(trace_window_s=0.0, trace=None)
    assert harness.load_reader(name)(untraced) is None


def test_traced_rehearsal_reports_the_programs_spans(tmp_path, monkeypatch):
    """On the CPU the device metrics stay silent; the span metrics are
    positive, and the program's ``serve.admit`` spans time admission as the
    harness's wrapper does."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    result = tiny.run(trace=True)
    assert result["correct"] is True
    got = result["metrics"]
    assert got["syncs_per_tick"]["value"] > 0
    assert "idle_in_step_share" not in got and "idle_in_admit_share" not in got
    spans = ps.load(types.SimpleNamespace(trace_window_s=1.0, trace=None))
    admits = spans.named("serve.admit")
    mean_ms = sum(s.end - s.start for s in admits) / len(admits) / MS
    assert mean_ms == pytest.approx(got["admit_ms"]["value"], rel=0.2)
