"""Trace reduction on hand-built events: busy time is the union of op
intervals, module time is summed by program name, and idle gaps go to the
innermost host span."""
import pytest

from bench import trace as tr

MS = 1_000_000  # ns


def test_union_merges_overlaps_and_busy_clips_to_the_window():
    ops = [("a", 0, 4 * MS), ("b", 2 * MS, 6 * MS), ("c", 8 * MS, 9 * MS)]
    assert tr.union(ops) == [(0, 6 * MS), (8 * MS, 9 * MS)]
    assert tr.busy_ns(ops, 1 * MS, 10 * MS) == 6 * MS
    assert tr.gaps(ops, 1 * MS, 10 * MS) == [(6 * MS, 8 * MS), (9 * MS, 10 * MS)]


def test_totals_by_program_and_op_name():
    events = [("jit_decode(12)", 0, 3 * MS), ("jit_decode(12)", 5 * MS, 7 * MS),
              ("jit_prefill_b1(9)", 8 * MS, 9 * MS),
              ("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", 0, 2 * MS)]
    got = tr.totals(events)
    assert got["%fusion.3"] == (pytest.approx(0.002), 1)
    assert got["jit_decode"] == (pytest.approx(0.005), 2)
    assert got["jit_prefill_b1"] == (pytest.approx(0.001), 1)


def test_idle_goes_to_the_innermost_span():
    spans = [("bench.step_once", 0, 10 * MS), ("bench.admit", 1 * MS, 4 * MS),
             ("bench.observe", 11 * MS, 12 * MS)]
    idle = [(0, 2 * MS), (9 * MS, 13 * MS)]
    got = tr.attribute(idle, spans)
    assert got["bench.step_once"] == pytest.approx(0.002)
    assert got["bench.admit"] == pytest.approx(0.001)
    assert got["bench.observe"] == pytest.approx(0.001)
    assert got[tr.UNLABELLED] == pytest.approx(0.002)


def test_summary_over_two_devices():
    devices = {
        0: {tr.OPS_LINE: [("fusion.1", 0, 5 * MS)],
            tr.MODULES_LINE: [("jit_decode(3)", 0, 5 * MS)]},
        1: {tr.OPS_LINE: [("fusion.1", 2 * MS, 4 * MS), ("copy.2", 20 * MS, 30 * MS)],
            tr.MODULES_LINE: [("jit_decode(3)", 2 * MS, 4 * MS)]},
    }
    host = [("bench.trace_window", 0, 10 * MS), ("bench.replica_step", 0, 10 * MS),
            ("other", 0, 10 * MS)]
    window = tr.window_of(host, "bench.trace_window")
    s = tr.summarize_events(devices, host, window)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == {0: pytest.approx(0.005), 1: pytest.approx(0.002)}
    assert s.idle_share() == pytest.approx(1 - (0.5 + 0.2) / 2)
    assert s.modules["jit_decode"] == [pytest.approx(0.005), pytest.approx(0.002)]
    assert s.ops["fusion.1"] == (pytest.approx(0.007), 2)
    # Idle per device, averaged: (5 + 8) / 2 ms, all inside replica_step.
    assert s.idle_by_host == {"bench.replica_step": pytest.approx(0.0065)}


def test_no_window_no_summary():
    assert tr.window_of([("bench.step_once", 0, 1)], "bench.trace_window") is None


def test_timeline_labels_the_innermost_span():
    spans = [("outer", 0, 10), ("inner", 2, 4), ("inner2", 6, 7)]
    assert tr.timeline(spans) == [("outer", 0, 2), ("inner", 2, 4), ("outer", 4, 6),
                                  ("inner2", 6, 7), ("outer", 7, 10)]


def test_attribution_is_linear_in_gaps_and_spans():
    import time

    spans = [("bench.tick", 10 * i, 10 * i + 8) for i in range(20000)]
    idle = [(10 * i + 7, 10 * i + 9) for i in range(20000)]
    t0 = time.perf_counter()
    got = tr.attribute(idle, spans)
    assert time.perf_counter() - t0 < 2.0
    assert got["bench.tick"] == pytest.approx(20000e-9)
    assert got[tr.UNLABELLED] == pytest.approx(20000e-9)


def test_roofline_pairs_runs_with_calls_from_the_end():
    """A run the trace missed at its start leaves the earliest call out,
    and the share stays the least time over the device time of the same
    calls."""
    from bench.harness import Call, LayerData, Spans, load_reader

    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    calls = [Call("prefill", 0, (9.0, 0.0)), Call("prefill", 0, (1.0, 0.0)),
             Call("prefill", 0, (2.0, 0.0))]
    summary = tr.Summary(window_s=10.0, busy_s={0: 5.0},
                         modules={"jit_prefill_b1": [2.0, 4.0]}, ops={},
                         idle_by_host={})
    data = LayerData(chips=1, peak=peak, spans=Spans({}), calls=calls,
                     trace_window_s=10.0, trace=summary)
    assert load_reader("prefill_roofline")(data) == pytest.approx(100 * 3.0 / 6.0)
    assert load_reader("decode_roofline")(data) is None
    assert load_reader("device_idle_share")(data) == pytest.approx(50.0)


def test_an_entry_of_one_cell_reads_through_its_base_reader():
    """``prefill_roofline.code`` (an entry with bounds or cells of its own)
    has no file of its own and reads as ``prefill_roofline``."""
    from bench.harness import Call, LayerData, Spans, base_name, load_reader

    assert base_name("prefill_roofline.code") == "prefill_roofline"
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e30}
    summary = tr.Summary(window_s=10.0, busy_s={0: 5.0},
                         modules={"jit_prefill_b1": [4.0]}, ops={}, idle_by_host={})
    data = LayerData(chips=1, peak=peak, spans=Spans({}),
                     calls=[Call("prefill", 0, (2.0, 0.0))], trace_window_s=10.0,
                     trace=summary)
    assert load_reader("prefill_roofline.code")(data) == \
        load_reader("prefill_roofline")(data) == pytest.approx(50.0)


def test_scopes_sum_op_time_by_name_stack():
    """Op events named by their name stack: each stack's device seconds
    summed over devices and clipped to the window; an op without one counts
    under ``""``. Busy time, ops and modules read as they do without the
    stacks."""
    attn, ffn = "jit(decode)/while/body/attn/dot_general", "jit(decode)/while/body/ffn/mul"
    scan = "jit(prefill_b1)/ssd_scan/exp"
    raw = {0: [("%fusion.1 = f32[8]", 0, 4, attn), ("%fusion.2 = f32[8]", 4, 6, ffn),
               ("%copy.3 = f32[8]", 6, 7, "")],
           1: [("%fusion.1 = f32[8]", 1, 3, attn), ("%fusion.9 = f32[8]", 8, 14, scan)]}
    devices = {dev: {tr.OPS_LINE: [(n, a * MS, b * MS) for n, a, b, _ in events],
                     tr.SCOPES_LINE: [(k, a * MS, b * MS) for _, a, b, k in events],
                     tr.MODULES_LINE: [("jit_decode(3)", 0, 7 * MS)]}
               for dev, events in raw.items()}
    host = [("bench.trace_window", 0, 10 * MS)]
    s = tr.summarize_events(devices, host, tr.window_of(host, "bench.trace_window"))
    assert s.scopes == {attn: pytest.approx(0.006), ffn: pytest.approx(0.002),
                        "": pytest.approx(0.001), scan: pytest.approx(0.002)}
    assert sum(s.scopes.values()) == pytest.approx(sum(v[0] for v in s.ops.values()))
    plain = tr.summarize_events({d: {k: v for k, v in ls.items() if k != tr.SCOPES_LINE}
                                 for d, ls in devices.items()}, host, (0, 10 * MS))
    assert (plain.ops, plain.modules, plain.busy_s) == (s.ops, s.modules, s.busy_s)
    assert plain.scopes == {"": pytest.approx(sum(s.scopes.values()))}


def test_name_stacks_come_from_the_event_metadata(tmp_path):
    """A hand-built trace file: the stack stat on an op's metadata, as a
    string or as a reference to a stat metadata's name; an op without it,
    and a plane not asked for, read as nothing."""
    from bench import xplane

    space = xplane._space_class()()
    for plane_name in ("/device:TPU:0", "/device:TPU:5"):
        plane = space.planes.add(name=plane_name)
        plane.stat_metadata[1].name = tr.NAME_STACK_STAT
        plane.stat_metadata[2].name = "flops"
        plane.stat_metadata[3].name = "jit(decode)/while/body/ffn/mul"
        plane.event_metadata[10].stats.add(metadata_id=1, str_value="jit(decode)/attn/dot")
        plane.event_metadata[10].stats.add(metadata_id=2, str_value="64")
        plane.event_metadata[11].stats.add(metadata_id=1, ref_value=3)
        plane.event_metadata[12].stats.add(metadata_id=2, str_value="8")
        line = plane.lines.add(name=tr.OPS_LINE)
        for key in (10, 11, 12, 10):
            line.events.add(metadata_id=key)
        plane.lines.add(name=tr.MODULES_LINE).events.add(metadata_id=10)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    got = xplane.op_stacks(str(path), tr.OPS_LINE, tr.NAME_STACK_STAT,
                           lambda name: name == "/device:TPU:0")
    assert got == {"/device:TPU:0": ["jit(decode)/attn/dot", "jit(decode)/while/body/ffn/mul",
                                     "", "jit(decode)/attn/dot"]}


def test_events_without_stacks_count_under_the_empty_stack():
    """The events of the two-device summary above carry no stack: their
    time is all under ``""`` and equals the ops' time."""
    devices = {0: {tr.OPS_LINE: [("fusion.1", 0, 5 * MS)]},
               1: {tr.OPS_LINE: [("fusion.1", 2 * MS, 4 * MS), ("copy.2", 20 * MS, 30 * MS)]}}
    s = tr.summarize_events(devices, [], (0, 10 * MS))
    assert s.scopes == {"": pytest.approx(0.007)}
    assert s.ops["fusion.1"] == (pytest.approx(0.007), 2)
