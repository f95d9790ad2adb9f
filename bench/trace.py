"""Reduction of a profiler trace to device busy time, module time, time per
name stack and idle gaps, on the trace's own clock.

The pure functions take plain ``(name, start_ns, end_ns)`` tuples, so they
can be checked on hand-built event lists; ``read_xplane`` reads an
``.xplane.pb`` through ``jax.profiler.ProfileData``. A TPU device plane is
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per operation and
its ``XLA Modules`` line one per program run (``jit_<name>(<id>)``). Each
op event's metadata carries the HLO op's ``op_name`` metadata, the JAX
name stack (``jax.named_scope`` paths among it), in its
``NAME_STACK_STAT`` stat (read by ``bench/xplane.py``). Host spans are the
``TraceAnnotation`` events on ``/host:CPU``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import xplane

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: The op events of ``OPS_LINE`` named by their name stack instead of their
#: HLO text (not a line of the trace: ``read_xplane`` builds it).
SCOPES_LINE = "XLA Ops by name stack"
#: The stat of an op event's metadata that holds the HLO op's ``op_name``.
NAME_STACK_STAT = "tf_op"
HOST_PLANE = "/host:CPU"
#: Host activity outside every annotated span.
UNLABELLED = "host (no span)"


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """The parts of ``events`` inside ``[lo, hi]``."""
    out = []
    for name, start, end in events:
        start, end = max(start, lo), min(end, hi)
        if end > start:
            out.append((name, start, end))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Disjoint, sorted intervals covered by any event."""
    merged: List[List[float]] = []
    for _, start, end in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some event runs."""
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``: covered by no event."""
    out, cursor = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def base_name(name: str) -> str:
    """``jit_decode(123)`` -> ``jit_decode``; an op's HLO text
    ``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return re.sub(r"\(\d+\)$", "", name.split(" = ", 1)[0])


def totals(events: Iterable[Event]) -> Dict[str, Tuple[float, int]]:
    """Seconds and count per event ``base_name``."""
    out: Dict[str, List[float]] = {}
    for name, start, end in events:
        acc = out.setdefault(base_name(name), [0.0, 0])
        acc[0] += (end - start) * 1e-9
        acc[1] += 1
    return {k: (v[0], int(v[1])) for k, v in out.items()}


def timeline(spans: Sequence[Event]) -> List[Event]:
    """The innermost host span at each instant, as disjoint segments
    ``(label, start, end)``: a stack sweep over spans that nest, as the
    annotations of one thread do. Time outside every span is
    ``UNLABELLED``."""
    segments: List[Event] = []
    stack: List[Event] = []
    cursor = None

    def emit(upto: float) -> None:
        nonlocal cursor
        if cursor is not None and upto > cursor:
            segments.append((stack[-1][0] if stack else UNLABELLED, cursor, upto))
        cursor = upto if cursor is None else max(cursor, upto)

    for span in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= span[1]:
            emit(stack[-1][2])
            stack.pop()
        emit(span[1])
        stack.append(span)
    while stack:
        emit(stack[-1][2])
        stack.pop()
    return segments


def attribute(idle: Sequence[Tuple[float, float]], spans: Sequence[Event]
              ) -> Dict[str, float]:
    """Seconds of ``idle`` time by the innermost host span covering it."""
    out: Dict[str, float] = {}
    segments = timeline(spans)
    j = 0
    for lo, hi in sorted(idle):
        covered = lo
        while j < len(segments) and segments[j][2] <= lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][1] < hi:
            label, a, b = segments[k]
            a, b = max(a, lo), min(b, hi)
            if b > a:
                if a > covered:
                    out[UNLABELLED] = out.get(UNLABELLED, 0.0) + (a - covered) * 1e-9
                out[label] = out.get(label, 0.0) + (b - a) * 1e-9
                covered = b
            k += 1
        if hi > covered:
            out[UNLABELLED] = out.get(UNLABELLED, 0.0) + (hi - covered) * 1e-9
    return out


@dataclasses.dataclass
class Summary:
    """What one traced window shows."""

    window_s: float                               # length of the window
    busy_s: Dict[int, float]                      # device id -> busy seconds
    modules: Dict[str, List[float]]               # program -> seconds of each run
    ops: Dict[str, Tuple[float, int]]             # op name -> device s, runs
    idle_by_host: Dict[str, float]                # host span -> idle s / device
    #: name stack -> device s, summed over devices like ``ops``
    scopes: Dict[str, float] = dataclasses.field(default_factory=dict)

    def idle_share(self) -> Optional[float]:
        if not self.busy_s or self.window_s <= 0:
            return None
        shares = [1.0 - b / self.window_s for b in self.busy_s.values()]
        return sum(shares) / len(shares)


def summarize_events(
    devices: Dict[int, Dict[str, List[Event]]],
    host: List[Event],
    window: Tuple[float, float],
    *,
    host_prefix: str = "bench.",
) -> Summary:
    """Reduce per-device lines and host spans to a ``Summary`` over
    ``window`` (ns on the trace's clock)."""
    lo, hi = window
    spans = [e for e in host if e[0].startswith(host_prefix)]
    busy, modules, ops, idle, scopes = {}, {}, {}, {}, {}
    for dev, lines in sorted(devices.items()):
        source = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        op_events = clip(source, lo, hi)
        named = lines.get(SCOPES_LINE) or [("", start, end) for _, start, end in source]
        for stack, start, end in clip(named, lo, hi):
            scopes[stack] = scopes.get(stack, 0.0) + (end - start) * 1e-9
        module_events = [e for e in lines.get(MODULES_LINE, []) if lo <= e[1] < hi]
        busy[dev] = busy_ns(op_events, lo, hi) * 1e-9
        for name, start, end in module_events:
            modules.setdefault(base_name(name), []).append((start, (end - start) * 1e-9))
        for name, (secs, count) in totals(op_events).items():
            prev = ops.get(name, (0.0, 0))
            ops[name] = (prev[0] + secs, prev[1] + count)
        for name, secs in attribute(gaps(op_events, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + secs / len(devices)
    runs = {name: [secs for _, secs in sorted(starts)] for name, starts in modules.items()}
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy, modules=runs,
                   ops=ops, idle_by_host=idle, scopes=scopes)


def latest_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, device_ids: Sequence[int]
                ) -> Tuple[Dict[int, Dict[str, List[Event]]], List[Event]]:
    """Device lines of ``device_ids`` and host span events of one trace; the
    op events also as ``SCOPES_LINE``, each named by its name stack (``""``
    where it has none; left out where the stacks do not pair with the
    events one to one, and ``summarize_events`` then puts all under
    ``""``)."""
    from jax.profiler import ProfileData

    def wanted(name: str) -> bool:
        match = DEVICE_PLANE.match(name)
        return bool(match) and int(match.group(1)) in device_ids

    data = ProfileData.from_file(path)
    stacks = xplane.op_stacks(path, OPS_LINE, NAME_STACK_STAT, wanted)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if wanted(plane.name):
            lines = devices.setdefault(int(DEVICE_PLANE.match(plane.name).group(1)), {})
            names = stacks.get(plane.name, [])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = list(line.events)
                lines[line.name] = [(e.name, e.start_ns, e.end_ns) for e in events]
                if line.name == OPS_LINE and len(names) == len(events):
                    lines[SCOPES_LINE] = [(n, e.start_ns, e.end_ns)
                                          for n, e in zip(names, events)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.end_ns) for e in line.events
                         if e.name.startswith("bench.")]
    return devices, host


def window_of(host: List[Event], name: str) -> Optional[Tuple[float, float]]:
    marks = [e for e in host if e[0] == name]
    if not marks:
        return None
    return marks[0][1], marks[0][2]
