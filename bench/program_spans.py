"""The program's own spans in a ``--trace 1`` run: the ``serve.*`` host
annotations the served path writes while the profiler runs, with their
arguments, and the device op lines beside them, clipped to the harness's
``bench.trace_window``.

``load`` reads the newest ``.xplane.pb`` under ``harness.TRACE_DIR`` once
per file, for every reader that asks. It returns ``None`` when the run was
not traced or the trace holds no ``serve.*`` span, as for a program that
writes none.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace as tr

PREFIX = "serve."


class Span(NamedTuple):
    name: str
    start: float                 # ns on the trace's clock
    end: float
    args: Dict


@dataclasses.dataclass
class ProgramSpans:
    """One traced window: its bounds, the program's spans and each device's
    op events."""

    window: Tuple[float, float]
    spans: List[Span]
    ops: Dict[int, List[tr.Event]]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def idle_inside(self, name: str) -> Optional[float]:
        """The share of the window in which a device runs no op while a span
        ``name`` of that device (its ``device`` argument) is open, mean over
        devices; ``None`` without such spans or devices. A replica's idle
        device is not counted under another device's spans, as when replicas
        on four chips step one after another."""
        lo, hi = self.window
        named = self.named(name)
        if not named or not self.ops or hi <= lo:
            return None
        shares = []
        for device, events in self.ops.items():
            inside = tr.union((s.name, s.start, s.end) for s in named
                              if s.args.get("device") == device)
            shares.append(overlap_ns(tr.gaps(events, lo, hi), inside) / (hi - lo))
        return sum(shares) / len(shares)


def overlap_ns(xs: List[Tuple[float, float]], ys: List[Tuple[float, float]]) -> float:
    """Nanoseconds covered by both of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip_spans(host: List[Span], lo: float, hi: float) -> List[Span]:
    """The parts of ``host``'s spans inside ``[lo, hi]``, arguments kept."""
    return [Span(name, start, end, args) for (name, args), start, end
            in tr.clip((((s.name, s.args), s.start, s.end) for s in host), lo, hi)]


def read_host(path: str) -> List[Span]:
    """Every ``serve.*`` span on the host plane of one trace, with its
    event stats as ``args``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: List[Span] = []
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            out += [Span(e.name, e.start_ns, e.end_ns, dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIX)]
    return out


_cache: Dict[Tuple, Optional[ProgramSpans]] = {}


def load(d) -> Optional[ProgramSpans]:
    """The program's spans of the run ``d`` describes, read once per trace
    file; ``None`` when there are none."""
    from bench import harness

    if not d.trace_window_s:
        return None
    try:
        path = tr.latest_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    devices = tuple(sorted(d.trace.busy_s)) if d.trace is not None else ()
    stat = os.stat(path)
    key = (path, stat.st_mtime_ns, stat.st_size, devices)
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(path, devices)
    return _cache[key]


def _read(path: str, devices: Tuple[int, ...]) -> Optional[ProgramSpans]:
    lines, host = tr.read_xplane(path, devices)
    window = tr.window_of(host, "bench.trace_window")
    if window is None:
        return None
    spans = clip_spans(read_host(path), *window)
    if not spans:
        return None
    ops = {dev: tr.clip(ls.get(tr.OPS_LINE) or ls.get(tr.MODULES_LINE, []), *window)
           for dev, ls in lines.items()}
    return ProgramSpans(window, spans, ops)
