"""One run of one benchmark cell: build the served deployment from the
cell's configuration, warm up every shape its traffic uses, drive the
traffic for ``--seconds`` from the client's side, and check what came out.

The system under test is the tAPP-served path of the program:
``build_engine`` (entry) -> ``ServingEngine.step_once`` (engine loop) ->
``invoke_batch`` (platform and scheduler) -> ``Replica.admit`` /
``Replica.step`` (replica) -> the jitted ``prefill_b1`` and ``decode``
programs (model) -> the device. The harness takes from it only the engine,
the replicas' outputs, and, with ``--trace 1``, spans it wraps around those
calls from its own code and the profiler's trace.

Every timestamp is taken by this loop: after each ``step_once`` it looks at
how far each request's ``output`` has grown.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench import counts, traffic
from bench import trace as tr
from bench.families import load_family
from bench.reference import load_reference

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = BENCH / ".cache" / "jax"
TRACE_DIR = BENCH / ".cache" / "trace"
#: Seconds at the end of the window that a ``--trace 1`` run reads from the
#: profiler's trace. The profiler starts ``TRACE_LEAD_S`` earlier, since
#: the device's first events after ``start_trace`` can be missing, and
#: stops after the window closes, since writing the trace stalls the host
#: for seconds.
TRACE_SECONDS = 4.0
TRACE_LEAD_S = 0.5
#: How long past the window's close the harness waits for due answers.
DRAIN_LIMIT_S = 60.0
CONFIG_DIR = BENCH / "configs"
METRIC_DIR = BENCH / "metrics"


# ---------------------------------------------------------------------------
# Files
# ---------------------------------------------------------------------------


def load_benchmark() -> Dict:
    with open(ROOT / "BENCHMARK.json") as fh:
        return json.load(fh)


def load_config(name: str) -> Dict:
    with open(CONFIG_DIR / f"{name}.json") as fh:
        c = json.load(fh)
    if c["name"] != name:
        raise ValueError(f"config file {name}.json names itself {c['name']!r}")
    return c


def base_name(name: str) -> str:
    """The quantity a metric entry reports: ``ttft_p95_ms.code`` is the
    harness's ``ttft_p95_ms`` in the cells that entry lists, under a bound
    of its own."""
    return name.split(".", 1)[0]


def load_reader(name: str) -> Callable:
    """The reader ``bench/metrics/<name>.py``, or that of its base name."""
    path = METRIC_DIR / f"{name}.py"
    if not path.exists():
        path = METRIC_DIR / f"{base_name(name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def configure_jax_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    holding every program, however quick to compile."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# ---------------------------------------------------------------------------
# Compiles
# ---------------------------------------------------------------------------


class CompileLog:
    """Backend compiles, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.entries: List[Tuple[float, str, float]] = []

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.entries.append((time.perf_counter(), kwargs.get("fun_name", "?"),
                                 duration))

    def between(self, lo: float, hi: float) -> List[Tuple[str, float]]:
        return [(name, secs) for t, name, secs in self.entries if lo <= t < hi]


# ---------------------------------------------------------------------------
# The deployment
# ---------------------------------------------------------------------------


def family(c: Dict):
    """The module of the configuration's architecture family
    (``bench/families/<reference>.py``): its weights, the program's layout
    and config, and its work counts."""
    return load_family(c["reference"])


def replicas_per_zone(chips: int) -> int:
    """One chip holds ``edge-0`` and ``cloud-0``; four chips hold two
    replicas per zone, one per device."""
    return max(1, chips // 2)


def build(c: Dict, seed: int, devices: Sequence):
    import jax
    from repro.launch.serve import build_engine
    from repro.models import Model

    fam = family(c)
    cfg = fam.program_config(c)
    params = fam.program_tree(fam.make_weights(c, seed))
    want = jax.tree.structure(jax.eval_shape(Model(cfg).init_params,
                                             jax.random.PRNGKey(0)))
    if jax.tree.structure(params) != want:
        raise ValueError(f"weights do not fit the program's layout: {want}")
    engine = build_engine(
        cfg, params, replicas_per_zone=replicas_per_zone(len(devices)),
        slots=c["replica"]["slots"], max_len=c["replica"]["max_len"],
        devices=list(devices), script=c["policy"],
    )
    return cfg, engine


def warm_up(engine, cfg, mix: Dict) -> None:
    """Run every program the window will: each replica's prefill at every
    length on the mix's grid with the argmax that admission takes, its
    decode step with its argmax, then a few requests through the engine."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    grid = traffic.length_grid(mix["prompt"])
    for replica in engine.replicas.values():
        for n in grid:
            tokens = rng.integers(1, cfg.vocab_size, size=n, dtype=np.int32)
            int(jnp.argmax(replica.prefill(0, tokens)))
        zeros = np.zeros((replica.slots,), np.int32)
        np.asarray(jnp.argmax(replica.decode(zeros, zeros), axis=-1))
    for tag in [None if t == "untagged" else t for t in mix["tags"]]:
        tokens = rng.integers(1, cfg.vocab_size, size=grid[0], dtype=np.int32)
        engine.submit(cfg.name, tokens, tag=tag, max_new_tokens=3)
    engine.run_until_done(max_ticks=100)


# ---------------------------------------------------------------------------
# Spans (--trace 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Call:
    """One prefill or decode the trace window holds, with its work."""

    kind: str                    # prefill | decode
    device: int
    work: Tuple[float, float]    # FLOPs, bytes


class Spans:
    """Host time of the layers, from wrappers the harness puts around the
    engine's and replicas' methods (instance attributes; the program's code
    is unchanged). Each wrapper also writes a ``TraceAnnotation``."""

    def __init__(self, c: Dict) -> None:
        self.c = c
        self.seconds: Dict[str, float] = {}
        self.calls_made: Dict[str, int] = {}
        self.decisions = 0
        self.admissions = 0
        self.recording = False
        self.traced: List[Call] = []
        self.tracing = False

    def reset(self) -> None:
        self.seconds, self.calls_made = {}, {}
        self.decisions = self.admissions = 0

    def add(self, name: str, secs: float) -> None:
        if self.recording:
            self.seconds[name] = self.seconds.get(name, 0.0) + secs
            self.calls_made[name] = self.calls_made.get(name, 0) + 1

    def timed(self, name: str, fn: Callable, after=None) -> Callable:
        from jax.profiler import TraceAnnotation

        def wrapped(*args, **kwargs):
            with TraceAnnotation(name):
                t0 = time.perf_counter()
                out = fn(*args, **kwargs)
                self.add(name, time.perf_counter() - t0)
            if after:
                after(out, *args, **kwargs)
            return out
        return wrapped

    def install(self, engine) -> None:
        engine._heartbeats = self.timed("bench.heartbeats", engine._heartbeats)
        engine._admit_queued = self.timed("bench.admit_queued", engine._admit_queued)
        invoke = engine.platform.invoke_batch

        def counted(invocations, **kwargs):
            invocations = list(invocations)
            if self.recording:
                self.decisions += len(invocations)
            return invoke(invocations, **kwargs)

        engine.platform.invoke_batch = self.timed("bench.invoke_batch", counted)
        for replica in engine.replicas.values():
            self._install_replica(replica)

    def _install_replica(self, replica) -> None:
        device = replica.device.id
        fam = family(self.c)

        def admitted(ok, request, placement):
            if ok and self.recording:
                self.admissions += 1
            if ok and self.tracing:
                work = fam.prefill_work(self.c, len(request.tokens))
                self.traced.append(Call("prefill", device, work))

        replica.admit = self.timed("bench.admit", replica.admit, after=admitted)
        step = replica.step
        timed_step = self.timed("bench.replica_step", step)

        def step_active():
            """Times the steps that decode; a replica with no active slot
            returns at once."""
            if not (replica.active and replica.alive):
                return step()
            positions = [st.position for st in replica.active.values()]
            out = timed_step()
            if self.tracing:
                work = fam.decode_work(self.c, positions)
                self.traced.append(Call("decode", device, work))
            return out

        replica.step = step_active


# ---------------------------------------------------------------------------
# The client
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class Record:
    spec: traffic.Spec
    due: float                   # perf_counter seconds
    sent: float
    request: object
    in_window: bool
    stamps: List[float] = dataclasses.field(default_factory=list)

    @property
    def finished(self) -> bool:
        return self.request.state in ("done", "failed")


@dataclasses.dataclass
class Drive:
    records: List[Record]
    window: Tuple[float, float]
    ticks: int
    longest_tick_s: float
    drained_s: float
    trace_window: Optional[Tuple[float, float]] = None


def drive(engine, model_id: str, specs: List[traffic.Spec], mix: Dict, *,
          seconds: float, clients: int, spans: Optional[Spans] = None,
          trace_from: Optional[float] = None,
          drain_limit: float = DRAIN_LIMIT_S) -> Drive:
    """Send ``specs`` as the mix's loop says, stepping the engine, until the
    window closes; then step until every request due in the window is
    answered (or ``drain_limit`` seconds pass).

    With ``spans``, the profiler's traced window runs from ``trace_from``
    seconds after the window opens to its close.
    """
    import jax.profiler

    closed = mix["loop"] == "closed"
    ramp = float(mix.get("ramp_s", 0.0))
    records: List[Record] = []
    live: List[Record] = []
    t0 = time.perf_counter()
    opens, closes = t0 + ramp, t0 + ramp + seconds
    next_spec = 0
    ticks = 0
    longest_tick = 0.0
    trace_state = {"profiling": False, "on": None, "note": None, "window": None}

    def send(spec: traffic.Spec, due: float) -> None:
        request = engine.submit(model_id, spec.tokens, tag=spec.tag,
                                max_new_tokens=spec.max_new_tokens)
        rec = Record(spec, due, time.perf_counter(), request,
                     in_window=opens <= due < closes)
        records.append(rec)
        live.append(rec)

    def take() -> traffic.Spec:
        nonlocal next_spec
        spec = specs[next_spec % len(specs)]
        next_spec += 1
        return spec

    def observe(now: float, sending: bool) -> None:
        done = []
        for rec in live:
            grown = len(rec.request.output) - len(rec.stamps)
            if grown > 0:
                rec.stamps.extend([now] * grown)
            if rec.finished:
                done.append(rec)
        for rec in done:
            live.remove(rec)
            if closed and sending:
                send(take(), now)

    def trace_step(now: float) -> None:
        if trace_from is None or spans is None:
            return
        start = opens + trace_from
        if not trace_state["profiling"] and now >= start - TRACE_LEAD_S:
            TRACE_DIR.parent.mkdir(parents=True, exist_ok=True)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
            trace_state["profiling"] = True
        if trace_state["profiling"] and trace_state["on"] is None and now >= start:
            trace_state["note"] = jax.profiler.TraceAnnotation("bench.trace_window")
            trace_state["note"].__enter__()
            trace_state["on"] = time.perf_counter()
            spans.tracing = True
        if trace_state["on"] is not None and trace_state["window"] is None and (
                now >= closes):
            spans.tracing = False
            off = time.perf_counter()
            trace_state["note"].__exit__(None, None, None)
            trace_state["window"] = (trace_state["on"], off)
            jax.profiler.stop_trace()

    if closed:
        for _ in range(clients):
            send(take(), t0)
    recording_started = False
    while True:
        now = time.perf_counter()
        if now >= closes:
            break
        if spans is not None and not recording_started and now >= opens:
            spans.reset()
            spans.recording = recording_started = True
        trace_step(now)
        if not closed:
            while next_spec < len(specs) and t0 + specs[next_spec].due <= now:
                spec = take()
                send(spec, t0 + spec.due)
        if not engine.queue and not any(r.active for r in engine.replicas.values()):
            if closed or next_spec >= len(specs):
                wake = closes
            else:
                wake = min(t0 + specs[next_spec].due, closes)
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            continue
        with jax.profiler.TraceAnnotation("bench.step_once"):
            t_tick = time.perf_counter()
            engine.step_once()
            tick = time.perf_counter() - t_tick
            if spans is not None:
                spans.add("bench.step_once", tick)
        ticks += 1
        longest_tick = max(longest_tick, tick)
        with jax.profiler.TraceAnnotation("bench.observe"):
            observe(time.perf_counter(), sending=True)
    if spans is not None:
        spans.recording = False
    trace_step(float("inf"))
    drain_start = time.perf_counter()
    waiting = [r for r in records if r.in_window and not r.finished]
    while waiting and time.perf_counter() < drain_start + drain_limit:
        engine.step_once()
        observe(time.perf_counter(), sending=False)
        waiting = [r for r in waiting if not r.finished]
    return Drive(records, (opens, closes), ticks, longest_tick,
                 time.perf_counter() - drain_start, trace_state["window"])


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


def client_metrics(run: Drive, seconds: float) -> Tuple[Dict[str, float], Dict]:
    """The client-side metrics, and what lies behind each."""
    lo, hi = run.window
    window = [r for r in run.records if r.in_window]
    inf = float("inf")
    ok = [r for r in window if r.request.state == "done" and r.stamps]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in ok] + [inf] * (len(window) - len(ok))
    latency = [(r.stamps[-1] - r.due) * 1e3 for r in ok] + [inf] * (len(window) - len(ok))
    itl = [(b - a) * 1e3 for r in window for a, b in zip(r.stamps, r.stamps[1:])]
    tokens = sum(1 for r in run.records for t in r.stamps if lo <= t < hi)
    metrics = {
        "ttft_p95_ms": traffic.percentile(ttft, 95),
        "itl_p95_ms": traffic.percentile(itl, 95),
        "latency_p95_ms": traffic.percentile(latency, 95),
        "output_tok_s": tokens / seconds,
    }
    late = [(r.sent - r.due) * 1e3 for r in window]
    detail = {
        "medians_ms": {"ttft": traffic.percentile(ttft, 50),
                       "itl": traffic.percentile(itl, 50),
                       "latency": traffic.percentile(latency, 50)},
        "samples": {"ttft": len(ttft), "itl": len(itl), "latency": len(latency)},
        "requests": {"attempted": len(window), "done": len(ok),
                     "failed": len(window) - len(ok),
                     "ramp_requests": len(run.records) - len(window)},
        "tokens_in_window": tokens,
        "generator_late_ms": {"median": traffic.percentile(late, 50),
                              "p99": traffic.percentile(late, 99),
                              "max": max(late) if late else float("nan")},
        "ticks": run.ticks,
        "longest_tick_ms": run.longest_tick_s * 1e3,
        "drain_s": run.drained_s,
    }
    return metrics, detail


@dataclasses.dataclass
class LayerData:
    """What a per-layer metric's reader may read."""

    chips: int
    peak: Dict[str, float]
    spans: Spans
    calls: List[Call]
    trace_window_s: float
    trace: Optional[tr.Summary]


def reduce_trace(engine_devices: Sequence[int]) -> Tuple[Optional[tr.Summary], str]:
    path = tr.latest_xplane(str(TRACE_DIR))
    devices, host = tr.read_xplane(path, engine_devices)
    window = tr.window_of(host, "bench.trace_window")
    if window is None or not devices:
        return None, f"trace {path}: window={window} device planes={sorted(devices)}"
    summary = tr.summarize_events(devices, host, window)
    return summary, f"trace {path}: device planes={sorted(devices)}"


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Served:
    """A finished request kept for the comparison with the reference."""

    prompt: np.ndarray
    output: List[int]


def check_placements(engine, records: List[Record]) -> int:
    """Requests placed on a replica the policy's static analyzer says their
    tag can never reach."""
    analysis = engine.platform.verify_policy()
    bad = 0
    for rec in records:
        allowed = analysis.selectable(rec.spec.tag or "default")
        if allowed is None or rec.request.replica not in allowed:
            bad += 1
    return bad


def check_tokens(records: List[Record]) -> int:
    """Requests due in the window that did not finish with all their
    tokens."""
    return sum(1 for r in records
               if r.request.state != "done"
               or len(r.request.output) != r.spec.max_new_tokens)


def sample(records: List[Record], k: int, seed: int) -> List[Served]:
    """``k`` finished requests drawn from the seed: the longest, then one
    from each replica none of those came from, then the rest at random, so
    that every replica's device is compared where ``k`` allows."""
    done = [r for r in records if r.request.state == "done" and r.request.output]
    if not done:
        return []
    lengths = [r.spec.prompt_len + len(r.request.output) for r in done]
    longest = int(np.argmax(lengths))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [int(i) for i in rng.permutation(len(done)) if i != longest]
    first, seen = [longest], {done[longest].request.replica}
    for i in rest:
        if done[i].request.replica not in seen:
            first.append(i)
            seen.add(done[i].request.replica)
    picks = (first + [i for i in rest if i not in first])[:k]
    return [Served(np.asarray(done[i].spec.tokens), list(done[i].request.output))
            for i in picks]


def logit_gaps(c: Dict, seed: int, served: List[Served], *, pad_to: int,
               max_out: int, control: bool) -> Tuple[float, Optional[float]]:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of ``served``; with
    ``control``, also the widest gap of the token the fp8 control puts
    first."""
    import jax.numpy as jnp

    fam = family(c)
    ref = load_reference(c["reference"])
    program = ref.gap_program(tuple(sorted(fam.model_keys(c).items())), control)
    w = fam.make_weights(c, seed)
    worst, worst_control = 0.0, (0.0 if control else None)
    for item in served:
        n, m = len(item.prompt), len(item.output)
        tokens = np.zeros((pad_to,), np.int32)
        tokens[:n] = item.prompt
        tokens[n:n + m] = item.output[: pad_to - n]
        positions = np.zeros((max_out,), np.int32)
        positions[:m] = np.arange(n - 1, n - 1 + m)
        ids = np.zeros((max_out,), np.int32)
        ids[:m] = item.output
        out = program(w, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(ids))
        if control:
            gaps, low = (np.asarray(x)[:m] for x in out)
            worst_control = max(worst_control, float(low.max()))
        else:
            gaps = np.asarray(out)[:m]
        worst = max(worst, float(gaps.max()))
    return worst, worst_control


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------


#: Every end-to-end metric the harness takes, with its unit.
END_TO_END = (("ttft_p95_ms", "ms"), ("itl_p95_ms", "ms"), ("latency_p95_ms", "ms"),
              ("output_tok_s", "tokens/s"), ("setup_s", "s"))


def run_cell(c: Dict, mix: Dict, *, seed: int, seconds: float, trace: bool,
             devices: Sequence, end_to_end: Sequence[Tuple[str, str]] = END_TO_END,
             per_layer: Sequence[Tuple[str, str]] = (),
             control: bool = False, started: Optional[float] = None,
             peak: Optional[Dict[str, float]] = None, log=print) -> Dict:
    """Everything of a run after the look for a chip; returns the result
    line's object (and, with ``control``, the fp8 control's checks and its
    ``correct`` under ``control``)."""
    import jax

    started = time.perf_counter() if started is None else started
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        return _run_cell(c, mix, seed=seed, seconds=seconds, trace=trace,
                         devices=devices, end_to_end=end_to_end,
                         per_layer=per_layer, control=control,
                         started=started, compiles=compiles, peak=peak, log=log)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run_cell(c, mix, *, seed, seconds, trace, devices, end_to_end, per_layer,
              control, started, compiles, peak, log) -> Dict:
    import jax

    chips = len(devices)
    cfg, engine = build(c, seed, devices)
    warm_up(engine, cfg, mix)
    slots = sum(r.slots for r in engine.replicas.values())
    specs = traffic.schedule(mix, seed, seconds, slots=slots,
                             vocab_size=cfg.vocab_size)
    clients = int(mix.get("clients_per_slot", 0) * slots)
    spans = None
    if trace:
        spans = Spans(c)
        spans.install(engine)
    setup_s = time.perf_counter() - started
    trace_len = min(TRACE_SECONDS, seconds)
    run = drive(engine, cfg.name, specs, mix, seconds=seconds, clients=clients,
                spans=spans, trace_from=seconds - trace_len)
    in_window = compiles.between(run.window[0], time.perf_counter())
    metrics, detail = client_metrics(run, seconds)
    window = [r for r in run.records if r.in_window]

    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": int(peak_bytes)}
    stats = engine.platform.stats()
    log(f"[{c['name']} x {mix['name']}] seed={seed} seconds={seconds} "
        f"setup_s={setup_s:.6g} requests={json.dumps(detail['requests'])}")
    log(f"[end-to-end] p95 {json.dumps(metrics)}")
    log(f"[end-to-end] medians_ms {json.dumps(detail['medians_ms'])} "
        f"samples {json.dumps(detail['samples'])} "
        f"tokens_in_window={detail['tokens_in_window']} ticks={detail['ticks']} "
        f"longest_tick_ms={detail['longest_tick_ms']:.6g} drain_s={detail['drain_s']:.6g}")
    log(f"[generator] late_ms {json.dumps(detail['generator_late_ms'])}")
    log(f"[compiles] in window: {len(in_window)} {in_window[:5]}")
    log(f"[platform] {stats}")

    result_metrics = {}
    breakdown = None
    if trace:
        summary, where = reduce_trace([d.id for d in devices])
        log(f"[trace] {where}")
        data = LayerData(
            chips=chips, peak=peak or counts.peaks(devices[0].device_kind),
            spans=spans, calls=spans.traced,
            trace_window_s=(run.trace_window[1] - run.trace_window[0]
                            if run.trace_window else 0.0),
            trace=summary)
        if summary is not None:
            device["busy_s"] = sum(summary.busy_s.values()) / len(summary.busy_s)
            device["window_s"] = summary.window_s
            log(f"[trace] busy_s per device {json.dumps(summary.busy_s)} "
                f"window_s={summary.window_s} modules "
                f"{json.dumps({k: [sum(v), len(v)] for k, v in summary.modules.items()})}")
            log(f"[trace] calls recorded: prefill="
                f"{sum(1 for x in spans.traced if x.kind == 'prefill')} decode="
                f"{sum(1 for x in spans.traced if x.kind == 'decode')}")
            leaves = sorted(((k, v) for k, v in summary.ops.items()
                             if not k.startswith("%while")), key=lambda kv: -kv[1][0])
            log(f"[trace] top ops (s, runs) {json.dumps(leaves[:15])}")
            stacks = sorted(summary.scopes.items(), key=lambda kv: -kv[1])
            log(f"[trace] name stacks: {len(stacks)}, device s {sum(summary.scopes.values())} "
                f"(ops {sum(v[0] for v in summary.ops.values())}); top "
                f"{json.dumps(stacks[:15])}")
            programs = sorted(((k, sum(v)) for k, v in summary.modules.items()),
                              key=lambda kv: -kv[1])[:10]
            gaps = sorted(summary.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
            breakdown = {"device_ops": [[k, v] for k, v in programs],
                         "idle_gaps": [[k, v] for k, v in gaps]}
        log(f"[spans] seconds {json.dumps(spans.seconds)} calls "
            f"{json.dumps(spans.calls_made)} decisions={spans.decisions} "
            f"admissions={spans.admissions}")
        for name, unit in per_layer:
            value = load_reader(name)(data)
            if value is not None:
                result_metrics[name] = {"value": float(value), "unit": unit}
    else:
        values = dict(metrics, setup_s=setup_s)
        for name, unit in end_to_end:
            result_metrics[name] = {"value": values[base_name(name)], "unit": unit}

    # Checks: what the timed path produced, after the window.
    placements = check_placements(engine, window)
    short = check_tokens(window)
    chosen = sample(window, c["check"]["sample_requests"], seed)
    served_tokens = sum(len(s.output) for s in chosen)
    del engine, spans
    gc.collect()
    pad_to = c["replica"]["max_len"]
    gap, control_gap = logit_gaps(c, seed, chosen, pad_to=pad_to,
                                  max_out=mix["output"]["max"], control=control)
    limit = c["check"]["logit_gap_limit"]
    if not chosen:
        gap = float("inf")
    log(f"[check] compared {served_tokens} served tokens of {len(chosen)} requests "
        f"against the reference")
    checks = {
        "misplaced": {"value": placements, "limit": 0},
        "unfinished": {"value": short, "limit": 0},
        "logit_gap": {"value": gap, "limit": limit},
    }
    result = {
        "correct": judge(checks),
        "attempted": detail["requests"]["attempted"],
        "failed": detail["requests"]["failed"],
        "metrics": result_metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if control:
        # The control in the program's place, judged by the same checks.
        control_checks = dict(checks, logit_gap={"value": control_gap, "limit": limit})
        result["control"] = {"correct": judge(control_checks), "checks": control_checks}
    result["checks"] = checks
    return result


def judge(checks: Dict) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(v["value"] <= v["limit"] for v in checks.values())


def finite(obj):
    """``obj`` with every non-finite float (a tail over failed requests)
    as ``None``, so that the line is strict JSON."""
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    if isinstance(obj, float) and not np.isfinite(obj):
        return None
    return obj


def format_checks(checks: Dict) -> List[str]:
    return [f"check {name}: {v['value']} limit {v['limit']}"
            for name, v in checks.items()]


def main(argv: Optional[Sequence[str]] = None, started: Optional[float] = None) -> int:
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU, found {devices[0].platform!r}", file=sys.stderr)
        return 3
    if len(devices) < cell["chips"]:
        print(f"needs {cell['chips']} chips, found {len(devices)}", file=sys.stderr)
        return 3
    configure_jax_cache()
    def of_cell(metrics):
        return [(m["name"], m["unit"]) for m in metrics
                if args.workload in m.get("workloads", [args.workload])]

    result = run_cell(
        load_config(cell["config"]), traffic.load_mix(cell["traffic"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices[: cell["chips"]], end_to_end=of_cell(bench["end_to_end"]),
        per_layer=of_cell(bench["per_layer"]), started=started,
        log=lambda line: print(line, flush=True))
    for line in format_checks(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(finite(result), allow_nan=False), flush=True)
    return 0
