"""Find the knee of an open-loop cell: the highest offered rate at which
completions keep pace and the backlog does not grow over the window.

    python3 bench/sweep.py --workload smollm-chat --seconds 15 --rates 20 30 40

One process builds the cell's deployment once, warms it up, and drives one
window per rate, in order, letting the engine empty between them. Each rate
prints one line: arrivals and completions in the window, the backlog
(requests sent and not finished) at each quarter of it, and the tails. The
knee found this way is written into the mix's file by hand, once.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402


def backlog(records, t):
    return sum(1 for r in records
               if r.due <= t and not (r.finished and r.stamps and r.stamps[-1] <= t))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    harness.configure_jax_cache()
    cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[args.workload]
    c = harness.load_config(cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    devices = jax.devices()[: cell["chips"]]
    t0 = time.perf_counter()
    cfg, engine = harness.build(c, args.seed, devices)
    harness.warm_up(engine, cfg, mix)
    print(f"set-up {time.perf_counter() - t0:.1f} s", flush=True)
    slots = sum(r.slots for r in engine.replicas.values())
    for rate in args.rates:
        m = dict(mix, rate_rps=rate)
        specs = traffic.schedule(m, args.seed, args.seconds, slots=slots,
                                 vocab_size=cfg.vocab_size)
        run = harness.drive(engine, cfg.name, specs, m, seconds=args.seconds,
                            clients=0, drain_limit=0.0)
        lo, hi = run.window
        quarters = [backlog(run.records, lo + q * (hi - lo) / 4) for q in range(5)]
        window = [r for r in run.records if r.in_window]
        finished = sum(1 for r in run.records
                       if r.finished and r.stamps and lo <= r.stamps[-1] < hi)
        metrics, detail = harness.client_metrics(run, args.seconds)
        print(json.dumps({"rate_rps": rate, "arrivals": len(window),
                          "completions": finished, "backlog_quarters": quarters,
                          "metrics": metrics, "late_ms": detail["generator_late_ms"]}),
              flush=True)
        deadline = time.perf_counter() + 120
        while (engine.queue or any(r.active for r in engine.replicas.values())) \
                and time.perf_counter() < deadline:
            engine.step_once()
        if engine.queue or any(r.active for r in engine.replicas.values()):
            print("engine did not empty; stopping the sweep", flush=True)
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
