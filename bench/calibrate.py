"""Readings for the limits of ``correct``: one cell's sound runs and its
control on many seeds, in one process.

    python3 bench/calibrate.py --workload smollm-chat --seconds 10 --seeds 1 2 3

Each seed is a whole run of the cell (set-up, a short window at the cell's
own load, the drain, the checks) with the control read on the same
prompts and served tokens: the reference computed with fp8 matrix products,
and the gap of the token it puts first, judged by the run's own checks
(``control_correct`` has to come out false). One JSON line per seed; the
limit is set by hand between the largest sound reading and the smallest
control reading.
"""
import argparse
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
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
    for seed in args.seeds:
        gc.collect()
        result = harness.run_cell(c, mix, seed=seed, seconds=args.seconds,
                                  trace=False, devices=devices, control=True,
                                  log=lambda line: None)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "logit_gap": result["checks"]["logit_gap"]["value"],
            "control_correct": result["control"]["correct"],
            "control_logit_gap": result["control"]["checks"]["logit_gap"]["value"],
            "misplaced": result["checks"]["misplaced"]["value"],
            "unfinished": result["checks"]["unfinished"]["value"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
