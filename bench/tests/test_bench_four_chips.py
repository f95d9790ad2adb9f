"""CPU rehearsal of the four-chip cell on four virtual devices: two replicas
a zone, one per device, stepped by one host loop, the closed loop keeping
every slot busy. The device count is fixed when a process starts, so the
run is a child process of its own."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "smollm-4chip-closed"

SCRIPT = """
import json, sys
sys.path[:0] = ["bench/tests", "src", "."]
import jax
import _bench_tiny as tiny
from bench import harness

cell = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[sys.argv[1]]
engines = []
build = harness.build

def kept(*args):
    cfg, engine = build(*args)
    engines.append(engine)
    return cfg, engine

harness.build = kept
devices = jax.devices()[: cell["chips"]]
result = tiny.run(c=tiny.tiny_config(cell["config"]), mix=tiny.tiny_mix(cell["traffic"]),
                  devices=devices)
engine = engines[0]
result["replicas"] = {name: r.device.id for name, r in engine.replicas.items()}
result["served_by"] = sorted({r.replica for r in engine.done})
print(json.dumps(result))
"""


def test_four_chip_cell_runs_correct_on_four_devices():
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count=4".strip())
    proc = subprocess.run([sys.executable, "-c", SCRIPT, CELL], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True, result["checks"]
    assert result["device"]["count"] == 4
    checks = result["checks"]
    assert checks["misplaced"]["value"] == 0 and checks["unfinished"]["value"] == 0
    assert result["replicas"] == {"edge-0": 0, "edge-1": 1, "cloud-0": 2, "cloud-1": 3}
    assert result["served_by"] == ["cloud-0", "cloud-1", "edge-0", "edge-1"]
    assert result["attempted"] > 0 and result["failed"] == 0
