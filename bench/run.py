"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and per-layer metrics are files under ``bench/``
found by name. The last line of standard output is one JSON object; the
numbers compared for ``correct``, each beside its limit, are the last lines
of standard error. Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import pathlib
import sys
import time

STARTED = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(started=STARTED))
