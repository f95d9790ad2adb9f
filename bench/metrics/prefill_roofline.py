"""Model programs: the least time the chip needs for the traced prefills'
work (``bench/counts.py``), over the device time of the ``prefill_b1``
program's runs in the trace. Silent when the program is not found."""
from bench.counts import least_seconds

MODULE = "jit_prefill_b1"


def read(d):
    if d.trace is None:
        return None
    runs = d.trace.modules.get(MODULE, [])
    calls = [c for c in d.calls if c.kind == "prefill"]
    paired = min(len(runs), len(calls))
    if not paired:
        return None
    # Runs and calls pair in order; the trace can miss a run at its start.
    device_s = sum(runs[-paired:])
    return 100.0 * sum(least_seconds(c.work, d.peak) for c in calls[-paired:]) / device_s
