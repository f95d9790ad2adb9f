"""Model programs: the least time the chip needs for the traced decode
steps' work (``bench/counts.py``: active slots, live positions, bf16
weights read once), over the device time of the ``decode`` program's runs
in the trace. Silent when the program is not found."""
from bench.counts import least_seconds

MODULE = "jit_decode"


def read(d):
    if d.trace is None:
        return None
    runs = d.trace.modules.get(MODULE, [])
    calls = [c for c in d.calls if c.kind == "decode"]
    paired = min(len(runs), len(calls))
    if not paired:
        return None
    # Runs and calls pair in order; the trace can miss a run at its start.
    device_s = sum(runs[-paired:])
    return 100.0 * sum(least_seconds(c.work, d.peak) for c in calls[-paired:]) / device_s
