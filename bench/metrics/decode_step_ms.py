"""Replica decode: time in ``Replica.step`` per call on a replica with
active slots. The step ends in a host sync on the next tokens, so it
includes the device's time."""


def read(d):
    n = d.spans.calls_made.get("bench.replica_step", 0)
    if not n:
        return None
    return 1e3 * d.spans.seconds["bench.replica_step"] / n
