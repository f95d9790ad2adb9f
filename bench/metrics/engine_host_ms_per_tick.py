"""Engine loop: time in ``step_once`` outside the replicas' decode steps and
``_admit_queued`` (heartbeats, straggler EMA, the loop itself), per tick."""


def read(d):
    s, n = d.spans.seconds, d.spans.calls_made
    if not n.get("bench.step_once"):
        return None
    own = (s["bench.step_once"] - s.get("bench.replica_step", 0.0)
           - s.get("bench.admit_queued", 0.0))
    return 1e3 * own / n["bench.step_once"]
