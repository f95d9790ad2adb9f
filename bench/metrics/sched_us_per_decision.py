"""Scheduler and platform: host self time of ``invoke_batch`` (the tAPP
decision and the admission ledger), without the ``Replica.admit`` calls its
callback makes, per invocation evaluated, queued re-invocations included."""


def read(d):
    s = d.spans.seconds
    if not d.spans.decisions or "bench.invoke_batch" not in s:
        return None
    own = s["bench.invoke_batch"] - s.get("bench.admit", 0.0)
    return 1e6 * own / d.spans.decisions
