"""Replica admission: time in ``Replica.admit`` (prefill, the eager slot
merge and the argmax sync) per request admitted."""


def read(d):
    if not d.spans.admissions:
        return None
    return 1e3 * d.spans.seconds["bench.admit"] / d.spans.admissions
