"""Replica decode: the share of the traced window in which a device runs no
operation while a replica on that device is inside a decode step
(``serve.step``: building the step's inputs, dispatching ``decode``, the
read-back of the next tokens, appending them), mean over the cell's
devices."""
from bench import program_spans


def of(spans):
    share = spans.idle_inside("serve.step")
    return None if share is None else 100.0 * share


def read(d):
    spans = program_spans.load(d)
    return None if spans is None else of(spans)
