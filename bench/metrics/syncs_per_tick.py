"""Host syncs: device-to-host reads (the program's ``serve.sync`` spans:
one per admission, one per decode step) per engine tick (``serve.tick``)
in the traced window."""
from bench import program_spans


def of(spans):
    ticks = len(spans.named("serve.tick"))
    return len(spans.named("serve.sync")) / ticks if ticks else None


def read(d):
    spans = program_spans.load(d)
    return None if spans is None else of(spans)
