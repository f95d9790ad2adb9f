"""Whole step: model FLOPs of every prompt and output token processed in
the traced window, over the window's seconds times chips times the peak."""


def read(d):
    if not d.calls or d.trace_window_s <= 0:
        return None
    flops = sum(c.work[0] for c in d.calls)
    return 100.0 * flops / (d.trace_window_s * d.chips * d.peak["bf16_flops_per_s"])
