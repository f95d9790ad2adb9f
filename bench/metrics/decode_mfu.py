"""Whole step, decode part: model FLOPs of the output tokens decoded in the
traced window, over the window's seconds times chips times the peak. It
bounds ``decode_roofline`` whatever program the decode runs in."""


def read(d):
    calls = [c for c in d.calls if c.kind == "decode"]
    if not calls or d.trace_window_s <= 0:
        return None
    flops = sum(c.work[0] for c in calls)
    return 100.0 * flops / (d.trace_window_s * d.chips * d.peak["bf16_flops_per_s"])
