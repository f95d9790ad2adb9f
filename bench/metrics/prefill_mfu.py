"""Whole step, prefill part: model FLOPs of the prompts processed in the
traced window, over the window's seconds times chips times the peak. It
bounds ``prefill_roofline`` whatever program the prefill runs in."""


def read(d):
    calls = [c for c in d.calls if c.kind == "prefill"]
    if not calls or d.trace_window_s <= 0:
        return None
    flops = sum(c.work[0] for c in calls)
    return 100.0 * flops / (d.trace_window_s * d.chips * d.peak["bf16_flops_per_s"])
