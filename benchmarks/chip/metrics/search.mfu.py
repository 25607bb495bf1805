"""Useful model FLOPs per second over the chips' bf16 peak: the FLOPs of
every allocation scored in the window (padding lanes do not count) over
the window, divided by device count x peak."""
from peaks import peaks_for


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["evals"]:
        return None
    pk = peaks_for(ctx["device_kind"])
    rate = ctx["evals"] * ctx["work"]["flops_per_lane"] / ctx["window_s"]
    return 100.0 * rate / (ctx["device_count"] * pk["bf16_flops"])
