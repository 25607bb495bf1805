"""Least time over the device time of the generation program in the trace.
Least time, summed over the window's dispatches, is the larger of FLOPs
over the bf16 peak and bytes over HBM bandwidth; the algorithm's FLOPs and
bytes of each dispatched lane (padding lanes included, since the device
computes them) come from the family's work function. ``bound`` says which
of the two sets the least time of most dispatches."""
from batched import bucket_size
from peaks import peaks_for


def read(ctx):
    t = ctx["trace"]
    if not t or t["program_s"] <= 0 or not ctx["dispatches"]:
        return None
    pk, w = peaks_for(ctx["device_kind"]), ctx["work"]
    least = 0.0
    by_flops = 0
    for n in ctx["dispatches"]:
        lanes = bucket_size(n)
        tf = lanes * w["flops_per_lane"] / (ctx["device_count"]
                                            * pk["bf16_flops"])
        tb = (lanes * w["bytes_per_lane"] + w["bytes_per_dispatch"]) / (
            ctx["device_count"] * pk["hbm_bytes_per_s"])
        least += max(tf, tb)
        by_flops += tf >= tb
    bound = "flops" if 2 * by_flops >= len(ctx["dispatches"]) else "bytes"
    return 100.0 * least / t["program_s"], {"bound": bound}
