"""Share of the window in which the search ran on the host, outside the
evaluator's calls: NSGA-II selection and variation, decoding, the hardware
objectives. The spans around ``val_error_batch`` end once it has returned
host floats, so the device has finished inside them."""


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["spans"]:
        return None
    inside = sum(t1 - t0 for t0, t1 in ctx["spans"])
    return 100.0 * (1.0 - inside / ctx["window_s"])
