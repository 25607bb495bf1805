"""Share of the dispatched lanes that are padding: over the window's
``evaluator.dispatch`` spans, the padded size (stat ``bucket``) less the
real allocations (stat ``lanes``), over the padded size. The device
computes padding lanes in full. Carries the sums and the dispatch count."""
import progspans


def read(ctx):
    got = progspans.read(ctx)
    d = got and got.get("dispatch")
    if not d:
        return None
    return (100.0 * (d["bucket_lanes"] - d["lanes"]) / d["bucket_lanes"],
            dict(d))
