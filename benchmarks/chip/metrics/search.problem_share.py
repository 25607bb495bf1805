"""Share of the window in ``MOHAQProblem.evaluate_population`` outside the
evaluator: the program's ``mohaq.evaluate`` spans less the harness's
``bench.evaluator`` spans around ``val_error_batch`` (screening, decoding,
the error memo, and the hardware objectives under ``mohaq.objectives``).
Carries the device's idle time inside it (``idle_pct``), the share under
``mohaq.objectives`` (``spans_pct``), and beside it the share and idle time
of building each search's problem from the target (``search.build``:
``build_pct``, ``build_idle_pct``)."""
import progspans


def read(ctx):
    got = progspans.read(ctx)
    layer = got and got["layers"].get("problem")
    if not layer:
        return None
    build = got["layers"].get("build", {})
    return layer["share"], {"idle_pct": layer["idle_pct"],
                            "spans_pct": layer["spans_pct"],
                            "build_pct": build.get("share"),
                            "build_idle_pct": build.get("idle_pct")}
