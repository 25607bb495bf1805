"""Share of the window in the population evaluator's host work: the
program's ``evaluator.errors`` spans less its ``evaluator.wait`` spans
(qp-stack assembly and padding, the dispatch's enqueue, the readback and
the count-to-error% math). Carries the device's idle time inside it
(``idle_pct``), the idle time while the host waits on the device
(``wait_idle_pct``), that idle time under ``evaluator.stack``,
``.dispatch``, ``.readback`` and the rest (``by_span``), and the share
under each (``spans_pct``)."""
import progspans


def read(ctx):
    got = progspans.read(ctx)
    layer = got and got["layers"].get("evaluator")
    if not layer:
        return None
    return layer["share"], {"idle_pct": layer["idle_pct"],
                            "wait_idle_pct": got.get("wait_idle_pct"),
                            "by_span": layer["idle_by_span"],
                            "spans_pct": layer["spans_pct"]}
