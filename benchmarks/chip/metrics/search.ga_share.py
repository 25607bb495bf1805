"""Share of the window in NSGA-II's own host work: the program's
``ga.initial`` and ``ga.generation`` spans less its ``mohaq.evaluate``
spans (ranking, crowding, variation, survival, the GA's memo). Carries the
device's idle time inside it (``idle_pct``), the share under each of
``ga.rank``, ``ga.offspring`` and ``ga.survive`` (``spans_pct``), and the
generations begun in the window."""
import progspans


def read(ctx):
    got = progspans.read(ctx)
    layer = got and got["layers"].get("ga")
    if not layer:
        return None
    return layer["share"], {"idle_pct": layer["idle_pct"],
                            "spans_pct": layer["spans_pct"],
                            "generations": got["generations"]}
