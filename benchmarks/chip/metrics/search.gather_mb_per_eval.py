"""Megabytes of per-lane weight copies the bucket programs gathered, per
allocation scored: over the window's ``evaluator.dispatch`` spans, the sum
of stat ``gathered_bytes`` (what the program's bucket program copies out
of its banks, a leaf it contracts against the menu rows counting 0) over
the sum of stat ``lanes`` (the allocations each dispatch scored). None
unless the run was traced and every dispatch of the window carries the
stat, as a program that records no such stat does not. Carries the
dispatch count and the gigabytes gathered."""
import progspans
import xplane


def per_eval(spans, window_s):
    """(MB per allocation, extras) from the trace's host spans, or None."""
    wins = [(s, e) for n, s, e, _ in spans if n == xplane.WINDOW]
    if len(wins) != 1 or (wins[0][1] - wins[0][0]) * 1e-9 != window_s:
        return None
    lo, hi = wins[0]
    stats = [st for n, s, _, st in spans
             if n == progspans.DISPATCH and lo <= s < hi]
    if not stats or any("gathered_bytes" not in st for st in stats):
        return None
    gathered = sum(int(st["gathered_bytes"]) for st in stats)
    lanes = sum(int(st["lanes"]) for st in stats)
    return 1e-6 * gathered / lanes, {"dispatches": len(stats),
                                     "gathered_gb": 1e-9 * gathered}


def read(ctx, root=progspans.TRACE_ROOT):
    t = ctx.get("trace")
    path = progspans.find_trace(root) if t else None
    if not path:
        return None
    return per_eval(progspans.load_spans(path), t["window_s"])
