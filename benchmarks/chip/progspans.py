"""The program's own host spans in a traced window, split by layer: host
time, device idle and padded lanes, on the device's clock.

The search path records ``jax.profiler.TraceAnnotation`` spans while a
profiler session is active (``search.*``, ``ga.*``, ``mohaq.*``,
``evaluator.*``; ``repro.core.api.SearchSession.run`` lists them), so a
``--trace 1`` run writes them into the same ``.xplane.pb`` as the device's
operations. This module finds that file (the newest under ``.cache/trace``)
and uses it only if its ``bench.window`` span lasts exactly the window the
harness reduced (``ctx["trace"]["window_s"]``), which makes it the same
file. A layer's time is its self time: the union of its spans minus the
union of the named child spans, clipped to the window. The device is idle
where no operation runs on it (``xplane``'s busy intervals, averaged over
the device planes as ``xplane.reduce`` averages busy time).

A program that records no such spans gives no layer, and every reader
returns None.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(HERE, ".cache", "trace")

EVALUATOR_SPAN = "bench.evaluator"      # the harness's span around the call
DISPATCH = "evaluator.dispatch"
WAIT = "evaluator.wait"
# layer -> (its spans, the child spans whose time is not its own, the named
# parts of its self time)
LAYERS = {
    "ga": (("ga.initial", "ga.generation"), ("mohaq.evaluate",),
           ("ga.rank", "ga.offspring", "ga.survive")),
    "problem": (("mohaq.evaluate",), (EVALUATOR_SPAN,),
                ("mohaq.objectives",)),
    "evaluator": (("evaluator.errors",), (WAIT,),
                  ("evaluator.stack", DISPATCH, "evaluator.readback")),
    "build": (("search.build",), (), ()),
}
SPANS = ({xplane.WINDOW, EVALUATOR_SPAN, WAIT}
         | {n for spans, kids, parts in LAYERS.values()
            for n in spans + kids + parts})

Span = Tuple[str, int, int, dict]         # name, start ns, end ns, stats
Intervals = List[List[float]]


def _minus(a: Intervals, b: Intervals) -> Intervals:
    """Merged intervals ``a`` less merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def _length(iv: Intervals) -> float:
    return sum(e - s for s, e in iv)


def _union(spans: Sequence[Span], names, lo, hi) -> Intervals:
    return xplane._merge(xplane._clip(
        [(s, e) for n, s, e, _ in spans if n in names], lo, hi))


def layers(spans: Sequence[Span], devices: Dict, window_s: float
           ) -> Optional[Dict]:
    """Shares of the window (%) from the program's spans and the device
    events (``xplane.load``'s ``devices``): for each layer of ``LAYERS``
    with spans in the window, its self time ``share``, the device's idle
    time inside it ``idle_pct``, and both under each named part and the
    ``rest``. Also the idle time inside ``evaluator.wait``, the number of
    generations begun in the window and the dispatches' lane counts. None
    unless the trace holds one ``bench.window`` that lasts ``window_s``."""
    wins = [(s, e) for n, s, e, _ in spans if n == xplane.WINDOW]
    if len(wins) != 1 or (wins[0][1] - wins[0][0]) * 1e-9 != window_s:
        return None
    lo, hi = wins[0]
    busy = []
    for plane in sorted(devices):
        lines = devices[plane]
        evs = lines.get(xplane.OPS_LINE, []) + lines.get(
            xplane.MODULES_LINE, [])
        busy.append(xplane._merge(xplane._clip(
            [(s, e) for _, s, e in evs], lo, hi)))
    if not busy:
        return None

    def pct(ns: float) -> float:
        return 100.0 * ns / (hi - lo)

    def idle(iv: Intervals) -> float:
        return pct(sum(_length(_minus(iv, b)) for b in busy) / len(busy))

    out: Dict = {"layers": {}, "generations": sum(
        1 for n, s, _, _ in spans if n == "ga.generation" and lo <= s < hi)}
    for layer, (names, kids, parts) in LAYERS.items():
        own = _union(spans, names, lo, hi)
        if not own:
            continue
        own = _minus(own, _union(spans, kids, lo, hi))
        split, rest = {}, own
        for part in parts:
            under = _union(spans, (part,), lo, hi)
            split[part] = _minus(own, _minus(own, under))   # own & under
            rest = _minus(rest, under)
        split["rest"] = rest
        out["layers"][layer] = {
            "share": pct(_length(own)), "idle_pct": idle(own),
            "spans_pct": {k: pct(_length(v)) for k, v in split.items()},
            "idle_by_span": {k: idle(v) for k, v in split.items()}}
    if WAIT in {n for n, *_ in spans}:
        out["wait_idle_pct"] = idle(_union(spans, (WAIT,), lo, hi))
    stats = [st for n, s, _, st in spans
             if n == DISPATCH and lo <= s < hi and "bucket" in st]
    if stats:
        out["dispatch"] = {"dispatches": len(stats),
                           "lanes": sum(int(st["lanes"]) for st in stats),
                           "bucket_lanes": sum(int(st["bucket"])
                                               for st in stats)}
    return out


def find_trace(root: str = TRACE_ROOT) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``root``, or None."""
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load_spans(path: str) -> List[Span]:
    """The host spans of ``SPANS`` in a trace, with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name in SPANS]
    return out


def read(ctx, root: str = TRACE_ROOT) -> Optional[Dict]:
    """``layers`` of this run's trace, or None when the run was not traced
    or the newest trace under ``root`` is not the one the harness reduced.
    Kept in ``ctx``, so the metrics of one run read the file once."""
    if "progspans" not in ctx:
        t = ctx.get("trace")
        path = find_trace(root) if t else None
        ctx["progspans"] = layers(
            load_spans(path), xplane.load(path)["devices"],
            t["window_s"]) if path else None
    return ctx["progspans"]


def layer(ctx, name: str, root: str = TRACE_ROOT) -> Optional[Dict]:
    """One layer of ``read``, or None."""
    got = read(ctx, root)
    return got["layers"].get(name) if got else None
