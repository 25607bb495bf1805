"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle time over the traced window, the device time
of one jitted program, the device operations that took most time, and the
longest idle gaps named by what the host was doing in them.

The window is the host span named ``WINDOW`` (a
``jax.profiler.TraceAnnotation`` the harness puts around its measured
window). Host spans named in ``HOST_LABELS`` name the gaps: the innermost
one that covers a gap's midpoint gives its label.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"
HOST_LABELS = ("bench.evaluator", "bench.search")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _merge(intervals: Sequence[Tuple[float, float]]):
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def load(path: str) -> Dict:
    """{"host": [(name, start_ns, end_ns)], "devices": {plane: {line:
    [(name, start_ns, end_ns)]}}} of the trace's host spans and device
    events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW or e.name in HOST_LABELS:
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return {"host": host, "devices": devices}


def reduce(events: Dict, program: str, top: int = 10) -> Dict:
    """Busy/idle over the window, averaged over the device planes; device
    time of the modules whose name contains ``program``; the ``top`` device
    ops by summed time (a loop's time includes its body's ops, which are
    listed too) and the ``top`` longest idle gaps (of the first device)
    with their host labels. Times in seconds."""
    wins = [(s, e) for n, s, e in events["host"] if n == WINDOW]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(wins)}")
    lo, hi = wins[0]
    if not events["devices"]:
        raise ValueError("the trace holds no device plane")
    busy, prog, ops = [], [], {}
    gaps_of_first = None
    for plane in sorted(events["devices"]):
        lines = events["devices"][plane]
        evs = lines.get(OPS_LINE, []) + lines.get(MODULES_LINE, [])
        merged = _merge(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        prog.append(sum(e - s for s, e in _clip(
            [(s, e) for n, s, e in lines.get(MODULES_LINE, [])
             if program in n], lo, hi)))
        for n, s, e in lines.get(OPS_LINE, []):
            if e > lo and s < hi:
                n = n.split(" = ", 1)[0]              # the HLO op's name
                ops[n] = ops.get(n, 0.0) + (min(e, hi) - max(s, lo))
        if gaps_of_first is None:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps_of_first = [(edges[i], edges[i + 1])
                             for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]
    labels = [(n, s, e) for n, s, e in events["host"] if n in HOST_LABELS]

    def label(s, e):
        mid = 0.5 * (s + e)
        covering = [(ee - ss, n) for n, ss, ee in labels if ss <= mid <= ee]
        return min(covering)[1] if covering else "bench.between_searches"

    gaps = sorted(((label(s, e), (e - s) * 1e-9) for s, e in gaps_of_first),
                  key=lambda g: -g[1])[:top]
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    n = len(busy)
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / n * 1e-9,
            "program_s": sum(prog) / n * 1e-9,
            "device_ops": [[k, v * 1e-9] for k, v in top_ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
