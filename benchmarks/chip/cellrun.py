"""One run of one benchmark cell: set-up, a measured window of whole MOHAQ
searches, the check of what the window produced against the plain
reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own, found by name:

  BENCHMARK.json            cells: workload name -> config + traffic
  configs/<config>.json     sizes, source, precision, family, limits
  families/<family>.py      weights, fold, plain reference, work counts
  traffic/<mix>.json        GA settings, fold sizes, warmed buckets
  metrics/<metric>.py       ``read(ctx)`` -> a per-layer number or None

Set-up (``setup_s``, from process start): weights and fold from the seed
on the device, the reference's unquantized pass (teacher labels and
activation ranges), MMSE weight clips on the device, the program's target,
and one dispatch at every compile bucket the mix can reach. The window
then runs whole searches (``SearchSession.run``) back to back, each seeded
from ``--seed`` and its index, until ``--seconds`` have passed; the last
search always finishes, and ``search_evals_per_s`` is every allocation the
searches scored over the whole window.

Correctness: once the window has closed, the peak memory read and the
program's state freed, a sample of the window's allocations drawn from the
seed is scored again by the family's plain reference at the precision the
config states. Both what the timed dispatch returned (each lane's wrong
count per validation subset) and the answer the search received (the max
over subsets, as an error %) are compared with the reference's; every
answer must have a number and each gap must be within the config's limit.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE = os.path.join(HERE, ".cache")


def span(name: str, on: bool):
    """A host span in the profiler's trace, or nothing when not tracing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str):
    """(benchmark, cell, config, mix) for a workload name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, cfg, mix


def load_family(name: str):
    return importlib.import_module(f"families.{name}").FAMILY


def load_metric(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_info(chips: int, require_tpu: bool = True):
    """The devices this cell may use; exits without a result when JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        sys.exit(f"chip benchmark: needs a TPU, but JAX found platform "
                 f"{devs[0].platform!r} ({devs[0].device_kind}, "
                 f"{len(devs)} device(s)); there is no fallback")
    if len(devs) < chips:
        sys.exit(f"chip benchmark: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    return devs[:chips]


class Recorder:
    """The program's target, with every call of the evaluator timed on the
    host clock (each returns host floats, so the device has finished) and
    what it produced kept for the check: each allocation's answer (its
    max-over-subsets error %) and the per-subset wrong counts that the
    timed dispatch returned for its lane."""

    def __init__(self, target, evaluator, annotate: bool):
        self.target = target
        self.annotate = annotate
        self.spans: List[tuple] = []
        self.answers: Dict[tuple, tuple] = {}
        self.dispatches: List[int] = []
        self._outs: List = []

        def recorded(*args, **kw):       # the class's, as it stands now
            out = type(evaluator)._dispatch(evaluator, *args, **kw)
            self._outs.append(out)
            return out

        evaluator._dispatch = recorded

    def __getattr__(self, name):
        return getattr(self.target, name)

    def val_error_batch(self, allocs, *args, **kw):
        self._outs.clear()
        t0 = time.perf_counter()
        with span("bench.evaluator", self.annotate):
            out = self.target.val_error_batch(allocs, *args, **kw)
        self.spans.append((t0, time.perf_counter()))
        if len(self._outs) != 1:
            raise RuntimeError(f"expected one recorded dispatch for "
                               f"{len(allocs)} allocations, saw "
                               f"{len(self._outs)}")
        counts = np.asarray(self._outs.pop()).astype(np.int64)
        self.dispatches.append(len(allocs))
        names = self.target.layer_names
        for i, (a, e) in enumerate(zip(allocs, out)):
            self.answers[tuple(a[n] for n in names)] = (float(e), counts[i])
        return out


def evaluator_of(target, mix):
    """The program's population evaluator that the mix's searches call."""
    fmt = mix["ga"]["bank_format"]
    return target.batched_evaluator(**({} if fmt == "f32"
                                       else {"bank_format": fmt}))


def random_allocs(names, n: int, rng) -> List[dict]:
    from families.common import MENU
    return [{nm: (int(rng.choice(MENU)), int(rng.choice(MENU)))
             for nm in names} for _ in range(n)]


def build(cfg, mix, seed: int):
    """Weights, fold, grids and the program's target of one cell."""
    from families import common as C

    fam = load_family(cfg["family"])
    params = fam.init_weights(cfg, seed)
    inputs = fam.make_inputs(cfg, mix, seed)
    labels, act_ranges = C.calibrate(fam, cfg, params, inputs)
    wclips, wranges = C.weight_grids(fam, cfg, params)
    grids = C.Grids(act_ranges, wclips, wranges)
    n_sub, rows = mix["fold"]["subsets"], mix["fold"]["rows"]
    subsets = [(inputs[s * rows:(s + 1) * rows],
                labels[s * rows:(s + 1) * rows]) for s in range(n_sub)]
    target = fam.build_target(cfg, params, subsets, grids)
    return fam, params, inputs, labels, grids, target


def sram_bytes(target) -> int:
    """The float32 model's size: no allocation is screened out for memory,
    so every candidate of every generation reaches the device."""
    return 4 * (sum(target.layer_weights.values()) + target.vector_weights)


def window(rec: Recorder, mix, seed: int, seconds: float, trace_dir=None):
    """Whole searches back to back until ``seconds`` have passed. Returns
    (window seconds, allocations scored, searches run)."""
    import jax
    from repro.core.api import SearchSession
    from families.common import seed_int

    ga = mix["ga"]
    sram = sram_bytes(rec.target)
    evals = searches = 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with span("bench.window", bool(trace_dir)):
            t0 = time.perf_counter()
            while True:
                with span("bench.search", bool(trace_dir)):
                    session = SearchSession(
                        rec, ga["platform"], tuple(ga["objectives"]),
                        sram_override=sram, share_memo=False,
                        bank_format=ga["bank_format"])
                    res = session.run(
                        generations=ga["generations"], pop=ga["pop"],
                        initial=ga["initial"],
                        seed=seed_int(seed, f"search{searches}"))
                evals += res.problem.n_error_evals
                searches += 1
                if time.perf_counter() - t0 >= seconds:
                    break
            window_s = time.perf_counter() - t0
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return window_s, evals, searches


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def sample(answers, seed: int, k: int):
    """``k`` of the window's allocations, drawn from the seed."""
    from families.common import seed_int
    keys = sorted(answers)
    rng = np.random.default_rng(seed_int(seed, "sample"))
    k = min(k, len(keys))
    return [keys[i] for i in sorted(rng.choice(len(keys), k, replace=False))]


def reference_counts(fam, cfg, mix, params, inputs, labels, grids, keys,
                     which: str = "reference") -> List:
    """Per-subset wrong counts of the plain reference for each allocation
    key (layer order), at the config's stated precision, or one step below
    it for ``which="control"``; None where a logit is not finite."""
    from families import common as C
    names = fam.layer_names(cfg)
    score = C.reference_scorer(fam, cfg, params, inputs, labels,
                               mix["fold"]["subsets"],
                               C.precision_of(cfg, which))
    return [score(C.qp_rows(dict(zip(names, key)), names, grids))
            for key in keys]


def answer_of(counts, positions: int) -> float:
    """The program's answer from per-subset counts: the max over subsets of
    the percentage of wrong positions."""
    return float(np.max(100.0 * np.asarray(counts) / positions))


def compare(answers, refs, keys, cfg, mix, n_answers: int):
    """Each number compared, with its limit, and whether all hold.
    ``answers[key] = (answer %, per-subset counts)`` as produced;
    ``refs[i]`` the reference's per-subset counts for ``keys[i]``."""
    rows, length = mix["fold"]["rows"], mix["fold"]["length"]
    positions = rows * length
    limits = cfg["limits"]
    bad = [k for k, r in zip(keys, refs) if r is None
           or not np.isfinite(answers[k][0])]
    pairs = [(answers[k], r) for k, r in zip(keys, refs)
             if k not in bad]
    sub = [100.0 * abs(int(c) - int(rc)) / positions
           for (_, cs), r in pairs for c, rc in zip(cs, r)]
    ans = [abs(a - answer_of(r, positions)) for (a, _), r in pairs]
    want = min(mix["check"]["sample"], n_answers)
    checks = {
        "answers_without_number": {"value": len(bad), "limit": 0},
        "answers_compared": {"value": len(pairs), "limit": want},
        "answer_gap_widest_pp": {
            "value": max(ans, default=float("nan")),
            "limit": limits["answer_gap_widest_pp"]},
        "subset_gap_widest_pct": {
            "value": max(sub, default=float("nan")),
            "limit": limits["subset_gap_widest_pct"]},
        "subset_gap_mean_pct": {
            "value": float(np.mean(sub)) if sub else float("nan"),
            "limit": limits["subset_gap_mean_pct"]}}
    ok = (not bad and want > 0 and len(pairs) == want
          and all(checks[k]["value"] <= checks[k]["limit"]
                  for k in ("answer_gap_widest_pp", "subset_gap_widest_pct",
                            "subset_gap_mean_pct")))
    return checks, ok


def check(fam, cfg, mix, params, inputs, labels, grids, answers, seed):
    """Score a sample of the window's allocations, drawn from the seed,
    with the plain reference; compare the program's answers and per-subset
    counts with the reference's. Returns (checks, ok)."""
    keys = sample(answers, seed, mix["check"]["sample"])
    refs = reference_counts(fam, cfg, mix, params, inputs, labels, grids,
                            keys)
    return compare(answers, refs, keys, cfg, mix, len(answers))


def per_layer(bench, cell, ctx) -> Dict[str, dict]:
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        got = load_metric(m["name"]).read(ctx)
        if got is None:
            continue
        value, extra = (got if isinstance(got, tuple) else (got, {}))
        out[m["name"]] = {"value": value, "unit": m["unit"], **extra}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, cfg=None,
        mix=None) -> dict:
    """One run; returns the result object (the caller prints it).
    ``cfg``/``mix`` replace the cell's files (tests at small sizes)."""
    bench, cell, cfg0, mix0 = load_cell(workload)
    cfg, mix = cfg or cfg0, mix or mix0
    devices = device_info(cell["chips"], require_tpu)
    import jax
    from families.common import seed_int

    fam, params, inputs, labels, grids, target = build(cfg, mix, seed)
    rec = Recorder(target, evaluator_of(target, mix), annotate=trace)
    rng = np.random.default_rng(seed_int(seed, "warm"))
    for b in mix["warm_buckets"]:
        target.val_error_batch(random_allocs(target.layer_names, b, rng),
                               bank_format=mix["ga"]["bank_format"])
    setup_s = time.perf_counter() - t_start

    trace_dir = os.path.join(CACHE, "trace", workload) if trace else None
    if trace_dir:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    window_s, evals, searches = window(rec, mix, seed, seconds, trace_dir)
    peak = memory_peak(devices)
    print(f"window: {searches} searches, {evals} allocations scored in "
          f"{window_s:.3f} s, {len(compiles)} programs compiled inside it; "
          f"set-up {setup_s:.3f} s", file=sys.stderr, flush=True)

    ctx = {"window_s": window_s, "evals": evals, "spans": rec.spans,
           "dispatches": rec.dispatches, "work": fam.work(cfg, mix),
           "device_count": len(devices), "device_kind":
           devices[0].device_kind, "trace": None}
    result_breakdown = None
    if trace_dir:
        import xplane
        red = xplane.reduce(xplane.load(xplane.find_trace(trace_dir)),
                            mix["program"])
        ctx["trace"] = red
        result_breakdown = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}

    answers = rec.answers
    del rec, target
    gc.collect()
    checks, ok = check(fam, cfg, mix, params, inputs, labels, grids,
                       answers, seed)

    if trace:
        metrics = per_layer(bench, cell, ctx)
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"},
                   "search_evals_per_s": {"value": evals / window_s,
                                          "unit": "evals/s"}}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if ctx["trace"]:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    result = {"correct": bool(ok), "attempted": evals,
              "failed": checks["answers_without_number"]["value"],
              "metrics": metrics, "device": device}
    if result_breakdown:
        result["breakdown"] = result_breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result
