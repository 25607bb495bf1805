"""Chip smoke test: MOHAQ's search and serving paths at the paper's widths.

Drives the program's own entry points once, in one process, on a TPU, with
the paper's Bi-SRU (``PAPER_CFG``: input 23, hidden 550 per direction,
projection 256, 4 SRU layers, 1904 outputs). Weights are trained from a
fixed seed on the synthetic speech task; depth is not cut.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded search only

One chip, in order:
  1. train + calibrate (``train_small_sru(cfg=PAPER_CFG)``);
  2. a 2-generation ``SearchSession(trained, "bitfusion", ("error",
     "speedup"))`` at population 16, once with f32 banks and once with
     packed banks (one banked dispatch per generation), then the first
     generation's allocations scored again through the Pallas lane
     (``BatchedSRUEvaluator(use_kernel=True)``: ``bank_mxv_pop`` /
     ``bank_qmm_pop`` / ``sru_scan_pop``, compiled, never interpreted);
  3. the f32 search's front packed by ``tools/convert_checkpoint.py`` and
     served, 8 requests over the 3 SLO classes, through
     ``ContinuousBatcher`` + ``ServingEngine`` with ``use_kernel`` False and
     True;
  4. every output compared with the plain float32 reference: the scalar
     ``sru.forward(qp=)`` computed here under
     ``jax.default_matmul_precision("highest")``.

Weights are trained ``TRAIN_STEPS`` steps, the program's default, under
``jax.default_matmul_precision("highest")``. The model's validation error
at that precision must sit at least ``BASELINE_MARGIN_PP`` points under
the error of always answering the most frequent label, so the weights
under test are trained, not near chance. Its error at the program's
default precision is printed beside it: on a TPU it is far higher.

Comparisons with the reference, each with its limit:
  - per-frame argmax disagreement with the reference's logits (percent of
    frames) and the relative logit gap ||logits - reference|| /
    ||reference|| (2-norm over all logits): the four search lanes (f32
    and packed banks, XLA and Pallas) on the first generation's
    allocations over the whole validation fold, and both serving lanes on
    every served frame. The program runs at the backend's default matmul
    precision, so on the same frames the run first measures the gap
    between the scalar forward at default and at highest precision. Both
    measures obey the triangle inequality, so a lane may exceed that gap
    only by its own distance from the default-precision scalar forward,
    which may be at most ``FLIP_ALLOWANCE_PCT`` percent of frames and
    ``REL_ALLOWANCE`` (lanes sum in other orders). A lane that computes
    something else lands far above;
  - error rates (search: max over the 4 validation subsets of 384 frames;
    serving: over all served frames) within ``ERR_TOL_PP`` percentage points
    of the reference's.
Bit-equality is reported, not required.

Lines before the last report whether each bitwise contract held on the
chip and the seconds each phase took (a smoke's numbers, not a benchmark).
The last line is one JSON object naming the device. Any failed check or
phase exits non-zero. Without a TPU the script exits non-zero at once.

Four chips: the population-sharded search (``make_population_mesh`` ->
``pop_sharding.shard_population``, shard_map) against the single-device
evaluator on the same allocations; sharded error counts must equal them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
POP = 16
GENERATIONS = 2
TRAIN_STEPS = 400
ERR_TOL_PP = 3.0
BASELINE_MARGIN_PP = 3 * ERR_TOL_PP
FLIP_ALLOWANCE_PCT = 1.0
REL_ALLOWANCE = 1e-3
N_REQUESTS = 8
REQ_FRAMES = 48
CHUNK = 16
SLOS = ("premium", "standard", "economy")


def _check_device(chips: int):
    """The TPU this run needs, or exit: there is no CPU fallback."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return d


class Report:
    """Timings, bitwise contracts and hard checks of one run."""

    def __init__(self, kind: str):
        self.kind = kind
        self.failures = []

    def time(self, phase: str, first_s: float, steady_s=None,
             what: str = ""):
        steady = (f"steady {steady_s:.3f} s" if steady_s is not None
                  else "steady not measured")
        print(f"time {phase}: first {first_s:.3f} s (compile included), "
              f"{steady}{' ' + what if what else ''} [{self.kind}]",
              flush=True)

    def contract(self, name: str, held: bool, detail: str):
        print(f"contract {name}: {'held' if held else 'did not hold'} "
              f"({detail}) [{self.kind}]", flush=True)

    def check(self, name: str, ok: bool, detail: str):
        print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failures.append(name)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _train(rep: Report, cfg):
    """The weights under test, trained, calibrated and scored at full
    matmul precision; the baselines the search sees are then scored again
    at the program's own default precision."""
    import jax
    from repro.core import sru_experiment as X

    def train():
        with jax.default_matmul_precision("highest"):
            trained = X.train_small_sru(steps=TRAIN_STEPS, cfg=cfg)
        ref = trained.baseline_val_error, trained.baseline_test_error
        trained.baseline_val_error = trained.val_error()
        trained.baseline_test_error = trained.test_error()
        return trained, ref

    (trained, (err, test_err)), t = _timed(train)
    rep.time("train+calibrate", t,
             what=f"({TRAIN_STEPS} steps at highest precision, "
                  f"calibration, baseline errors)")
    # the error of always answering each subset's most frequent label
    majority = max(100.0 * (1.0 - np.max(np.bincount(np.asarray(l).ravel()))
                            / np.asarray(l).size)
                   for _, l in trained.val_subsets)
    bound = majority - BASELINE_MARGIN_PP
    rep.check("baseline trained below bound",
              bool(np.isfinite(err)) and err <= bound,
              f"at highest precision val {err:.3f}%, test {test_err:.3f}%; "
              f"majority-label {majority:.3f}%, bound {bound:.3f}%; at "
              f"default precision val {trained.baseline_val_error:.3f}%, "
              f"test {trained.baseline_test_error:.3f}%")
    return trained


def _session(trained, **kw):
    from repro.core.api import SearchSession
    # memory screening off (the bound is the f32 model size), so every
    # candidate reaches the banked dispatch; share_memo=False so each
    # search scores its own candidates
    return SearchSession(trained, "bitfusion", ("error", "speedup"),
                         sram_override=int(trained.cfg.model_bytes()),
                         share_memo=False, **kw)


def _search(trained, rep: Report, fmt: str, mesh=None):
    """One 2-generation search; returns (result, {alloc key: error}) with
    the keys in scoring order, so the first POP are generation 0's."""
    sr, t = _timed(lambda: _session(trained, bank_format=fmt,
                                    mesh=mesh).run(
        generations=GENERATIONS, pop=POP, initial=POP, seed=0))
    memo = dict(sr.problem.error_memo)
    gen0 = [dict(k) for k in list(memo)[:POP]]
    _, steady = _timed(lambda: trained.val_error_batch(
        gen0, bank_format=fmt, mesh=mesh))
    tag = fmt if mesh is None else f"{fmt}, pop mesh"
    rep.time(f"search[{tag}]", t, steady,
             f"({GENERATIONS} generations, {len(memo)} allocations scored; "
             f"steady = one dispatch of {len(gen0)} candidates)")
    return sr, memo


def _reference_errors(trained, keys):
    """Scalar float32 reference errors, matmuls at full f32 precision."""
    import jax
    with jax.default_matmul_precision("highest"):
        return {k: trained.val_error(dict(k)) for k in keys}


def _within(rep: Report, name: str, got: dict, ref: dict):
    diffs = [abs(got[k] - ref[k]) for k in got]
    finite = all(np.isfinite(v) for v in got.values())
    rep.check(name, finite and max(diffs) <= ERR_TOL_PP,
              f"{len(got)} allocations, max |error - reference| "
              f"{max(diffs):.3f} pp, tolerance {ERR_TOL_PP} pp")


def _gap(got, ref):
    """(% of frames whose argmax differs, ||got - ref|| / ||ref||) over
    paired logit arrays of shape (..., n_outputs). The norm is over all
    logits: one activation landing on the next grid step moves a few
    logits by a whole step, which a max-norm would read as a broken lane."""
    import jax.numpy as jnp
    flips = frames = 0
    num = den = 0.0
    for g, r in zip(got, ref):
        flips += int(jnp.sum(jnp.argmax(g, -1) != jnp.argmax(r, -1)))
        frames += int(np.prod(r.shape[:-1]))
        num += float(jnp.sum(jnp.square(g - r)))
        den += float(jnp.sum(jnp.square(r)))
    return 100.0 * flips / frames, float(np.sqrt(num / den))


def _measured_gap(rep: Report, what: str, plain, ref):
    """Print and return the ``_gap`` between the scalar forward at default
    precision (``plain``) and at highest precision (``ref``)."""
    flips, rel = _gap(plain, ref)
    print(f"measured precision gap: scalar forward(qp=) at default vs "
          f"highest precision, {what}: argmax differs on {flips:.3f}% of "
          f"frames, relative logit gap {rel:.3g} [{rep.kind}]", flush=True)
    return flips, rel


def _close(rep: Report, name: str, got, ref, gap):
    """Hard check of logits against the float32 reference's, on the frames
    whose default-vs-highest ``gap`` was measured (see the module
    docstring for the limits)."""
    flips, rel = _gap(got, ref)
    limits = (gap[0] + FLIP_ALLOWANCE_PCT, gap[1] + REL_ALLOWANCE)
    finite = all(bool(np.all(np.isfinite(np.asarray(g)))) for g in got)
    rep.check(name, finite and flips <= limits[0] and rel <= limits[1],
              f"argmax differs on {flips:.3f}% of frames (limit "
              f"{limits[0]:.3f}%), relative logit gap {rel:.3g} (limit "
              f"{limits[1]:.3g})")


def _search_logits(trained, gen0, rep: Report):
    """Logits of the four search lanes on ``gen0`` against the scalar
    forward at highest precision, over the whole validation fold."""
    import jax
    import jax.numpy as jnp
    from repro.core import batched_eval
    from repro.models import sru

    cfg = trained.cfg
    feats = jnp.concatenate([f for f, _ in trained.val_subsets])
    qps = [trained.qp_for(a) for a in gen0]
    stack = jnp.asarray(batched_eval.stack_qps(qps, list(cfg.layer_names())))
    scalar = jax.jit(lambda p, qp: sru.forward(p, cfg, feats, qp=qp))
    with jax.default_matmul_precision("highest"):
        refs = [scalar(trained.params, qp) for qp in qps]
    gap = _measured_gap(
        rep, f"{len(qps)} allocations x {feats.shape[0]} x {feats.shape[1]} "
             f"validation frames",
        [scalar(trained.params, qp) for qp in qps], refs)
    # the evaluator's own banks: f32 rows plus the input-layer u-bank bound
    # to this validation fold, and the packed containers
    banks = {"f32": sru.extend_banks_u0(
                 trained.make_banks(trained.params), cfg, feats,
                 trained.qp_menu_tables()[1][0]),
             "packed": trained.make_packed_banks(trained.params)}
    for fmt, use_kernel in (("f32", False), ("packed", False),
                            ("f32", True), ("packed", True)):
        fwd = jax.jit(lambda p, q, b, k=use_kernel: sru.forward_population(
            p, cfg, feats, q, banks=b, use_kernel=k))
        logits = fwd(trained.params, stack, banks[fmt])
        lane = "pallas" if use_kernel else "xla"
        _close(rep, f"search {lane}[{fmt}] logits vs float32 reference",
               list(logits), refs, gap)


def _same(a: dict, b: dict):
    """(all shared keys map to equal values, "n/m equal")."""
    common = [k for k in a if k in b]
    eq = sum(a[k] == b[k] for k in common)
    return bool(common) and eq == len(common), f"{eq}/{len(common)} equal"


def one_chip(rep: Report, cfg):
    from repro.core import batched_eval

    trained = _train(rep, cfg)

    # ---- search: f32 and packed banks, one dispatch per generation ----
    res_f32, err_f32 = _search(trained, rep, "f32")
    res_pk, err_pk = _search(trained, rep, "packed")
    keys = list(dict.fromkeys(list(err_f32) + list(err_pk)))
    ref = _reference_errors(trained, keys)
    scalar = {k: trained.val_error(dict(k)) for k in err_f32}
    _within(rep, "search[f32] errors vs float32 reference", err_f32,
            {k: ref[k] for k in err_f32})
    _within(rep, "search[packed] errors vs float32 reference", err_pk,
            {k: ref[k] for k in err_pk})
    held, detail = _same(err_f32, scalar)
    rep.contract("batched == scalar error counts", held, detail)
    held, detail = _same(err_pk, err_f32)
    held = held and res_pk.front_key() == res_f32.front_key()
    rep.contract("packed == f32", held,
                 f"{detail}; fronts {'equal' if held else 'differ'}")
    rep.check("search front non-empty", len(res_f32.rows()) > 0,
              f"{len(res_f32.rows())} Pareto points")

    # ---- the Pallas lane on the first generation's allocations (the
    # initial population comes from the seed alone: both searches share it)
    gen0_keys = list(err_f32)[:POP]
    gen0 = [dict(k) for k in gen0_keys]
    lanes_equal = []
    for fmt, xla in (("f32", err_f32), ("packed", err_pk)):
        kev = batched_eval.BatchedSRUEvaluator(
            trained.cfg, trained.val_subsets, trained.qp_for,
            use_kernel=True, make_banks=trained.make_banks,
            qp_tables=trained.qp_menu_tables(), bank_format=fmt,
            make_packed_banks=trained.make_packed_banks)
        errs, first = _timed(lambda: kev.errors(gen0, trained.params))
        _, steady = _timed(lambda: kev.errors(gen0, trained.params))
        rep.time(f"pallas lane[{fmt}]", first, steady,
                 f"(one dispatch of {len(gen0)} candidates)")
        got = dict(zip(gen0_keys, errs))
        _within(rep, f"pallas lane[{fmt}] errors vs float32 reference",
                got, {k: ref[k] for k in gen0_keys})
        lanes_equal.append(_same(got, xla))
    _search_logits(trained, gen0, rep)

    # ---- pack the front, serve it on both lanes ----
    served = _serve(trained, res_f32, rep)
    held = all(h for h, _ in lanes_equal) and served
    rep.contract("kernel lane == XLA lane", held,
                 "search f32 " + lanes_equal[0][1] + ", search packed "
                 + lanes_equal[1][1] + ", served logits "
                 + ("equal" if served else "differ"))


def _serve(trained, res, rep: Report) -> bool:
    """Pack ``res``'s front, serve N_REQUESTS on both lanes, compare.
    Returns whether the two lanes' served logits are bitwise equal."""
    import jax
    from repro import serving as S
    from repro.data import synthetic
    from repro.models import sru
    from tools import convert_checkpoint as CC

    rows = res.rows()
    allocs = [r["alloc"] for r in rows]
    objectives = [{"error": r["error"], "speedup": r["speedup"]}
                  for r in rows]
    with tempfile.TemporaryDirectory() as d:
        (_, t_pack) = _timed(lambda: CC.pack_deployment(
            trained, allocs, d, objectives=objectives))
        art = S.DeploymentArtifact.load(d)
    rep.time("pack front", t_pack, what=f"({art.n_allocs} allocations)")

    batch = synthetic.speech_batch(trained.task, N_REQUESTS, REQ_FRAMES,
                                   seed=5)
    feats = np.asarray(batch["feats"], np.float32)
    labels = np.asarray(batch["labels"])
    cfg = trained.cfg
    fwd = jax.jit(lambda p, f, qp: sru.forward(p, cfg, f, qp=qp))

    def reference(alloc_idx, i):
        """Scalar forward(qp=) logits of request i, chunk by chunk."""
        qp = trained.qp_for(art.allocs[alloc_idx])
        return np.concatenate([
            np.asarray(fwd(trained.params, feats[i, s:s + CHUNK][None],
                           qp))[0] for s in range(0, REQ_FRAMES, CHUNK)])

    outs = {}
    for use_kernel in (False, True):
        lane = "pallas" if use_kernel else "xla"
        engine = S.ServingEngine(art, use_kernel=use_kernel)

        def serve_round(base):
            bat = S.ContinuousBatcher(engine, S.Router(art),
                                      max_lanes=N_REQUESTS, chunk=CHUNK,
                                      collect=True)
            for i in range(N_REQUESTS):
                bat.submit(S.Request(rid=base + i, slo=SLOS[i % len(SLOS)],
                                     feats=feats[i]))
            log = bat.run_until_idle()
            return bat, log

        (bat, log), first = _timed(lambda: serve_round(0))
        (_, log2), steady = _timed(lambda: serve_round(N_REQUESTS))
        s = log2.summary()
        rep.time(f"serve[{lane}]", first, steady,
                 f"({N_REQUESTS} requests x {REQ_FRAMES} frames, "
                 f"{s['n_dispatches']} dispatches, steady "
                 f"{s['tokens_per_s']:.1f} frames/s)")
        outs[lane] = (bat, log)

    bat, log = outs["xla"]
    per_req = {i: log.requests[i] for i in range(N_REQUESTS)}
    done = [i for i in range(N_REQUESTS) if i in bat.results]
    rep.check("all requests served", len(done) == N_REQUESTS,
              f"{len(done)}/{N_REQUESTS} completed, classes "
              f"{sorted({per_req[i].slo for i in done})}, allocations "
              f"{sorted({per_req[i].alloc for i in done})}")
    scalar_eq = 0
    err_served = {"xla": 0, "pallas": 0}
    err_ref = 0
    with jax.default_matmul_precision("highest"):
        refs = {i: reference(per_req[i].alloc, i) for i in done}
    plain = {i: reference(per_req[i].alloc, i) for i in done}
    gap = _measured_gap(rep, f"{len(done)} requests x {REQ_FRAMES} served "
                             f"frames, chunk by chunk",
                        [plain[i] for i in done], [refs[i] for i in done])
    for i in done:
        scalar_eq += int(np.array_equal(bat.results[i], plain[i]))
        err_ref += int(np.sum(np.argmax(refs[i], -1) != labels[i]))
        for lane, (b, _) in outs.items():
            err_served[lane] += int(np.sum(np.argmax(b.results[i], -1)
                                           != labels[i]))
    n_frames = len(done) * REQ_FRAMES
    pct = lambda n: 100.0 * n / max(n_frames, 1)
    for lane, (b, _) in outs.items():
        shapes = {b.results[i].shape for i in done}
        rep.check(f"served[{lane}] one row of logits per frame",
                  shapes == {(REQ_FRAMES, cfg.n_outputs)},
                  f"shapes {sorted(shapes)}")
        _close(rep, f"served[{lane}] logits vs float32 reference",
               [b.results[i] for i in done], [refs[i] for i in done], gap)
    for lane, n in err_served.items():
        rep.check(f"served[{lane}] frame error vs float32 reference",
                  abs(pct(n) - pct(err_ref)) <= ERR_TOL_PP,
                  f"{pct(n):.3f}% vs {pct(err_ref):.3f}% over {n_frames} "
                  f"frames, tolerance {ERR_TOL_PP} pp")
    rep.contract("served == scalar per chunk", scalar_eq == len(done),
                 f"{scalar_eq}/{len(done)} requests bitwise equal to "
                 f"forward(qp=) chunk by chunk")
    return all(np.array_equal(outs["xla"][0].results[i],
                              outs["pallas"][0].results[i]) for i in done)


def four_chips(rep: Report, cfg):
    """Population-sharded search vs the single-device evaluator."""
    from repro.launch.mesh import make_population_mesh

    trained = _train(rep, cfg)
    mesh = make_population_mesh()
    res_one, err_one = _search(trained, rep, "f32")
    res_mesh, err_mesh = _search(trained, rep, "f32", mesh=mesh)
    held, detail = _same(err_mesh, err_one)
    fronts = res_mesh.front_key() == res_one.front_key()
    rep.check("sharded error counts == single-device", bool(held),
              f"{detail} over a {int(mesh.shape['pop'])}-way pop mesh")
    rep.check("sharded front == single-device front", fronts,
              f"{len(res_mesh.rows())} vs {len(res_one.rows())} points")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the population-sharded search and "
                         "its single-device comparison")
    args = ap.parse_args(argv)
    dev = _check_device(args.chips)
    # the program: src/ holds the package, tools/ the artifact packer
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.core.sru_experiment import PAPER_CFG
    from repro.launch.compile_cache import use_repo_compile_cache
    import jax

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {use_repo_compile_cache() or 'off'}", flush=True)
    rep = Report(dev.device_kind)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(rep, PAPER_CFG)
    print(f"time total: {time.perf_counter() - t0:.3f} s [{rep.kind}]")
    if rep.failures:
        print("chip_smoke: failed checks: " + "; ".join(rep.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
