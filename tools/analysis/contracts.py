"""Layer 2 of the static-analysis gate: jaxpr contract checks.

Where the AST rules read source, these checks read the *IR* the search hot
path actually compiles. Each registered ``SearchTarget`` (see
``repro.core.target_registry``) supplies a tiny-but-real harness; the
checker traces its hot dispatches with ``jax.make_jaxpr`` / ``.lower()``
and asserts structural contracts:

C1  gather-don't-requantize — the banked ``forward_population`` jaxpr
    contains ZERO weight-quantize ops. Every fake-quant lowers to a
    ``round`` primitive; harnesses use a sequence length (the marker dim,
    3) that appears in no other model dimension, so activation quants
    carry the marker in their shapes and weight requants cannot. The
    banked jaxpr must contain only marker-carrying rounds — and as a
    detector sanity check, the requantizing lane (banks=None) must contain
    at least one non-marker round, proving the discrimination works.
    Targets that expose ``make_packed_banks`` get the packed variant too:
    the packed ``forward_population`` jaxpr must (a) likewise show only
    marker-carrying rounds and (b) close over NO f32 constant at a
    bank-stack shape ``(|menu|,) + weight_shape`` — weights ship as
    int8/int16 containers + scales; f32 rows exist only as in-trace
    dequant intermediates (sanity: the f32-bank jaxpr must show such a
    constant, or the leak detector proves nothing).
C2  no f64 — no ``convert_element_type`` to float64 and no float64
    intermediate anywhere in an eval jaxpr (the parity contracts are
    f32/fixed-point; a stray promotion silently changes every error count).
C3  donation — the per-generation ``_batch_err`` dispatch donates the
    qp-stack buffer exactly when the backend supports donation (not cpu):
    the lowered HLO carries the donation annotation iff expected.
C4  one dispatch — scoring a generation issues exactly ONE jitted call
    per compile bucket (the evaluator folds the validation subsets), and
    the harness evaluator is in the folded regime at all.
C5  lane independence — the banked ``forward_population`` jaxpr (f32 and
    packed lanes) and the serving ``forward_decode`` jaxpr must be
    *lane-independent* along the population axis: a per-variable batch-
    axis taint seeded at the qp stack (and per-lane feats) must flow
    through every eqn without being contracted, permuted or mixed (the
    ``dataflow`` engine's per-primitive axis-transfer rules), and must
    reach every output. This is the machine-checked form of the serving
    tier's population-axis-as-request-axis claim. Detector liveness: a
    deliberately lane-mixing wrapper (output flipped along the population
    axis) must FAIL the proof, or the harness cannot discriminate.

Contract findings anchor to the target's forward module (``anchor_path``)
at line 1 — there is no single source line for an IR property, but C5
messages embed the failing eqn's own traceback-derived source line.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Iterator, List, Optional, Sequence

from tools.analysis.core import Finding


def _ensure_src_on_path() -> None:
    try:
        import repro  # noqa: F401
    except ImportError:
        src = os.path.join(os.getcwd(), "src")
        if os.path.isdir(src) and src not in sys.path:
            sys.path.insert(0, src)


def _iter_eqns(jaxpr) -> Iterator[Any]:
    """All equations of a (closed) jaxpr, descending into sub-jaxprs
    (scan/while/cond/pjit bodies)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def _shapes(eqn) -> List[tuple]:
    out = []
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is not None:
            out.append(tuple(shape))
    return out


def _round_eqns(jaxpr):
    return [e for e in _iter_eqns(jaxpr) if e.primitive.name == "round"]


def _has_marker(eqn, marker_dim: int) -> bool:
    return any(marker_dim in s for s in _shapes(eqn))


def _f64_violations(jaxpr) -> List[str]:
    import numpy as np
    msgs = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name == "convert_element_type" \
                and eqn.params.get("new_dtype") == np.dtype("float64"):
            msgs.append("convert_element_type to float64")
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and dt == np.dtype("float64"):
                msgs.append(f"float64 intermediate from "
                            f"`{eqn.primitive.name}`")
    return msgs


def _contract_allocs(layer_names: Sequence[str], menu: Sequence[int],
                     pop: int = 4) -> List[dict]:
    """P=4 allocations cycling the menu so every layer exercises several
    (w_bits, a_bits) rows. pop=4 deliberately != the marker dim 3."""
    pairs = [(menu[i % len(menu)], menu[(i + 1) % len(menu)])
             for i in range(len(menu))]
    return [{name: pairs[(p + i) % len(pairs)]
             for i, name in enumerate(layer_names)}
            for p in range(pop)]


def check_harness(h) -> List[Finding]:
    """Run C1-C4 against one ContractHarness; returns findings (empty =
    all contracts hold)."""
    import jax
    import numpy as np

    from repro.core import batched_eval

    findings: List[Finding] = []

    def fail(rule: str, msg: str) -> None:
        findings.append(Finding(rule, h.anchor_path, 1,
                                f"[{h.name}] {msg}"))

    allocs = _contract_allocs(h.layer_names, h.target.menu)
    qp_stack = batched_eval.stack_qps([h.target.qp_for(a) for a in allocs],
                                      list(h.layer_names))
    params = h.target.params
    banks = h.target.make_banks(params)

    # --- C1: banked forward never requantizes weights -------------------
    banked = jax.make_jaxpr(
        lambda qp: h.forward_pop(params, h.feats, qp, banks))(qp_stack)
    rounds = _round_eqns(banked)
    if not rounds:
        fail("C1", "banked forward_population jaxpr has no round ops at "
             "all — activation fake-quant disappeared from the eval path")
    for eqn in rounds:
        if not _has_marker(eqn, h.marker_dim):
            fail("C1", "banked forward_population jaxpr contains a round "
                 f"op on shapes {_shapes(eqn)} without the activation "
                 f"marker dim {h.marker_dim}: a weight is being "
                 "re-quantized instead of gathered from the banks")
    if h.supports_requant:
        requant = jax.make_jaxpr(
            lambda qp: h.forward_pop(params, h.feats, qp, None))(qp_stack)
        if not any(not _has_marker(e, h.marker_dim)
                   for e in _round_eqns(requant)):
            fail("C1", "sanity: the requantizing lane (banks=None) shows "
                 "no non-marker round ops — the weight-quantize detector "
                 "cannot discriminate on this harness")
    else:
        requant = None

    # --- C1-packed: the packed lane ships integers, not f32 stacks ------
    # Two structural properties of the packed forward_population jaxpr:
    # (a) like C1, every round op carries the activation marker (weights
    #     come from containers, never a requantize), and (b) no f32
    #     constant at a bank-stack shape (|menu|, *weight_shape) — the
    #     closed-over weights must be the int8/int16 containers + scales;
    #     the f32 rows may only exist as in-trace dequant intermediates.
    make_packed = getattr(h.target, "make_packed_banks", None)
    packed_jx = None
    if make_packed is not None:
        pbanks = make_packed(params)
        packed_jx = jax.make_jaxpr(
            lambda qp: h.forward_pop(params, h.feats, qp, pbanks))(qp_stack)
        for eqn in _round_eqns(packed_jx):
            if not _has_marker(eqn, h.marker_dim):
                fail("C1", "packed forward_population jaxpr contains a "
                     f"round op on shapes {_shapes(eqn)} without the "
                     f"activation marker dim {h.marker_dim}: a weight is "
                     "being re-quantized instead of dequantized from the "
                     "packed containers")
        menu_len = len(h.target.menu)
        w_stack_shapes = {
            (menu_len,) + tuple(leaf.shape)
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)
            if getattr(path[-1], "key", None) == "W"}

        def _f32_stack_consts(jx) -> List[tuple]:
            return [tuple(cv.aval.shape) for cv in jx.jaxpr.constvars
                    if tuple(getattr(cv.aval, "shape", ())) in w_stack_shapes
                    and cv.aval.dtype == np.dtype("float32")]

        leaked = _f32_stack_consts(packed_jx)
        if leaked:
            fail("C1", "packed forward_population jaxpr closes over f32 "
                 f"bank stacks at weight shapes {sorted(set(leaked))} — "
                 "the packed lane must ship integer containers + scales")
        if not _f32_stack_consts(banked):
            fail("C1", "sanity: the f32-bank jaxpr shows no f32 bank-stack "
                 "constant at any weight shape — the packed-lane leak "
                 "detector cannot discriminate on this harness")

    # --- C2: no f64 anywhere in the eval jaxprs -------------------------
    for label, jx in (("banked", banked), ("requant", requant),
                      ("packed", packed_jx)):
        if jx is None:
            continue
        for msg in sorted(set(_f64_violations(jx))):
            fail("C2", f"{label} forward_population jaxpr: {msg}")

    # --- C5: lane independence (jaxpr dataflow prover) ------------------
    # The banked dispatch jaxprs are already traced above; seed the taint
    # at the qp stack (the only per-lane input of forward_pop) and let the
    # dataflow engine walk every eqn. The serving decode step adds feats
    # as a second per-lane input (population axis 0 on both).
    import jax.numpy as jnp

    from tools.analysis import dataflow as df

    def c5(label: str, report: df.LaneReport) -> None:
        for v in report.violations:
            fail("C5", f"{label} jaxpr is not lane-independent: "
                 f"{v.format()}")

    c5("banked forward_population",
       df.prove_lane_independence(banked, [0]))
    if packed_jx is not None:
        c5("packed forward_population",
           df.prove_lane_independence(packed_jx, [0]))
    if h.forward_decode is not None:
        P = qp_stack.shape[0]
        feats_lane = jnp.broadcast_to(
            jnp.asarray(h.feats)[:1], (P,) + tuple(h.feats.shape[1:]))
        for label, dbanks in (("decode-step (banked)", banks),) + (
                (("decode-step (packed)", pbanks),)
                if make_packed is not None else ()):
            c5(label, df.trace_and_prove(
                lambda f, qp, b=dbanks: h.forward_decode(params, f, qp, b),
                feats_lane, qp_stack, in_axes=[0, 0]))

    # detector liveness: a wrapper that flips the population axis of every
    # output MUST fail the proof, or C5 is proving nothing on this harness
    evil = jax.make_jaxpr(lambda qp: jax.tree_util.tree_map(
        lambda t: t[::-1], h.forward_pop(params, h.feats, qp, banks)))(
            qp_stack)
    if df.prove_lane_independence(evil, [0]).ok:
        fail("C5", "sanity: a deliberately lane-mixing forward (output "
             "flipped along the population axis) passed the lane-"
             "independence proof — the detector is not live on this "
             "harness")

    # --- C3 + C4 need the real evaluator --------------------------------
    ev = h.make_evaluator()
    if not getattr(ev, "_folded", False):
        fail("C4", "harness evaluator is not in the folded regime "
             "(equal-shape validation subsets) — the one-dispatch "
             "contract cannot hold")
        return findings

    stack = ev._stack(allocs)
    ev_banks = ev._banks_for(params)

    # C3: qp-stack donation annotation present iff the backend donates
    expect_donate = jax.default_backend() != "cpu"
    text = ev._batch_err.lower(params, ev_banks, ev._feats_all,
                               ev._labels_all, stack).as_text()
    donated = ("jax.buffer_donor" in text) or ("input_output_alias" in text)
    if donated != expect_donate:
        fail("C3", f"qp-stack donation annotation "
             f"{'missing' if expect_donate else 'present'} in the lowered "
             f"_batch_err on backend `{jax.default_backend()}` "
             f"(expected donate={expect_donate})")

    # C4: one jitted dispatch per generation (per compile bucket)
    calls: List[int] = []
    real = ev._batch_err

    def counting_stub(params, banks, feats, labels, lanes):
        calls.append(1)
        return np.zeros((lanes[0].shape[0], ev._n_subsets), np.int32)

    try:
        ev._batch_err = counting_stub
        for generation in range(2):
            before = len(calls)
            ev.errors(allocs, params)
            n = len(calls) - before
            if n != 1:
                fail("C4", f"scoring one generation issued {n} jitted "
                     "dispatches (expected exactly 1: folded subsets, one "
                     "compile bucket)")
                break
    finally:
        ev._batch_err = real
    return findings


def run_contracts(targets: Optional[Sequence[str]] = None) -> List[Finding]:
    """Trace and check every registered target (or the named subset).
    Harness/trace crashes surface as C0 findings so the gate fails loudly
    instead of dying."""
    _ensure_src_on_path()
    from repro.core import target_registry

    names = list(targets) if targets else target_registry.list_contract_targets()
    findings: List[Finding] = []
    for name in names:
        try:
            h = target_registry.get_contract_harness(name)
            findings += check_harness(h)
        except Exception as e:  # noqa: BLE001 — gate must report, not crash
            findings.append(Finding(
                "C0", "src/repro/core/target_registry.py", 1,
                f"[{name}] contract harness failed: {type(e).__name__}: "
                f"{e}"))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
