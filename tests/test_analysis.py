"""Tests for the repro-analyze static-analysis gate (tools/analysis).

Layer 1: per-rule positive + negative fixtures through ``analyze_source``
(the fixture's fake path opts it into path-scoped rules). Layer 2: the
jaxpr contract checker against the real SRU harness, plus deliberately
broken forwards each contract must reject (requantizing banked lane for
C1, lane-flipping and cross-lane-normalizing lanes for C5). Baseline:
round-trip (finding -> write baseline -> gate clean), the justification
requirement, and ``--changed-only`` stale-scoping. CLI: the ``--json``
object shape (findings/kernels/timings with ``layer`` tags) and the
``--max-seconds`` budget. The dataflow engine behind C5 has its own
suite in test_dataflow.py; the Pallas kernel verifier (K-rules) in
test_kernel_rules.py.
"""
import json
import textwrap

import pytest

from tools.analysis import baseline as bl
from tools.analysis.core import analyze_source

CORE_PATH = "src/repro/core/fixture.py"     # in scope for R1/R2
MODEL_PATH = "src/repro/models/sru.py"      # parity-frozen, in scope for R5
PLAIN_PATH = "src/repro/other/fixture.py"   # out of R1/R5 scope


def _rules(findings):
    return [f.rule for f in findings]


def _analyze(src, path=CORE_PATH):
    return analyze_source(textwrap.dedent(src), path)


# --------------------------------------------------------------- R1

def test_r1_flags_global_rng_in_core():
    out = _analyze("""
        import numpy as np
        def sample():
            return np.random.rand(3)
    """)
    assert _rules(out) == ["R1"]
    assert "np.random.rand" in out[0].message
    assert out[0].path == CORE_PATH and out[0].line == 4


def test_r1_flags_bare_stdlib_random():
    out = _analyze("""
        import random
        x = random.randint(0, 4)
    """)
    assert _rules(out) == ["R1"]


def test_r1_allows_seedsequence_idiom():
    out = _analyze("""
        import numpy as np
        ss = np.random.SeedSequence(0)
        rng = np.random.default_rng(ss)
        gen = np.random.Generator(np.random.PCG64(ss))
    """)
    assert out == []


def test_r1_out_of_scope_module_not_flagged():
    out = _analyze("""
        import numpy as np
        x = np.random.rand(3)
    """, path=PLAIN_PATH)
    assert out == []


def test_r1_searchtarget_module_in_scope_anywhere():
    out = _analyze("""
        import numpy as np
        class MambaTarget:
            supports_retrain = False
            def noise(self):
                return np.random.rand(2)
    """, path="src/repro/future/mamba_target.py")
    assert _rules(out) == ["R1"]


# --------------------------------------------------------------- R2

def test_r2_flags_deprecated_calls_by_alias_and_name():
    out = _analyze("""
        from repro.core import sru_experiment as X
        from repro.core.sru_experiment import build_problem
        p1 = X.experiment1_memory(None)
        p2 = build_problem(None, None, ())
    """, path="benchmarks/fixture.py")
    assert _rules(out) == ["R2", "R2"]
    assert "experiment1_memory" in out[0].message


def test_r2_exempts_shim_module_and_tests():
    src = """
        from repro.core import sru_experiment as X
        p = X.build_problem(None, None, ())
    """
    assert _analyze(src, path="src/repro/core/sru_experiment.py") == []
    assert _analyze(src, path="tests/test_sru_experiment.py") == []


def test_r2_ignores_unrelated_build_problem_methods():
    out = _analyze("""
        class SearchSession:
            def build_problem(self):
                return None
        s = SearchSession()
        p = s.build_problem()
    """, path="benchmarks/fixture.py")
    assert out == []


# --------------------------------------------------------------- R3

def test_r3_flags_host_effects_in_jitted_fn():
    out = _analyze("""
        import jax
        import numpy as np
        @jax.jit
        def step(x):
            print("tracing", x)
            y = np.asarray(x)
            return y.sum().item()
    """, path=PLAIN_PATH)
    assert sorted(_rules(out)) == ["R3", "R3", "R3"]
    msgs = " | ".join(f.message for f in out)
    assert "print()" in msgs and "np.asarray" in msgs and ".item()" in msgs


def test_r3_jax_debug_needs_allow_comment():
    flagged = _analyze("""
        import jax
        @jax.jit
        def step(x):
            jax.debug.print("x={}", x)
            return x
    """, path=PLAIN_PATH)
    assert _rules(flagged) == ["R3"]
    allowed = _analyze("""
        import jax
        @jax.jit
        def step(x):
            jax.debug.print("x={}", x)  # analyze: allow=R3 perf tracing
            return x
    """, path=PLAIN_PATH)
    assert allowed == []


def test_r3_ignores_host_effects_outside_jit():
    out = _analyze("""
        import numpy as np
        def host_step(x):
            print("fine here")
            return np.asarray(x)
    """, path=PLAIN_PATH)
    assert out == []


def test_r3_sees_jit_call_form_and_partial_decorator():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            print(x)
            return x
        def g(x):
            print(x)
            return x
        g = jax.jit(g)
    """, path=PLAIN_PATH)
    assert _rules(out) == ["R3", "R3"]


_SPAN_AROUND_CALL = """
    import jax
    from jax.profiler import TraceAnnotation
    @jax.jit
    def step(x):
        with jax.named_scope("step.body"):
            return x * 2
    def run(x):
        with TraceAnnotation("step", lanes=4):
            return step(x)
"""


def test_r3_allows_profiler_span_around_jitted_call():
    """The program's idiom: the host span around the call, a named scope
    inside the traced body."""
    assert _analyze(_SPAN_AROUND_CALL, path=PLAIN_PATH) == []


def test_r3_flags_profiler_span_moved_into_jitted_body():
    """The same module with the span moved inside ``step``: it would time
    the tracing and record nothing when the program runs."""
    broken = _SPAN_AROUND_CALL.replace(
        'with jax.named_scope("step.body"):',
        'with jax.profiler.TraceAnnotation("step.body"):')
    out = _analyze(broken, path=PLAIN_PATH)
    assert _rules(out) == ["R3"]
    assert "jax.profiler.TraceAnnotation" in out[0].message
    assert "`step`" in out[0].message and out[0].line == 6


def test_r3_flags_step_span_in_shard_map_body():
    out = _analyze("""
        import jax
        from jax.profiler import StepTraceAnnotation as Step
        def body(x):
            with Step("shard", step_num=0):
                return x + 1
        f = jax.shard_map(body, mesh=None, in_specs=None, out_specs=None)
        def host(x):
            with Step("host", step_num=0):
                return f(x)
    """, path=PLAIN_PATH)
    assert _rules(out) == ["R3"]
    assert "StepTraceAnnotation" in out[0].message and out[0].line == 5


# --------------------------------------------------------------- R4

def test_r4_flags_mutable_default_and_float_static():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("scale",))
        def f(x, scale=0.5, history=[]):
            return x * scale
    """, path=PLAIN_PATH)
    assert sorted(_rules(out)) == ["R4", "R4"]
    msgs = " | ".join(f.message for f in out)
    assert "float-valued static" in msgs and "mutable default" in msgs


def test_r4_flags_unknown_static_name():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("cfg",))
        def f(x, n):
            return x
    """, path=PLAIN_PATH)
    assert _rules(out) == ["R4"]
    assert "`cfg`" in out[0].message


def test_r4_clean_hashable_statics():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("n", "mode"))
        def f(x, n=4, mode="fused"):
            return x * n
    """, path=PLAIN_PATH)
    assert out == []


def test_r4_flags_float_static_via_argnums():
    """static_argnums is the positional spelling of the same contract —
    a float-defaulted static arg recompiles per value either way."""
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, scale=0.5):
            return x * scale
    """, path=PLAIN_PATH)
    assert _rules(out) == ["R4"]
    assert "float-valued static" in out[0].message
    assert "`scale`" in out[0].message


def test_r4_flags_mutable_static_via_scalar_argnums():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnums=2)
        def f(x, n, opts={}):
            return x
    """, path=PLAIN_PATH)
    msgs = " | ".join(f.message for f in out)
    assert "unhashable default for static arg `opts`" in msgs


def test_r4_flags_out_of_range_argnums():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnums=(5,))
        def f(x, n):
            return x
    """, path=PLAIN_PATH)
    assert _rules(out) == ["R4"]
    assert "out of range" in out[0].message


def test_r4_argnums_clean_and_vararg_tolerant():
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, n=4):
            return x * n
        @functools.partial(jax.jit, static_argnums=(3,))
        def g(x, *rest):
            return x
    """, path=PLAIN_PATH)
    assert out == []


# --------------------------------------------------------------- R5

def test_r5_flags_f64_in_parity_frozen_module():
    out = _analyze("""
        import jax
        import jax.numpy as jnp
        def promote(x):
            y = x.astype(jnp.float64)
            z = jnp.zeros(3, dtype="float64")
            jax.config.update("jax_enable_x64", True)
            return y + z
    """, path=MODEL_PATH)
    assert sorted(set(_rules(out))) == ["R5"]
    assert len(out) >= 3


def test_r5_allows_host_numpy_f64_and_other_modules():
    host = _analyze("""
        import numpy as np
        errs = np.zeros(4, dtype=np.float64)
    """, path="src/repro/core/batched_eval.py")
    assert host == []
    elsewhere = _analyze("""
        import jax.numpy as jnp
        y = jnp.float64(1.0)
    """, path=PLAIN_PATH)
    assert elsewhere == []


# --------------------------------------------------------------- R6

def test_r6_flags_bare_except_in_core():
    out = _analyze("""
        def load():
            try:
                return open("x").read()
            except:
                return None
    """)
    assert _rules(out) == ["R6"]
    assert "bare `except:`" in out[0].message


def test_r6_flags_blanket_swallow():
    out = _analyze("""
        def drain(items):
            for it in items:
                try:
                    it.close()
                except Exception:
                    pass
            try:
                items.flush()
            except (ValueError, BaseException):
                ...
    """)
    assert _rules(out) == ["R6", "R6"]


def test_r6_allows_named_and_handled():
    out = _analyze("""
        import warnings
        def load(path):
            try:
                return open(path).read()
            except FileNotFoundError:
                return None
            except OSError as e:
                warnings.warn(str(e))
                raise
        def retry(fn):
            try:
                return fn()
            except Exception as e:
                # a blanket catch that HANDLES (logs + re-raises) is fine
                warnings.warn(str(e))
                raise
    """)
    assert out == []


def test_r6_scope_and_pragma():
    src = """
        def f():
            try:
                return 1
            except:
                return 0
    """
    assert _analyze(src, path=PLAIN_PATH) == []          # out of scope
    assert _rules(_analyze(
        src, path="src/repro/distributed/fixture.py")) == ["R6"]
    allowed = _analyze("""
        def f():
            try:
                return 1
            except:   # analyze: allow=R6 legacy shim boundary
                return 0
    """)
    assert allowed == []


# ------------------------------------------------- pragmas and layers

def test_pragma_suppresses_multiple_rules():
    """One pragma may allowlist several rules: `allow=R4,R3 reason` (with
    or without spaces after the comma)."""
    out = _analyze("""
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("scale",))
        def f(x, scale=0.5):  # analyze: allow=R4, R3 float static test knob
            jax.debug.print("x={}", x)
            return x * scale
    """, path=PLAIN_PATH)
    # the R4 (float static, anchored to the def line) AND the R3 on the
    # directly-following jax.debug line are both suppressed by one pragma
    assert out == []


def test_pragma_unknown_rule_id_is_hard_error():
    out = _analyze("""
        import jax
        @jax.jit
        def f(x):
            jax.debug.print("x={}", x)  # analyze: allow=R3,R99 typo'd id
            return x
    """, path=PLAIN_PATH)
    # R3 (a known id) still suppresses; the unknown id is an E1 finding
    assert _rules(out) == ["E1"]
    assert "R99" in out[0].message and "known ids" in out[0].message


def test_pragma_star_cannot_hide_its_own_typo():
    out = _analyze("""
        x = 1  # analyze: allow=*,BOGUS belt and suspenders
    """, path=PLAIN_PATH)
    assert _rules(out) == ["E1"]
    assert "BOGUS" in out[0].message


def test_all_emittable_rule_ids_are_known():
    from tools.analysis.core import KNOWN_RULES
    from tools.analysis.rules import ALL_RULES
    assert {r.id for r in ALL_RULES} <= KNOWN_RULES
    assert {"C5", "K0", "K1", "K2", "K3", "K4", "E0", "E1"} <= KNOWN_RULES


def test_finding_layer_field():
    from tools.analysis.core import Finding
    assert Finding("R1", "a.py", 1, "m").layer == "ast"
    assert Finding("E1", "a.py", 1, "m").layer == "ast"
    assert Finding("C5", "a.py", 1, "m").layer == "contract"
    assert Finding("K2", "a.py", 1, "m").layer == "kernel"
    assert Finding("C5", "a.py", 1, "m").to_json()["layer"] == "contract"


# --------------------------------------------------------- baseline

def test_baseline_round_trip(tmp_path):
    findings = _analyze("""
        import numpy as np
        x = np.random.rand(3)
    """)
    assert _rules(findings) == ["R1"]
    path = tmp_path / "baseline.json"
    bl.write_baseline(str(path), findings, {})
    # fresh entries carry a TODO justification the loader must reject
    with pytest.raises(bl.BaselineError):
        data = json.loads(path.read_text())
        for e in data["findings"]:
            e["justification"] = ""
        path.write_text(json.dumps(data))
        bl.load_baseline(str(path))
    data = json.loads(path.read_text())
    for e in data["findings"]:
        e["justification"] = "legacy fixture, tracked in ISSUE 6"
    path.write_text(json.dumps(data))
    base = bl.load_baseline(str(path))
    new, grandfathered, stale = bl.apply_baseline(findings, base)
    assert new == [] and len(grandfathered) == 1 and stale == []


def test_baseline_stale_and_new(tmp_path):
    findings = _analyze("""
        import numpy as np
        x = np.random.rand(3)
    """)
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 1, "findings": [
        {"rule": "R1", "path": "src/gone.py", "line": 9,
         "justification": "was removed"}]}))
    new, grandfathered, stale = bl.apply_baseline(
        findings, bl.load_baseline(str(path)))
    assert len(new) == 1 and grandfathered == [] \
        and stale == [("R1", "src/gone.py", 9)]


def test_write_baseline_preserves_justifications_across_line_drift(tmp_path):
    f1 = _analyze("import numpy as np\nx = np.random.rand(3)\n")
    path = tmp_path / "baseline.json"
    prev = {(f1[0].rule, f1[0].path, f1[0].line): "known exception"}
    # same finding, shifted one line
    f2 = _analyze("import numpy as np\n\nx = np.random.rand(3)\n")
    bl.write_baseline(str(path), f2, prev)
    base = bl.load_baseline(str(path))
    assert list(base.values()) == ["known exception"]


# ------------------------------------------------- jaxpr contracts

@pytest.fixture(scope="module")
def sru_harness():
    from repro.core.target_registry import get_contract_harness
    return get_contract_harness("sru")


def test_contracts_pass_on_real_sru(sru_harness):
    from tools.analysis.contracts import check_harness
    assert check_harness(sru_harness) == []


def test_contracts_fail_on_requantizing_forward(sru_harness):
    """A 'banked' forward that ignores the banks and fake-quants its
    weights must trip C1 (the gather-don't-requantize contract)."""
    import dataclasses

    from repro.models import sru
    from tools.analysis.contracts import check_harness

    h = sru_harness
    cfg = h.target.cfg

    def requantizing_forward(params, feats, qp_stack, banks=None):
        return sru.forward_population(params, cfg, feats, qp_stack,
                                      banks=None)

    bad = dataclasses.replace(h, forward_pop=requantizing_forward,
                              supports_requant=False)
    findings = check_harness(bad)
    assert any(f.rule == "C1" and "re-quantized" in f.message
               for f in findings)
    assert all(f.path == h.anchor_path for f in findings)


def test_contracts_fail_on_f32_leak_in_packed_lane(sru_harness):
    """A 'packed' lane that secretly closes over the f32 bank stacks must
    trip the C1 packed-leak detector (weights have to ship as integer
    containers + scales)."""
    import dataclasses

    from repro.models import sru
    from tools.analysis.contracts import check_harness

    h = sru_harness
    cfg = h.target.cfg
    f32_banks = h.target.make_banks(h.target.params)

    def leaky_forward(params, feats, qp_stack, banks=None):
        # banked/requant lanes behave normally; the packed dict is swapped
        # for the closed-over f32 stacks — exactly the leak C1 polices
        if banks is not None and isinstance(banks["L0"]["fwd"]["W"], dict):
            banks = f32_banks
        return sru.forward_population(params, cfg, feats, qp_stack,
                                      banks=banks)

    bad = dataclasses.replace(h, forward_pop=leaky_forward)
    findings = check_harness(bad)
    assert any(f.rule == "C1" and "closes over f32 bank stacks"
               in f.message for f in findings)
    assert all(f.path == h.anchor_path for f in findings)


def test_c5_fails_on_lane_mixing_forward(sru_harness):
    """A forward that mixes population lanes — here: flipping the lane
    axis of an otherwise-correct banked forward — must trip the C5
    lane-independence prover with the exact mixing primitive named."""
    import dataclasses

    import jax

    from repro.models import sru
    from tools.analysis.contracts import check_harness

    h = sru_harness
    cfg = h.target.cfg

    def lane_flipping_forward(params, feats, qp_stack, banks=None):
        out = sru.forward_population(params, cfg, feats, qp_stack,
                                     banks=banks)
        return jax.tree_util.tree_map(lambda t: t[::-1], out)

    bad = dataclasses.replace(h, forward_pop=lane_flipping_forward,
                              forward_decode=None)
    findings = check_harness(bad)
    c5 = [f for f in findings if f.rule == "C5"]
    assert c5, [f.format() for f in findings]
    assert any("rev" in f.message and "not lane-independent" in f.message
               for f in c5)
    assert all(f.path == h.anchor_path for f in findings)


def test_c5_fails_on_cross_lane_normalization(sru_harness):
    """Subtler mixing than a flip: normalizing logits by a cross-lane
    mean. Every op is shape-preserving, so only dataflow can catch it."""
    import dataclasses

    from repro.models import sru
    from tools.analysis.contracts import check_harness

    h = sru_harness
    cfg = h.target.cfg

    def mean_mixing_forward(params, feats, qp_stack, banks=None):
        out = sru.forward_population(params, cfg, feats, qp_stack,
                                     banks=banks)
        return out - out.mean(axis=0, keepdims=True)

    bad = dataclasses.replace(h, forward_pop=mean_mixing_forward,
                              forward_decode=None)
    c5 = [f for f in check_harness(bad) if f.rule == "C5"]
    assert any("reduce" in f.message for f in c5), \
        [f.format() for f in c5]


def _lane_proof(fn, qp_stack):
    import jax

    from tools.analysis import dataflow as df
    return df.prove_lane_independence(jax.make_jaxpr(fn)(qp_stack), [0])


def test_c5_accepts_lax_map_over_population():
    """``lax.map`` over the population axis (a scan with no carry, lane-
    shared consts, the stack sliced by lane, as the granite forward runs
    its lanes) is lane-independent; its ys stack on axis 0."""
    import jax
    import jax.numpy as jnp

    w = jnp.ones((6, 5))
    live = jnp.asarray([True, False, True, True])

    def lanes(qp):
        return jax.lax.map(
            lambda lane: jax.lax.cond(lane[1], lambda r: jnp.tanh(r @ w),
                                      lambda r: jnp.zeros((3, 5)), lane[0]),
            (qp, live))

    rep = _lane_proof(lanes, jnp.ones((4, 3, 6)))
    assert rep.ok and rep.out_axes == [0], [v.format()
                                            for v in rep.violations]


def test_c5_rejects_scan_over_population_with_a_carry():
    """A scan over the population axis whose carry chains lane i into
    lane i+1 (a running sum of the lanes) mixes lanes."""
    import jax
    import jax.numpy as jnp

    def chained(qp):
        def step(c, row):
            c = c + row
            return c, c
        return jax.lax.scan(step, jnp.zeros((3, 6)), qp)[1]

    rep = _lane_proof(chained, jnp.ones((4, 3, 6)))
    assert not rep.ok
    assert any(v.primitive == "scan" and "carry" in v.reason
               for v in rep.violations)


def test_c5_rejects_lax_map_closing_over_the_qp_stack():
    """A ``lax.map`` whose body reads the whole tainted qp stack (here its
    mean over lanes) lets step i read every lane."""
    import jax
    import jax.numpy as jnp

    def closes_over(qp):
        return jax.lax.map(lambda row: row + jnp.sum(qp, axis=0) * 0.0, qp)

    rep = _lane_proof(closes_over, jnp.ones((4, 3, 6)))
    assert not rep.ok
    assert any(v.primitive == "scan" and "const" in v.reason
               for v in rep.violations)


@pytest.mark.parametrize("name", ["sru", "xlstm", "granite_hybrid"])
def test_contracts_hold_on_every_harness(name):
    """C1-C5 on each registered target's harness, after the lane-map
    refinement of the scan rule (the granite forward is a ``lax.map``)."""
    from tools.analysis.contracts import run_contracts
    assert run_contracts([name]) == []


@pytest.mark.parametrize("name", ["sru", "xlstm", "granite_hybrid"])
def test_flipped_output_fails_c5_on_every_harness(name):
    """The detector stays live on each harness: the banked forward with
    its output flipped along the population axis fails the proof."""
    import jax

    from repro.core import batched_eval
    from repro.core.target_registry import get_contract_harness
    from tools.analysis.contracts import _contract_allocs

    h = get_contract_harness(name)
    t = h.target
    qp_stack = batched_eval.stack_qps(
        [t.qp_for(a) for a in _contract_allocs(h.layer_names, t.menu)],
        list(h.layer_names))
    banks = t.make_banks(t.params)
    rep = _lane_proof(lambda qp: jax.tree_util.tree_map(
        lambda o: o[::-1], h.forward_pop(t.params, h.feats, qp, banks)),
        qp_stack)
    assert not rep.ok
    assert any(v.primitive == "rev" for v in rep.violations)


def test_contract_registry_lists_both_targets():
    from repro.core import target_registry as tr
    assert {"sru", "xlstm"} <= set(tr.list_contract_targets())
    h = tr.get_contract_harness("sru")
    assert h.marker_dim == tr.MARKER_DIM == 3
    with pytest.raises(KeyError):
        tr.get_contract_harness("nope")


def test_contract_registry_custom_target(sru_harness):
    import dataclasses

    from repro.core import target_registry as tr
    from tools.analysis.contracts import run_contracts

    custom = dataclasses.replace(sru_harness, name="custom")
    tr.register_contract_target("custom", lambda: custom)
    try:
        assert "custom" in tr.list_contract_targets()
        assert run_contracts(["custom"]) == []
    finally:
        tr._CUSTOM.pop("custom", None)


# --------------------------------------------- CLI: json / changed-only

def test_apply_baseline_restrict_paths_limits_stale():
    base = {("R1", "src/a.py", 3): "why", ("R1", "src/b.py", 7): "why"}
    new, grand, stale = bl.apply_baseline([], base,
                                          restrict_paths={"src/a.py"})
    assert new == [] and grand == []
    assert stale == [("R1", "src/a.py", 3)]     # b.py was out of scope
    _, _, stale_full = bl.apply_baseline([], base)
    assert len(stale_full) == 2


def test_cli_json_object_shape(tmp_path, capsys):
    from tools.analysis.__main__ import main
    mod = tmp_path / "fixture.py"
    mod.write_text("import numpy as np\nx = np.random.rand(3)\n")
    # out of R1 scope by path, so findings may be empty — the shape is
    # what's under test; force one finding with an unknown-pragma E1
    mod.write_text("x = 1  # analyze: allow=ZZZ nope\n")
    rc = main([str(mod), "--json", "--no-contracts", "--no-kernels",
               "--baseline", str(tmp_path / "none.json")])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert set(out) == {"findings", "kernels", "timings"}
    assert out["findings"] and out["findings"][0]["rule"] == "E1"
    assert out["findings"][0]["layer"] == "ast"
    assert "ast" in out["timings"] and "total" in out["timings"]
    assert out["kernels"] == []                  # --no-kernels


def test_cli_max_seconds_budget(tmp_path, capsys):
    from tools.analysis.__main__ import main
    mod = tmp_path / "clean.py"
    mod.write_text("x = 1\n")
    base = str(tmp_path / "none.json")
    assert main([str(mod), "--no-contracts", "--no-kernels",
                 "--baseline", base, "--max-seconds", "60"]) == 0
    assert main([str(mod), "--no-contracts", "--no-kernels",
                 "--baseline", base, "--max-seconds", "0"]) == 1
    assert "over the --max-seconds" in capsys.readouterr().err


def test_changed_only_scopes_to_git_diff(tmp_path, monkeypatch, capsys):
    """--changed-only lints only files changed vs the base ref (plus
    untracked), skips contracts/kernels, and does not report baseline
    entries outside the diff as stale."""
    import subprocess

    from tools.analysis.__main__ import main

    repo = tmp_path
    core = repo / "src" / "repro" / "core"
    core.mkdir(parents=True)
    git = ["git", "-c", "user.name=t", "-c", "user.email=t@t"]
    subprocess.run(git + ["init", "-q"], cwd=repo, check=True)
    # two committed files, both with R1 violations
    (core / "old.py").write_text("import numpy as np\na = np.random.rand(1)\n")
    (core / "hot.py").write_text("import numpy as np\nb = np.random.rand(1)\n")
    subprocess.run(git + ["add", "."], cwd=repo, check=True)
    subprocess.run(git + ["commit", "-qm", "seed"], cwd=repo, check=True)
    # only hot.py changes after the commit
    (core / "hot.py").write_text(
        "import numpy as np\nb = np.random.rand(1)\nc = np.random.rand(2)\n")
    monkeypatch.chdir(repo)
    # baseline grandfathers old.py's finding; it is outside the diff, so
    # a changed-only run must NOT call it stale
    baseline = repo / "baseline.json"
    baseline.write_text(json.dumps({"version": 1, "findings": [
        {"rule": "R1", "path": "src/repro/core/old.py", "line": 2,
         "justification": "legacy"}]}))
    rc = main(["src", "--changed-only", "--base-ref", "HEAD",
               "--baseline", str(baseline)])
    captured = capsys.readouterr()
    assert rc == 1                               # hot.py has new findings
    assert "hot.py" in captured.out and "old.py" not in captured.out
    assert "stale" not in captured.err
    # full run from the same tree DOES see old.py (and its baseline hit)
    rc_full = main(["src", "--no-contracts", "--no-kernels",
                    "--baseline", str(baseline)])
    assert rc_full == 1
    assert "old.py" in capsys.readouterr().out


# --------------------------------------------------------- repo gate

def test_repo_tree_is_clean():
    """The merged tree must lint clean (modulo the committed baseline) —
    the same invariant `python -m tools.analysis` enforces in check.sh."""
    from tools.analysis import analyze_paths, apply_baseline, load_baseline
    from tools.analysis.__main__ import DEFAULT_BASELINE
    findings = analyze_paths(["src", "examples", "benchmarks"])
    new, _, _ = apply_baseline(findings, load_baseline(DEFAULT_BASELINE))
    assert new == [], "\n".join(f.format() for f in new)
