"""The Layer-1 AST invariant rules (R1-R5) of the repro-analyze gate.

Each rule encodes one invariant the runtime parity suites otherwise catch
minutes into the slow lane (see ROADMAP "Static-analysis gate"):

R1  SeedSequence invariant — no global-RNG use (``np.random.<global fn>``,
    bare ``random.*``) under core/, distributed/, serving/, or any
    SearchTarget implementation. Seeded ``Generator``/``SeedSequence`` construction is
    the sanctioned idiom and stays allowed.
R2  Deprecated entrypoints — no calls to the ``sru_experiment`` shims
    (``build_problem``, ``experiment1``-``3``) outside the shim module and
    its tests; new code goes through ``repro.core.api``.
R3  Host side effects inside jit — ``print``, ``.item()``,
    ``np.asarray``/``np.array``, ``jax.debug.*`` inside a jit/shard_map-
    compiled function break tracing or silently sync the device; a
    ``jax.profiler.TraceAnnotation``/``StepTraceAnnotation`` there spans
    the trace, not the run (``jax.named_scope`` names device ops). An
    ``# analyze: allow=R3 <reason>`` comment on the line suppresses.
R4  Retrace hazards — mutable default args on jitted functions, and
    ``static_argnames`` naming float-valued/mutable-default (or
    nonexistent) parameters: every new value silently recompiles.
R5  Parity-frozen dtypes — no ``jnp.float64`` / ``dtype="float64"`` /
    x64-enable in the modules whose bitwise parity contracts the whole
    search rests on (models/sru.py, core/quantization.py,
    core/batched_eval.py, kernels/). Host-side numpy f64 math is exempt —
    the evaluator's count->percent division deliberately uses it.
R6  Swallowed exceptions — no bare ``except:`` and no
    ``except Exception/BaseException`` whose body only passes (pass /
    ``...`` / continue) under core/, distributed/, kernels/, or
    serving/. The
    crash-safety work (checkpoint/resume + fault injection) depends on
    failures PROPAGATING so the retry/degradation paths see them; a
    silent handler turns an injected fault into a wrong answer. Retry
    sites must name the exception types they absorb
    (``faults.TRANSIENT_DISPATCH_ERRORS`` is the sanctioned tuple).
"""
from __future__ import annotations

import ast
from typing import Iterator, List

from tools.analysis.core import Finding, JitInfo, ModuleContext, Rule

_NP_RANDOM_ALLOWED = {
    "default_rng", "Generator", "SeedSequence", "BitGenerator",
    "PCG64", "PCG64DXSM", "Philox", "MT19937", "SFC64",
}
_STDLIB_RANDOM_ALLOWED = {"Random", "SystemRandom"}

_DEPRECATED_ENTRYPOINTS = {
    "build_problem", "experiment1_memory", "experiment2_silago",
    "experiment3_bitfusion",
}
_SHIM_MODULE = "repro.core.sru_experiment"

# profiler host spans: entered on the host, so inside a traced body they
# time the tracing, not the device work
_HOST_SPANS = ("jax.profiler.TraceAnnotation",
               "jax.profiler.StepTraceAnnotation")

_PARITY_FROZEN = (
    "repro/models/sru.py", "repro/core/quantization.py",
    "repro/core/batched_eval.py", "repro/kernels/",
)


class GlobalRNGRule(Rule):
    id = "R1"
    doc = ("global RNG state in search-engine code (SeedSequence "
           "invariant)")

    def applies(self, ctx: ModuleContext) -> bool:
        return ("repro/core/" in ctx.path or "repro/distributed/" in ctx.path
                or "repro/serving/" in ctx.path
                or ctx.defines_search_target())

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Attribute):
                mod = ctx.resolve_module(func.value)
                if mod == "numpy.random" \
                        and func.attr not in _NP_RANDOM_ALLOWED:
                    yield self.finding(
                        ctx, node,
                        f"np.random.{func.attr}() uses global RNG state; "
                        "spawn a Generator from the search's single "
                        "np.random.SeedSequence instead")
                elif mod == "random" \
                        and func.attr not in _STDLIB_RANDOM_ALLOWED:
                    yield self.finding(
                        ctx, node,
                        f"random.{func.attr}() uses the stdlib global RNG; "
                        "use a seeded np.random.Generator")
            elif isinstance(func, ast.Name):
                target = ctx.resolve_call_target(func)
                if target and target.startswith("numpy.random.") \
                        and target.rsplit(".", 1)[1] not in _NP_RANDOM_ALLOWED:
                    yield self.finding(
                        ctx, node,
                        f"{target}() uses global RNG state; spawn a "
                        "Generator from the search's SeedSequence instead")
                elif target and target.startswith("random.") \
                        and target.rsplit(".", 1)[1] \
                        not in _STDLIB_RANDOM_ALLOWED:
                    yield self.finding(
                        ctx, node,
                        f"{target}() uses the stdlib global RNG; use a "
                        "seeded np.random.Generator")


class DeprecatedEntrypointRule(Rule):
    id = "R2"
    doc = "calls to deprecated sru_experiment entrypoints"

    def applies(self, ctx: ModuleContext) -> bool:
        if ctx.path.endswith(_SHIM_MODULE.replace(".", "/") + ".py"):
            return False
        parts = ctx.path.split("/")
        return "tests" not in parts    # the shims' dedicated tests are exempt

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            name = None
            if isinstance(func, ast.Name):
                tgt = ctx.resolve_call_target(func)
                if tgt and tgt.startswith(_SHIM_MODULE + "."):
                    name = tgt.rsplit(".", 1)[1]
            elif isinstance(func, ast.Attribute):
                if ctx.resolve_module(func.value) == _SHIM_MODULE:
                    name = func.attr
            if name in _DEPRECATED_ENTRYPOINTS:
                yield self.finding(
                    ctx, node,
                    f"deprecated entrypoint sru_experiment.{name}(); use "
                    "repro.core.api (SearchSession / "
                    "build_problem_from_target)")


class HostSideEffectRule(Rule):
    id = "R3"
    doc = "host side effects inside jit/shard_map-compiled functions"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for jit in ctx.jitted:
            body = jit.node.body if isinstance(jit.node, ast.Lambda) \
                else jit.node
            nodes = ast.walk(body) if not isinstance(body, list) \
                else (n for stmt in body for n in ast.walk(stmt))
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                where = f"in jitted `{jit.name}`"
                if isinstance(func, ast.Name) and func.id == "print":
                    yield self.finding(
                        ctx, node, f"print() {where} runs at trace time "
                        "only; use jax.debug.print if intended")
                elif isinstance(func, ast.Attribute) \
                        and func.attr == "item" and not node.args:
                    yield self.finding(
                        ctx, node, f".item() {where} forces a host sync "
                        "and fails under tracing")
                elif isinstance(func, ast.Attribute) \
                        and func.attr in ("asarray", "array") \
                        and ctx.resolve_module(func.value) == "numpy":
                    yield self.finding(
                        ctx, node, f"np.{func.attr}() {where} materializes "
                        "a tracer on the host (TracerError under jit)")
                elif isinstance(func, ast.Attribute) \
                        and ctx.resolve_module(func.value) == "jax.debug":
                    yield self.finding(
                        ctx, node, f"jax.debug.{func.attr}() {where} "
                        "without an allowlist comment "
                        "(# analyze: allow=R3 <reason>)")
                elif ctx.resolve_call_target(func) in _HOST_SPANS:
                    yield self.finding(
                        ctx, node, f"{ctx.resolve_call_target(func)} "
                        f"{where} opens its span at trace time only and "
                        "records nothing when the program runs; put it "
                        "around the call, or use jax.named_scope inside")


def _is_mutable_literal(node: ast.AST) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set)):
        return True
    return (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id in ("list", "dict", "set", "bytearray"))


def _static_names(kwargs) -> List[str]:
    node = kwargs.get("static_argnames")
    names: List[str] = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        names.append(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        names += [e.value for e in node.elts
                  if isinstance(e, ast.Constant) and isinstance(e.value, str)]
    return names


def _static_nums(kwargs) -> List[int]:
    """Literal ints from ``static_argnums`` — the positional spelling of
    ``static_argnames``. Only compile-time-constant indices resolve; a
    computed argnums expression is invisible to this rule (as everywhere
    in Layer 1)."""
    node = kwargs.get("static_argnums")
    nums: List[int] = []
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        nums.append(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        nums += [e.value for e in node.elts
                 if isinstance(e, ast.Constant)
                 and isinstance(e.value, int)
                 and not isinstance(e.value, bool)]
    return nums


class RetraceHazardRule(Rule):
    id = "R4"
    doc = "silent-retrace hazards on jitted functions"

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for jit in ctx.jitted:
            node = jit.node
            args = node.args
            params = ([a.arg for a in getattr(args, "posonlyargs", [])]
                      + [a.arg for a in args.args])
            # align defaults with the tail of the positional params
            defaults = {}
            for name, d in zip(params[len(params) - len(args.defaults):],
                               args.defaults):
                defaults[name] = d
            for a, d in zip(args.kwonlyargs, args.kw_defaults):
                if d is not None:
                    defaults[a.arg] = d
            for name, d in defaults.items():
                if _is_mutable_literal(d):
                    yield Finding(
                        self.id, ctx.path, d.lineno,
                        f"mutable default for `{name}` on jitted "
                        f"`{jit.name}`: shared across traces and "
                        "unhashable as a static")
            statics = _static_names(jit.kwargs)
            all_params = params + [a.arg for a in args.kwonlyargs]
            # static_argnums is the same contract in positional clothing:
            # resolve each index to its parameter name so the float/mutable
            # default checks below apply through either spelling
            for n in _static_nums(jit.kwargs):
                if 0 <= n < len(params):
                    statics.append(params[n])
                elif args.vararg is None:
                    yield self.finding(
                        ctx, node,
                        f"static_argnums index {n} is out of range for "
                        f"jitted `{jit.name}` ({len(params)} positional "
                        f"parameter(s))")
            for s in statics:
                if s not in all_params:
                    if args.kwarg is None and not isinstance(node,
                                                            ast.Lambda):
                        yield self.finding(
                            ctx, node,
                            f"static_argnames names `{s}` which is not a "
                            f"parameter of jitted `{jit.name}`")
                    continue
                d = defaults.get(s)
                if d is None:
                    continue
                if isinstance(d, ast.Constant) and isinstance(d.value, float):
                    yield Finding(
                        self.id, ctx.path, d.lineno,
                        f"float-valued static arg `{s}` on jitted "
                        f"`{jit.name}`: every distinct value recompiles "
                        "silently — pass it as a traced array instead")
                elif _is_mutable_literal(d):
                    yield Finding(
                        self.id, ctx.path, d.lineno,
                        f"unhashable default for static arg `{s}` on "
                        f"jitted `{jit.name}`")


def _is_f64_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and node.value == "float64":
        return True
    if isinstance(node, ast.Attribute) and node.attr == "float64":
        return ctx.resolve_module(node.value) in ("jax.numpy", "numpy",
                                                  "jax.dtypes")
    return False


class ParityDtypeRule(Rule):
    id = "R5"
    doc = "float64/dtype-promotion literals in parity-frozen modules"

    def applies(self, ctx: ModuleContext) -> bool:
        return any(frag in ctx.path for frag in _PARITY_FROZEN)

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        flagged = set()

        def flag(node, msg):
            key = (node.lineno, msg)
            if key not in flagged:
                flagged.add(key)
                yield self.finding(ctx, node, msg)

        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Attribute) and node.attr == "float64" \
                    and ctx.resolve_module(node.value) == "jax.numpy":
                yield from flag(node, "jnp.float64 in a parity-frozen "
                                "module: the search's bitwise-parity "
                                "contracts are f32/fixed-point only")
            elif isinstance(node, ast.Call):
                func = node.func
                if isinstance(func, ast.Attribute) and func.attr == "astype" \
                        and node.args \
                        and _is_f64_expr(ctx, node.args[0]):
                    yield from flag(node, ".astype(float64) in a "
                                    "parity-frozen module promotes the "
                                    "on-device dtype")
                for kw in node.keywords:
                    if kw.arg == "dtype" and _is_f64_expr(ctx, kw.value):
                        # host-side numpy f64 math is allowed; only flag
                        # dtype= handed to a jnp/jax call
                        tgt = ctx.resolve_call_target(func) or ""
                        if tgt.startswith("jax.") or isinstance(kw.value,
                                                                ast.Constant):
                            yield from flag(node, "dtype=float64 on a jax "
                                            "call in a parity-frozen module")
                tgt = ctx.resolve_call_target(func)
                if tgt == "jax.config.update" and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and node.args[0].value == "jax_enable_x64":
                    yield from flag(node, "jax_enable_x64 flips every "
                                    "dtype-promotion rule the parity "
                                    "contracts were frozen under")


def _handler_names(handler: ast.ExceptHandler) -> List[str]:
    """Exception class names a handler catches (empty for bare except)."""
    t = handler.type
    if t is None:
        return []
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    names = []
    for e in elts:
        if isinstance(e, ast.Name):
            names.append(e.id)
        elif isinstance(e, ast.Attribute):
            names.append(e.attr)
    return names


def _swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler body does nothing but suppress: only pass,
    ``...`` or continue statements (logging/re-raising/recovery bodies are
    fine)."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Constant) \
                and stmt.value.value is Ellipsis:
            continue
        return False
    return True


class SwallowedExceptionRule(Rule):
    id = "R6"
    doc = ("bare/blanket exception handlers that swallow failures in "
           "crash-safety-critical modules")

    _SCOPE = ("repro/core/", "repro/distributed/", "repro/kernels/",
              "repro/serving/")
    _BLANKET = {"Exception", "BaseException"}

    def applies(self, ctx: ModuleContext) -> bool:
        return any(frag in ctx.path for frag in self._SCOPE)

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            names = _handler_names(node)
            if node.type is None:
                yield self.finding(
                    ctx, node,
                    "bare `except:` catches everything (KeyboardInterrupt, "
                    "injected faults, ...); name the exception types — the "
                    "degradation paths need failures to propagate")
            elif self._BLANKET & set(names) and _swallows(node):
                caught = next(iter(self._BLANKET & set(names)))
                yield self.finding(
                    ctx, node,
                    f"`except {caught}` with a pass-only body silently "
                    "swallows failures (including injected faults); name "
                    "the types and handle or re-raise")


ALL_RULES = (GlobalRNGRule(), DeprecatedEntrypointRule(),
             HostSideEffectRule(), RetraceHazardRule(), ParityDtypeRule(),
             SwallowedExceptionRule())
