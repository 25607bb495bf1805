"""Shared visitor/reporting core of the repro-analyze static-analysis gate.

A ``Rule`` inspects one parsed module at a time through a ``ModuleContext``
that pre-computes everything every rule needs — import aliasing (so
``np.random`` resolves to ``numpy.random`` whatever the local name is),
the set of jit/shard_map-compiled functions, and the inline-pragma
suppression table. Findings print as ``path:line RULE-ID message`` and are
matched against the committed baseline (``baseline.py``) before they fail
the gate.

Inline suppression: a ``# analyze: allow=R3 <reason>`` comment on the
violating line (or the line directly above it) suppresses the named rules
for that line only — the allowlist-comment escape hatch R3's jax.debug
clause requires. Multiple rules may be listed (``allow=R3,C5`` or
``allow=R3, C5``); ``allow=*`` suppresses every rule on that line. A
pragma naming a rule id the gate does not know is itself a finding (E1)
that no pragma can suppress — a typo'd allowlist must not silently
suppress nothing (or, worse, look like it suppresses something).
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

_PRAGMA = re.compile(
    r"#\s*analyze:\s*allow=([A-Za-z0-9_*-]+(?:\s*,\s*[A-Za-z0-9_*-]+)*)")

#: Every rule id any layer of the gate can emit. Pragmas are validated
#: against this set (unknown id -> E1); keep in sync when adding rules.
KNOWN_RULES = frozenset({
    "R1", "R2", "R3", "R4", "R5", "R6",            # layer 1: AST lint
    "C0", "C1", "C2", "C3", "C4", "C5",            # layer 2: jaxpr contracts
    "K0", "K1", "K2", "K3", "K4",                  # layer 3: kernel verifier
    "E0", "E1",                                    # gate-integrity errors
    "*",
})


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, posix separators
    line: int
    message: str

    @property
    def layer(self) -> str:
        """Which gate layer emitted this finding: ``ast`` (R-rules and the
        E gate-integrity errors, both products of source analysis),
        ``contract`` (C-rules, jaxpr level) or ``kernel`` (K-rules)."""
        return {"C": "contract", "K": "kernel"}.get(self.rule[:1], "ast")

    def format(self) -> str:
        return f"{self.path}:{self.line} {self.rule} {self.message}"

    def to_json(self) -> dict:
        return {"rule": self.rule, "layer": self.layer, "path": self.path,
                "line": self.line, "message": self.message}


@dataclasses.dataclass
class JitInfo:
    """One jit/shard_map-compiled function: the def (or lambda) node plus
    the keyword arguments of the compiling call (static_argnames, ...)."""
    node: ast.AST
    name: str
    kwargs: Dict[str, ast.AST]


class ModuleContext:
    """Parsed module + the resolution tables shared by every rule."""

    def __init__(self, path: str, source: str):
        self.path = path.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        # line -> set of rule ids allowed by an inline pragma; pragmas
        # naming unknown rule ids become E1 findings that run_rules emits
        # OUTSIDE the suppression path (a pragma cannot allowlist its own
        # typo away)
        self.allow: Dict[int, Set[str]] = {}
        self.pragma_findings: List[Finding] = []
        for i, line in enumerate(self.lines, 1):
            m = _PRAGMA.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")}
                unknown = sorted(rules - KNOWN_RULES)
                if unknown:
                    self.pragma_findings.append(Finding(
                        "E1", self.path, i,
                        f"pragma names unknown rule id(s) "
                        f"{', '.join(unknown)} — known ids: "
                        f"{', '.join(sorted(KNOWN_RULES - {'*'}))}"))
                self.allow[i] = rules & KNOWN_RULES
        # local name -> dotted module ("np" -> "numpy")
        self.module_aliases: Dict[str, str] = {}
        # local name -> (module, original name) from "from m import n as l"
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.module_aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
                    if a.asname:
                        self.module_aliases[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = (node.module,
                                                             a.name)
        self.jitted: List[JitInfo] = _find_jitted(self)
        self._jit_nodes = {id(j.node) for j in self.jitted}

    # ---- resolution helpers ----

    def resolve_module(self, node: ast.AST) -> Optional[str]:
        """Dotted module path an expression refers to, if it is (an alias
        of) an imported module: ``np.random`` -> "numpy.random"."""
        if isinstance(node, ast.Name):
            if node.id in self.module_aliases:
                return self.module_aliases[node.id]
            if node.id in self.from_imports:      # from pkg import submodule
                mod, orig = self.from_imports[node.id]
                return f"{mod}.{orig}"
            return None
        if isinstance(node, ast.Attribute):
            base = self.resolve_module(node.value)
            return f"{base}.{node.attr}" if base else None
        return None

    def resolve_call_target(self, func: ast.AST) -> Optional[str]:
        """Fully-qualified name a call's func expression resolves to, e.g.
        ``jit`` (from jax) -> "jax.jit", ``X.build_problem`` ->
        "repro.core.sru_experiment.build_problem"."""
        if isinstance(func, ast.Name):
            if func.id in self.from_imports:
                mod, orig = self.from_imports[func.id]
                return f"{mod}.{orig}"
            return None
        if isinstance(func, ast.Attribute):
            base = self.resolve_module(func.value)
            return f"{base}.{func.attr}" if base else None
        return None

    def suppressed(self, rule: str, line: int) -> bool:
        for ln in (line, line - 1):
            rules = self.allow.get(ln, ())
            if rule in rules or "*" in rules:
                return True
        return False

    def defines_search_target(self) -> bool:
        """Heuristic: the module implements a ``SearchTarget`` (a class
        with a ``val_error_batch`` method or a ``supports_retrain``
        attribute) — pulls it into the R1 SeedSequence-invariant scope even
        outside core/ and distributed/."""
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and item.name == "val_error_batch":
                    return True
                if isinstance(item, ast.Assign):
                    for t in item.targets:
                        if isinstance(t, ast.Name) \
                                and t.id == "supports_retrain":
                            return True
        return False


def _is_jit_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    target = ctx.resolve_call_target(node)
    if target in ("jax.jit", "jax.shard_map",
                  "jax.experimental.shard_map.shard_map"):
        return True
    # plain attribute without an import resolution (e.g. jax.jit when jax
    # itself resolves) is covered above; fall back to a literal match
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return ctx.resolve_module(node.value) == "jax"
    return False


def _is_partial_expr(ctx: ModuleContext, node: ast.AST) -> bool:
    return ctx.resolve_call_target(node) == "functools.partial"


def _find_jitted(ctx: ModuleContext) -> List[JitInfo]:
    jitted: List[JitInfo] = []
    wrapped: Dict[str, Dict[str, ast.AST]] = {}

    def kw_map(call: ast.Call) -> Dict[str, ast.AST]:
        return {k.arg: k.value for k in call.keywords if k.arg}

    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call) and _is_jit_expr(ctx, node.func):
            if node.args:
                first = node.args[0]
                if isinstance(first, ast.Name):
                    wrapped[first.id] = kw_map(node)
                elif isinstance(first, ast.Lambda):
                    jitted.append(JitInfo(first, "<lambda>", kw_map(node)))
        elif isinstance(node, ast.Call) and _is_partial_expr(ctx, node.func):
            if node.args and _is_jit_expr(ctx, node.args[0]):
                # partial(jax.jit, static_argnames=...) used as a decorator
                # factory: resolved at the decorator site below
                pass

    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if _is_jit_expr(ctx, dec):
                jitted.append(JitInfo(node, node.name, {}))
            elif isinstance(dec, ast.Call):
                if _is_jit_expr(ctx, dec.func):
                    jitted.append(JitInfo(node, node.name,
                                          {k.arg: k.value
                                           for k in dec.keywords if k.arg}))
                elif _is_partial_expr(ctx, dec.func) and dec.args \
                        and _is_jit_expr(ctx, dec.args[0]):
                    jitted.append(JitInfo(node, node.name,
                                          {k.arg: k.value
                                           for k in dec.keywords if k.arg}))
        if node.name in wrapped and id(node) not in {id(j.node)
                                                     for j in jitted}:
            jitted.append(JitInfo(node, node.name, wrapped[node.name]))
    return jitted


class Rule:
    """Base class: subclasses set ``id``/``doc`` and implement ``check``.
    The runner handles pragma suppression and scoping via ``applies``."""

    id: str = "R?"
    doc: str = ""

    def applies(self, ctx: ModuleContext) -> bool:
        return True

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, ctx: ModuleContext, node: ast.AST,
                message: str) -> Finding:
        return Finding(self.id, ctx.path, getattr(node, "lineno", 1), message)


def run_rules(ctx: ModuleContext, rules: Sequence[Rule]) -> List[Finding]:
    # E1 pragma errors bypass suppression by construction: an unknown id
    # never enters ctx.allow, and a `*` on the same line must not hide the
    # typo either
    out: List[Finding] = list(ctx.pragma_findings)
    for rule in rules:
        if not rule.applies(ctx):
            continue
        for f in rule.check(ctx):
            if not ctx.suppressed(f.rule, f.line):
                out.append(f)
    return out


def collect_files(paths: Iterable[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"
                               and not d.startswith(".")]
                files += [os.path.join(dirpath, f)
                          for f in sorted(filenames) if f.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
    return files


def analyze_source(source: str, path: str,
                   rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    """Analyze one module given as text (the test-fixture entry point).
    ``path`` controls rule scoping, so fixtures can opt into path-scoped
    rules by naming themselves e.g. ``src/repro/core/fixture.py``."""
    from tools.analysis.rules import ALL_RULES
    ctx = ModuleContext(path, source)
    return run_rules(ctx, rules if rules is not None else ALL_RULES)


def analyze_paths(paths: Sequence[str], root: str = ".",
                  rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    from tools.analysis.rules import ALL_RULES
    rules = rules if rules is not None else ALL_RULES
    findings: List[Finding] = []
    for fpath in collect_files(paths):
        rel = os.path.relpath(fpath, root).replace(os.sep, "/")
        with open(fpath, "r", encoding="utf-8") as fh:
            source = fh.read()
        try:
            ctx = ModuleContext(rel, source)
        except SyntaxError as e:
            findings.append(Finding("E0", rel, e.lineno or 1,
                                    f"syntax error: {e.msg}"))
            continue
        findings += run_rules(ctx, rules)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
