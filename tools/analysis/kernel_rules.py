"""Layer-3 of the static-analysis gate: the Pallas kernel verifier (K-rules).

The Pallas kernels in ``src/repro/kernels/`` are the one part of the hot
path the jaxpr contract checker cannot see inside: a ``pallas_call`` is a
single opaque primitive whose correctness hangs on out-of-band agreements —
grid/BlockSpec divisibility against the shapes the jitted wrappers feed,
index_map bounds for the scalar-prefetched bank-row gather, the VMEM
working set staying under the per-core budget, and the packed-container
layout matching between producer (``quantization.build_packed_weight_bank``
/ ``ref.pack_weights``) and consumer (``quant_matmul._unpack_block``).
This module checks all four statically, without ever executing a kernel
body.

How capture works: ``capture_pallas_calls`` monkeypatches
``pallas.pallas_call`` (both kernel modules bind it late through their
``pl`` module alias), records (grid, specs, out_shape, concrete operands,
call site) and returns zeros of ``out_shape`` so the wrapper code runs to
completion eagerly — no compile, no interpret loop, millisecond cost. The
drivers then invoke every *unjitted* public wrapper (``ops.<fn>.__wrapped__``)
at production-representative shapes: eager execution keeps scalar-prefetch
operands concrete, which is what lets K2 bounds-check the bank-row gather
with real index values, and bypassing jit defeats trace caching so repeat
in-process runs re-capture.

Rules (findings anchor to the ``pl.pallas_call`` site's file:line):

- K0  coverage/driver health: every ``pl.pallas_call`` site found by AST in
      kernels/ must be exercised by at least one driver, and no driver may
      crash. A site the verifier never saw is unverified, which is a
      finding, not a pass.
- K1  grid/BlockSpec divisibility: every operand dim divides its block dim,
      block rank matches operand rank, spec count matches operand count,
      grid entries are positive. (The raw kernels assert some of this; K1
      re-derives it from the captured call so a refactor that drops an
      assert still fails the gate.)
- K2  index_map discipline: index_maps are evaluated over the whole grid
      with the *concrete* scalar-prefetch operands — every returned block
      index must be an in-bounds integer (block stays inside the operand
      array; catches an out-of-range bank-row gather), return arity must
      match operand rank, and re-evaluation must be deterministic (the
      purity proxy: an index_map that reads mutable state fails this).
- K3  VMEM working set: sum of blocked in/out tiles, double-buffered (x2,
      the pipeline keeps a compute copy and a DMA copy per stream), plus
      scalar-prefetch operands, must fit the configurable per-core budget
      (default 16 MB; ``--vmem-budget-mb``).
- K4  packed-container layout agreement: ``pack_weights -> _unpack_block``
      must round-trip integer codes exactly for every packed width, and
      ``build_packed_weight_bank`` containers dequantized with the
      *kernel's* unpacker must reproduce the f32 ``build_weight_bank``
      stack bitwise (the parity contract the serving tier ships on).

``run_kernel_checks`` returns ``(findings, report)``: findings join the
baseline/pragma workflow like every other layer, the report feeds the
``--json`` ``kernels`` section (per-site grid/VMEM numbers).
"""
from __future__ import annotations

import ast
import contextlib
import dataclasses
import functools
import itertools
import os
import traceback
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from tools.analysis.core import Finding

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KERNEL_DIR = os.path.join(REPO_ROOT, "src", "repro", "kernels")
DEFAULT_VMEM_BUDGET_MB = 16.0   # per-core VMEM (see /opt guides); v4/v5 ~16MB
_MAX_GRID_POINTS = 4096         # full index_map enumeration cap


# --------------------------------------------------------------- AST sites

@dataclasses.dataclass(frozen=True)
class Site:
    """One ``pl.pallas_call`` call site found by AST."""
    path: str        # repo-relative, posix separators
    line: int
    func: str        # enclosing function name ("<module>" at top level)


def _rel(path: str) -> str:
    return os.path.relpath(path, REPO_ROOT).replace(os.sep, "/")


def enumerate_sites(kernel_dir: str = KERNEL_DIR) -> List[Site]:
    """All ``*.pallas_call`` call expressions in the kernels package."""
    sites: List[Site] = []
    for fname in sorted(os.listdir(kernel_dir)):
        if not fname.endswith(".py"):
            continue
        fpath = os.path.join(kernel_dir, fname)
        with open(fpath, "r", encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=fpath)
        rel = _rel(fpath)

        def visit(node: ast.AST, func: str) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                func = node.name
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                sites.append(Site(rel, node.lineno, func))
            for child in ast.iter_child_nodes(node):
                visit(child, func)

        visit(tree, "<module>")
    return sites


# ----------------------------------------------------------------- capture

@dataclasses.dataclass
class PallasCapture:
    """One recorded ``pallas_call`` invocation: everything the K-rules need,
    with the kernel body never executed."""
    path: str                      # repo-relative call-site file
    line: int                      # call-site line
    func: str                      # enclosing function (frame name)
    kernel_name: str
    grid: Tuple[int, ...]
    in_specs: List[Any]            # BlockSpecs for operands[nsp:]
    out_specs: List[Any]
    out_shapes: List[Any]          # ShapeDtypeStructs
    num_scalar_prefetch: int
    operands: Tuple[Any, ...]      # concrete arrays, prefetch first
    driver: str = ""

    @property
    def site(self) -> str:
        return f"{self.path}:{self.line}"


def _kernel_fn_name(kernel: Any) -> str:
    fn = getattr(kernel, "func", kernel)      # functools.partial
    return getattr(fn, "__name__", repr(fn))


def _as_list(x: Any) -> List[Any]:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


@contextlib.contextmanager
def capture_pallas_calls() -> Iterator[List[PallasCapture]]:
    """Patch ``pallas.pallas_call`` to record call metadata + operands and
    return zeros of ``out_shape`` instead of running the kernel. Both
    kernel modules resolve ``pl.pallas_call`` late through the module
    attribute, so one patch point covers every site."""
    import jax.numpy as jnp
    from jax.experimental import pallas

    captures: List[PallasCapture] = []
    real = pallas.pallas_call

    def fake_pallas_call(kernel, *, grid=None, grid_spec=None, in_specs=None,
                         out_specs=None, out_shape=None, **_kw):
        # call-site: innermost non-verifier frame (kernels/*.py in the real
        # drivers; the caller's file for test-local synthetic sites)
        here = os.path.abspath(__file__)
        frame = next(fr for fr in reversed(traceback.extract_stack())
                     if os.path.abspath(fr.filename) != here
                     and "contextlib" not in fr.filename)
        if grid_spec is not None:
            g = tuple(grid_spec.grid)
            ins = _as_list(grid_spec.in_specs)
            outs = _as_list(grid_spec.out_specs)
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
        else:
            g = tuple(grid) if grid is not None else ()
            ins = _as_list(in_specs)
            outs = _as_list(out_specs)
            nsp = 0
        shapes = _as_list(out_shape)
        multi = isinstance(out_shape, (list, tuple))

        def runner(*operands):
            captures.append(PallasCapture(
                path=_rel(frame.filename), line=frame.lineno or 1,
                func=frame.name, kernel_name=_kernel_fn_name(kernel),
                grid=g, in_specs=ins, out_specs=outs, out_shapes=shapes,
                num_scalar_prefetch=nsp, operands=operands))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in shapes]
            return zeros if multi else zeros[0]

        return runner

    pallas.pallas_call = fake_pallas_call
    try:
        yield captures
    finally:
        pallas.pallas_call = real


# ----------------------------------------------------------------- drivers

def default_drivers() -> List[Tuple[str, Callable[[], None]]]:
    """(name, thunk) pairs, one per public wrapper x configuration, at
    production-representative shapes: blocks resolve to the real 128-lane
    tiles and T is a full utterance chunk, so the K3 working-set numbers
    are the deployed ones, not toy ones. Each thunk calls the UNJITTED
    wrapper so capture happens on every run."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels import quant_matmul as kqmm
    from repro.core import quantization as Q

    rng = np.random.default_rng(0)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    drivers: List[Tuple[str, Callable[[], None]]] = []

    def quant_matmul_driver(bits: int) -> Callable[[], None]:
        def run() -> None:
            x = f32(128, 256)
            packed, scales = ops.pack_for_kernel(f32(256, 128), bits, 3.0)
            ops.quant_matmul.__wrapped__(x, packed, scales, bits,
                                         interpret=True)
        return run

    for bits in (2, 4, 8):
        drivers.append((f"quant_matmul[bits={bits}]",
                        quant_matmul_driver(bits)))

    def sru_scan_driver() -> None:
        u = [f32(8, 256, 128) for _ in range(3)]
        v = [f32(128) for _ in range(4)]
        ops.sru_scan.__wrapped__(*u, *v, interpret=True)

    def sru_scan_pop_driver() -> None:
        u = [f32(4, 8, 256, 128) for _ in range(3)]
        v = [f32(128) for _ in range(4)]
        ops.sru_scan_pop.__wrapped__(*u, *v, interpret=True)

    def bank_mxv_pop_driver() -> None:
        x = f32(4, 8, 512)
        bank = f32(len(Q.SUPPORTED_BITS), 512, 128)
        idx = jnp.asarray([0, 3, 1, 2], jnp.int32)
        ops.bank_mxv_pop.__wrapped__(x, bank, idx, interpret=True)

    def bank_qmm_pop_driver() -> None:
        w = f32(512, 128)
        triples = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 3.0)
        packed = Q.build_packed_weight_bank(w, triples)
        x = f32(4, 8, 512)
        idx = jnp.asarray([0, 1, 2, 3], jnp.int32)
        ops.bank_qmm_pop.__wrapped__(x, packed, idx, interpret=True)

    def unpack_codes_driver(bits: int) -> Callable[[], None]:
        def run() -> None:
            packed, _ = ops.pack_for_kernel(f32(256, 128), bits, 3.0)
            kqmm.unpack_codes(packed, bits, interpret=True)
        return run

    for bits in (2, 4):
        drivers.append((f"unpack_codes[bits={bits}]",
                        unpack_codes_driver(bits)))

    drivers += [("sru_scan", sru_scan_driver),
                ("sru_scan_pop", sru_scan_pop_driver),
                ("bank_mxv_pop", bank_mxv_pop_driver),
                ("bank_qmm_pop", bank_qmm_pop_driver)]
    return drivers


# ----------------------------------------------------------------- K-rules

def _spec_operand_pairs(cap: PallasCapture):
    """(kind, spec, shape, dtype) for every blocked stream of the call."""
    blocked = cap.operands[cap.num_scalar_prefetch:]
    pairs = []
    for spec, op in zip(cap.in_specs, blocked):
        pairs.append(("in", spec, tuple(op.shape), op.dtype))
    for spec, sh in zip(cap.out_specs, cap.out_shapes):
        pairs.append(("out", spec, tuple(sh.shape), sh.dtype))
    return pairs


def check_k1(cap: PallasCapture) -> List[str]:
    """Grid/BlockSpec divisibility and arity against the captured shapes."""
    msgs: List[str] = []
    if any(int(g) <= 0 for g in cap.grid):
        msgs.append(f"grid {cap.grid} has a non-positive dimension")
    n_blocked = len(cap.operands) - cap.num_scalar_prefetch
    if len(cap.in_specs) != n_blocked:
        msgs.append(
            f"{len(cap.in_specs)} in_specs for {n_blocked} blocked operands "
            f"(num_scalar_prefetch={cap.num_scalar_prefetch})")
    for kind, spec, shape, _dt in _spec_operand_pairs(cap):
        block = tuple(spec.block_shape)
        if len(block) != len(shape):
            msgs.append(f"{kind} block {block} rank != operand shape {shape}")
            continue
        for d, (b, s) in enumerate(zip(block, shape)):
            if b is None:
                continue
            if b <= 0 or s % b:
                msgs.append(
                    f"{kind} operand dim {d} of shape {shape} is not "
                    f"divisible by block {block} (dim {d}: {s} % {b} = "
                    f"{s % b if b else '?'}) — the kernel would read out of "
                    f"bounds or drop a remainder tile")
    return msgs


def _grid_points(grid: Sequence[int]) -> Iterator[Tuple[int, ...]]:
    total = 1
    for g in grid:
        total *= int(g)
    pts = itertools.product(*(range(int(g)) for g in grid))
    return itertools.islice(pts, _MAX_GRID_POINTS) if total \
        > _MAX_GRID_POINTS else pts


def check_k2(cap: PallasCapture) -> List[str]:
    """index_map bounds/determinism over the full grid with the concrete
    scalar-prefetch operands (the bank-row gather check)."""
    import numpy as np
    msgs: List[str] = []
    prefetch = [np.asarray(op) for op in
                cap.operands[:cap.num_scalar_prefetch]]
    for kind, spec, shape, _dt in _spec_operand_pairs(cap):
        block = tuple(spec.block_shape)
        if len(block) != len(shape):
            continue    # K1 already flagged it
        imap = spec.index_map
        if imap is None:
            continue
        label = f"{kind} spec (block {block})"
        for pt in _grid_points(cap.grid):
            try:
                out = imap(*pt, *prefetch)
                again = imap(*pt, *prefetch)
            except Exception as e:        # wrong arity, state, ...
                msgs.append(f"{label}: index_map raised at grid {pt}: {e!r}")
                break
            out_t = out if isinstance(out, tuple) else (out,)
            again_t = again if isinstance(again, tuple) else (again,)
            try:
                idxs = [int(v) for v in out_t]
                idxs2 = [int(v) for v in again_t]
            except (TypeError, ValueError):
                msgs.append(f"{label}: index_map returned non-integer block "
                            f"indices {out_t!r} at grid {pt}")
                break
            if idxs != idxs2:
                msgs.append(f"{label}: index_map is non-deterministic at "
                            f"grid {pt}: {idxs} then {idxs2}")
                break
            if len(idxs) != len(shape):
                msgs.append(f"{label}: index_map returned {len(idxs)} block "
                            f"indices for rank-{len(shape)} operand {shape}")
                break
            bad = [d for d, (i, b, s) in enumerate(zip(idxs, block, shape))
                   if i < 0 or (i * (b or s)) + (b or s) > s]
            if bad:
                msgs.append(
                    f"{label}: out-of-bounds block index at grid {pt}: "
                    f"indices {idxs} put dims {bad} outside operand shape "
                    f"{shape} — an out-of-range gather (e.g. a bank-row "
                    f"index >= the menu size) reads garbage weights")
                break
    return msgs


def estimate_vmem_bytes(cap: PallasCapture) -> int:
    """Working-set estimate: blocked tiles double-buffered (one compute +
    one in-flight DMA copy per stream, the standard pallas pipeline), plus
    the scalar-prefetch operands which live in SMEM/VMEM whole."""
    total = 0
    for _kind, spec, shape, dt in _spec_operand_pairs(cap):
        block = tuple(spec.block_shape)
        if len(block) != len(shape):
            continue
        n = 1
        for b, s in zip(block, shape):
            n *= int(b) if b else int(s)
        total += 2 * n * dt.itemsize
    for op in cap.operands[:cap.num_scalar_prefetch]:
        total += op.size * op.dtype.itemsize
    return total


def check_k3(cap: PallasCapture, budget_bytes: int) -> List[str]:
    est = estimate_vmem_bytes(cap)
    if est > budget_bytes:
        return [f"estimated VMEM working set {est / 2**20:.2f} MiB exceeds "
                f"the {budget_bytes / 2**20:.2f} MiB per-core budget "
                f"(double-buffered blocked tiles + scalar prefetch); shrink "
                f"the block or raise --vmem-budget-mb with a justification"]
    return []


def _unpack_def_line() -> Tuple[str, int]:
    """Anchor K4 findings at the consumer: ``_unpack_block``'s def line."""
    fpath = os.path.join(KERNEL_DIR, "quant_matmul.py")
    with open(fpath, "r", encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_unpack_block":
            return _rel(fpath), node.lineno
    return _rel(fpath), 1


def check_k4(pack_fn: Optional[Callable] = None,
             unpack_fn: Optional[Callable] = None) -> List[Finding]:
    """Producer/consumer packed-layout agreement. ``pack_fn``/``unpack_fn``
    default to the real ``ref.pack_weights`` / the kernels' decode
    (``quant_matmul._unpack_block``, run through ``unpack_codes``) — tests
    inject broken ones to prove the detector is live."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ref as kref
    from repro.kernels import quant_matmul as kqmm
    from repro.core import quantization as Q

    pack = pack_fn or kref.pack_weights
    unpack = unpack_fn or functools.partial(kqmm.unpack_codes,
                                            interpret=True)
    path, line = _unpack_def_line()
    out: List[Finding] = []
    rng = np.random.default_rng(7)

    # (a) pack -> unpack round-trips integer codes exactly, every width
    for bits in (2, 4, 8):
        lo, hi = Q.INT_RANGES[bits]
        q = jnp.asarray(rng.integers(lo, hi + 1, size=(8, 6)), jnp.int8)
        got = np.asarray(unpack(pack(q, bits), bits))[:8]
        if not np.array_equal(got, np.asarray(q)):
            first = tuple(np.argwhere(got != np.asarray(q))[0])
            out.append(Finding(
                "K4", path, line,
                f"pack_weights->_unpack_block round-trip broken for "
                f"bits={bits}: first mismatch at {first} "
                f"(got {got[first]}, want {int(np.asarray(q)[first])}) — "
                f"producer and kernel consumer disagree on the packed "
                f"container layout"))

    # (b) packed bank dequantized with the KERNEL's unpacker reproduces the
    # f32 bank stack bitwise (the serving parity contract)
    w = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
    triples = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 2.0)
    packed = Q.build_packed_weight_bank(w, triples)
    f32_bank = np.asarray(Q.build_weight_bank(w, triples))
    for k, bits in enumerate(Q.SUPPORTED_BITS):
        codes = packed[f"q{bits}"]
        if bits in Q._PACK_BITS:
            codes = unpack(codes, bits)[:16]
        deq = np.asarray(codes.astype(jnp.float32)
                         * packed["scale"][k][None, :])
        if not np.array_equal(deq, f32_bank[k]):
            out.append(Finding(
                "K4", path, line,
                f"build_packed_weight_bank q{bits} container dequantized "
                f"with the kernel unpacker differs from the f32 "
                f"build_weight_bank row (max abs err "
                f"{np.max(np.abs(deq - f32_bank[k])):.3g}) — the packed "
                f"serving lane would ship different weights than the "
                f"search evaluated"))
    return out


# ------------------------------------------------------------------ runner

def run_kernel_checks(
    vmem_budget_mb: float = DEFAULT_VMEM_BUDGET_MB,
    pack_fn: Optional[Callable] = None,
    unpack_fn: Optional[Callable] = None,
    drivers: Optional[Sequence[Tuple[str, Callable[[], None]]]] = None,
) -> Tuple[List[Finding], List[Dict[str, Any]]]:
    """Run K0–K4 over every pallas_call site. Returns (findings, report);
    the report is the ``--json`` ``kernels`` section: one entry per
    captured call with its grid and VMEM estimate."""
    budget = int(vmem_budget_mb * 2**20)
    sites = enumerate_sites()
    captures: List[PallasCapture]
    findings: List[Finding] = []
    with capture_pallas_calls() as captures:
        for name, thunk in (drivers if drivers is not None
                            else default_drivers()):
            before = len(captures)
            try:
                thunk()
            except Exception as e:
                findings.append(Finding(
                    "K0", _rel(os.path.join(KERNEL_DIR, "ops.py")), 1,
                    f"kernel-check driver {name!r} crashed: {e!r} — its "
                    f"pallas_call sites are unverified"))
            for cap in captures[before:]:
                cap.driver = name

    covered = {(c.path, c.func) for c in captures}
    for site in sites:
        if (site.path, site.func) not in covered:
            findings.append(Finding(
                "K0", site.path, site.line,
                f"pallas_call site in {site.func}() is not exercised by any "
                f"kernel-check driver — add a driver (see "
                f"tools/analysis/kernel_rules.default_drivers) so K1-K3 "
                f"can verify it"))

    report: List[Dict[str, Any]] = []
    for cap in captures:
        for rule, msgs in (("K1", check_k1(cap)),
                           ("K2", check_k2(cap)),
                           ("K3", check_k3(cap, budget))):
            for msg in msgs:
                findings.append(Finding(
                    rule, cap.path, cap.line,
                    f"[{cap.func} via {cap.driver}] {msg}"))
        report.append({
            "site": cap.site, "function": cap.func,
            "kernel": cap.kernel_name, "driver": cap.driver,
            "grid": list(cap.grid),
            "num_scalar_prefetch": cap.num_scalar_prefetch,
            "vmem_bytes_est": estimate_vmem_bytes(cap),
            "vmem_budget_bytes": budget,
        })

    findings += check_k4(pack_fn=pack_fn, unpack_fn=unpack_fn)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, report
