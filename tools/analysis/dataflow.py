"""jaxpr dataflow engine: per-variable batch-axis taint propagation.

The C5 lane-independence prover (``contracts.py``) rests on this module.
The serving tier reuses the population axis as the request axis — lane *i*
of a decode dispatch carries request *i*'s frames under request *i*'s
allocation — which is only sound if every op in the banked forward is
*lane-independent*: output lane *i* depends on input lane *i* (plus
lane-shared constants) and nothing else. This engine machine-checks that
claim on the closed jaxpr the dispatch actually traces to.

Model: each variable carries a taint = the axis position of the population
axis in that variable, or ``None`` if the variable is lane-shared (weights,
banks, broadcast constants). Taints seed at the designated inputs (the qp
grid stack, per-lane feats) and flow through every equation via
per-primitive axis-transfer rules:

- elementwise / ``select_n`` / type conversions preserve the axis (all
  tainted operands must agree on it);
- ``broadcast_in_dim`` / ``reshape`` / ``transpose`` / ``squeeze`` /
  ``expand_dims`` remap it structurally (a reshape that splits or merges
  the population axis FAILS — prefix-product rule);
- ``dot_general`` requires the axis to ride a *batch* dimension (a
  contraction or free-dim pairing across lanes is a cross-lane mix);
- ``reduce_*`` / ``argmax`` / ``cum*`` / ``sort`` / ``rev`` over the
  population axis FAIL (they contract or permute lanes);
- ``gather``/``scatter`` are checked against their dimension_numbers: a
  per-lane index gathering from a lane-shared bank is the sanctioned
  gather-don't-requantize idiom; lane-shared indices selecting *from* the
  population axis are a mix;
- ``scan``/``while``/``cond``/``pjit``/``custom_jvp_call`` recurse into
  their sub-jaxprs (scan carries run to a taint fixpoint; scanning *over*
  the population axis FAILS);
- ``pallas_call`` and any primitive without a rule FAIL CLOSED when a
  tainted operand reaches them — an unknown op is an unproven op.

A proof succeeds when no rule fires and the population axis survives to
every output. Violations carry the failing primitive, operand shapes and
the traceback-derived source line of the eqn, so the finding points at
model code, not at the checker.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

Axis = Optional[int]


@dataclasses.dataclass(frozen=True)
class AxisViolation:
    """One lane-independence failure: the eqn that broke the axis."""
    primitive: str
    reason: str
    shapes: Tuple[Tuple[int, ...], ...]
    source: str                       # "file:line (fn)" best-effort

    def format(self) -> str:
        return (f"`{self.primitive}` {self.reason} "
                f"[operands {list(self.shapes)}] at {self.source}")


@dataclasses.dataclass
class LaneReport:
    """Result of one lane-independence proof attempt."""
    violations: List[AxisViolation]
    out_axes: List[Axis]

    @property
    def ok(self) -> bool:
        return not self.violations


class _Mix(Exception):
    """Internal: a transfer rule refused the eqn (reason in args[0])."""


def _aval_shape(v) -> Tuple[int, ...]:
    return tuple(getattr(getattr(v, "aval", None), "shape", ()) or ())


def _source_of(eqn) -> str:
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # noqa: BLE001 — cosmetics must never sink the proof
        return "<unknown>"


def _is_literal(v) -> bool:
    return hasattr(v, "val")          # jax.core.Literal carries .val


# --------------------------------------------------------------------------
# per-primitive transfer rules
#
# Each rule maps (eqn, input taints) -> output taints, or raises _Mix with
# the human-readable reason. Rules run ONLY when at least one input is
# tainted: an all-shared eqn can produce nothing lane-dependent.

_ELEMENTWISE = frozenset("""
    abs acos acosh add add_any and asin asinh atan atan2 atanh cbrt ceil
    clamp clz conj convert_element_type copy cos cosh device_put digamma
    div eq erf erf_inv erfc exp exp2 expm1 floor ge gt imag integer_pow
    is_finite le lgamma log log1p logistic lt max min mul ne neg nextafter
    not or population_count pow real reduce_precision rem round rsqrt
    select_n shift_left shift_right_arithmetic shift_right_logical sign
    sin sinh sqrt square stop_gradient sub tan tanh xor
""".split())

# call-like primitives: params key holding the (closed) sub-jaxpr whose
# invars map 1:1 onto the eqn's invars
_CALL_JAXPR_PARAM = {
    "jit": "jaxpr",             # named "pjit" before jax 0.7
    "pjit": "jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "remat": "jaxpr",
    "checkpoint": "jaxpr",
    "remat2": "jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "custom_jvp_call_jaxpr": "fun_jaxpr",
    "custom_vjp_call_jaxpr": "fun_jaxpr",
}


def _one_axis(ins: Sequence[Axis], what: str) -> int:
    axes = {a for a in ins if a is not None}
    if len(axes) > 1:
        raise _Mix(f"{what} combines operands whose population axes "
                   f"disagree ({sorted(axes)})")
    return axes.pop()


def _t_elementwise(eqn, ins: Sequence[Axis]) -> List[Axis]:
    axis = _one_axis(ins, "elementwise op")
    for v, a in zip(eqn.invars, ins):
        if a is not None and len(_aval_shape(v)) <= a:
            raise _Mix("tainted operand rank below its population axis")
    out_rank = len(_aval_shape(eqn.outvars[0]))
    if axis >= out_rank:
        raise _Mix("population axis does not fit the output rank")
    return [axis] * len(eqn.outvars)


def _t_broadcast_in_dim(eqn, ins) -> List[Axis]:
    bdims = tuple(eqn.params["broadcast_dimensions"])
    return [bdims[ins[0]]]


def _t_reshape(eqn, ins) -> List[Axis]:
    axis = ins[0]
    if axis is None:
        raise _Mix("reshape tainted through a non-array operand")
    dims = eqn.params.get("dimensions")
    in_shape = _aval_shape(eqn.invars[0])
    out_shape = tuple(eqn.params["new_sizes"])
    if dims is not None and tuple(dims) != tuple(range(len(in_shape))):
        raise _Mix("reshape with a dimensions permutation touching a "
                   "tainted operand (conservatively rejected)")
    # prefix-product rule: the population axis survives a reshape iff some
    # output axis has the same extent AND the same number of elements
    # before it — i.e. the reshape neither splits nor merges it.
    pre = math.prod(in_shape[:axis])
    for d, size in enumerate(out_shape):
        if size == in_shape[axis] and math.prod(out_shape[:d]) == pre:
            return [d]
    raise _Mix(f"reshape {in_shape}->{out_shape} splits or merges the "
               f"population axis (axis {axis})")


def _t_transpose(eqn, ins) -> List[Axis]:
    perm = tuple(eqn.params["permutation"])
    return [perm.index(ins[0])]


def _t_rev(eqn, ins) -> List[Axis]:
    if ins[0] in tuple(eqn.params["dimensions"]):
        raise _Mix("rev permutes the population axis (lane i would read "
                   "lane P-1-i)")
    return [ins[0]]


def _t_reduce(eqn, ins) -> List[Axis]:
    axes = tuple(eqn.params["axes"])
    axis = _one_axis(ins, "reduction")
    if axis in axes:
        raise _Mix("reduction contracts the population axis (mixes every "
                   "lane into one value)")
    return [axis - sum(1 for d in axes if d < axis)] * len(eqn.outvars)


def _t_cumulative(eqn, ins) -> List[Axis]:
    if ins[0] == eqn.params["axis"]:
        raise _Mix("cumulative op runs along the population axis (lane i "
                   "reads lanes 0..i)")
    return [ins[0]]


def _t_sort(eqn, ins) -> List[Axis]:
    axis = _one_axis(ins, "sort")
    if axis == eqn.params["dimension"]:
        raise _Mix("sort permutes the population axis data-dependently")
    return list(ins)


def _t_concatenate(eqn, ins) -> List[Axis]:
    axis = _one_axis(ins, "concatenate")
    if eqn.params["dimension"] == axis:
        raise _Mix("concatenate stacks extra rows onto the population "
                   "axis (lane numbering no longer matches requests)")
    return [axis]


def _t_pad(eqn, ins) -> List[Axis]:
    if ins[1] is not None:
        raise _Mix("pad value is lane-dependent but rank-0")
    axis = ins[0]
    lo, hi, interior = tuple(eqn.params["padding_config"])[axis]
    if lo or interior:
        raise _Mix("pad shifts the population axis (low/interior padding "
                   "renumbers lanes)")
    return [axis]


def _t_slice(eqn, ins) -> List[Axis]:
    axis = ins[0]
    starts = tuple(eqn.params["start_indices"])
    limits = tuple(eqn.params["limit_indices"])
    strides = tuple(eqn.params["strides"] or (1,) * len(starts))
    size = _aval_shape(eqn.invars[0])[axis]
    if (starts[axis], limits[axis], strides[axis]) != (0, size, 1):
        raise _Mix("slice selects a subset of the population axis "
                   "(renumbers lanes)")
    return [axis]


def _t_dynamic_slice(eqn, ins) -> List[Axis]:
    if any(a is not None for a in ins[1:]):
        raise _Mix("dynamic_slice start index is lane-dependent")
    axis = ins[0]
    sizes = tuple(eqn.params["slice_sizes"])
    if sizes[axis] != _aval_shape(eqn.invars[0])[axis]:
        raise _Mix("dynamic_slice carves the population axis at a traced "
                   "offset (lane selection is data-dependent)")
    return [axis]


def _t_dynamic_update_slice(eqn, ins) -> List[Axis]:
    op_ax, up_ax = ins[0], ins[1]
    if any(a is not None for a in ins[2:]):
        raise _Mix("dynamic_update_slice start index is lane-dependent")
    if op_ax is None and up_ax is None:
        return [None]
    axis = op_ax if op_ax is not None else up_ax
    if op_ax is not None and up_ax is not None and op_ax != up_ax:
        raise _Mix("dynamic_update_slice operand/update disagree on the "
                    "population axis")
    up_shape = _aval_shape(eqn.invars[1])
    out_shape = _aval_shape(eqn.outvars[0])
    if up_ax is not None and up_shape[axis] != out_shape[axis]:
        raise _Mix("dynamic_update_slice writes lane-dependent values to "
                   "a subset of the population axis")
    return [axis]


def _t_squeeze(eqn, ins) -> List[Axis]:
    dims = tuple(eqn.params["dimensions"])
    axis = ins[0]
    if axis in dims:
        raise _Mix("squeeze removes the population axis")
    return [axis - sum(1 for d in dims if d < axis)]


def _t_expand_dims(eqn, ins) -> List[Axis]:
    dims = tuple(eqn.params["dimensions"])
    out_rank = len(_aval_shape(eqn.outvars[0]))
    kept = [d for d in range(out_rank) if d not in dims]
    return [kept[ins[0]]]


def _gather_batch_positions(eqn) -> List[int]:
    dn = eqn.params["dimension_numbers"]
    out_rank = len(_aval_shape(eqn.outvars[0]))
    return [d for d in range(out_rank) if d not in dn.offset_dims]


def _indices_batch_index(eqn, idx_axis: int) -> int:
    """k-th batch dim of start_indices (excluding the index-vector dim)."""
    idx_rank = len(_aval_shape(eqn.invars[1]))
    vector_dim = idx_rank - 1   # lax gather puts the index vector last
    if idx_axis == vector_dim:
        raise _Mix("gather index-vector dimension is lane-dependent")
    return sum(1 for d in range(idx_axis) if d != vector_dim)


def _t_gather(eqn, ins) -> List[Axis]:
    op_ax, idx_ax = ins[0], ins[1]
    dn = eqn.params["dimension_numbers"]
    slice_sizes = tuple(eqn.params["slice_sizes"])
    op_shape = _aval_shape(eqn.invars[0])
    op_batch = tuple(getattr(dn, "operand_batching_dims", ()) or ())
    idx_batch = tuple(getattr(dn, "start_indices_batching_dims", ()) or ())
    if idx_ax is not None:
        # per-lane indices (the bank-row gather): the lane axis of the
        # indices becomes a batch dim of the output — lane i gathers with
        # lane i's index only.
        k = _indices_batch_index(eqn, idx_ax)
        if op_ax is not None:
            # both sides carry the axis: only sound when vmap paired them
            # as batching dims (lane i reads operand lane i).
            if op_ax not in op_batch \
                    or idx_batch[op_batch.index(op_ax)] != idx_ax:
                raise _Mix("gather mixes a lane-dependent operand with "
                           "lane-dependent indices without a batching-dim "
                           "pairing")
        return [_gather_batch_positions(eqn)[k]]
    # operand tainted, indices lane-shared
    if op_ax in op_batch:
        k = _indices_batch_index(eqn, idx_batch[op_batch.index(op_ax)])
        return [_gather_batch_positions(eqn)[k]]
    if op_ax in tuple(dn.start_index_map):
        raise _Mix("gather selects rows FROM the population axis with "
                   "lane-shared indices (output lane i can read any input "
                   "lane)")
    if op_ax in tuple(dn.collapsed_slice_dims):
        raise _Mix("gather collapses the population axis")
    if slice_sizes[op_ax] != op_shape[op_ax]:
        raise _Mix("gather windows the population axis (partial slice at "
                   "a shared offset renumbers lanes)")
    kept = [d for d in range(len(op_shape))
            if d not in tuple(dn.collapsed_slice_dims) and d not in op_batch]
    return [tuple(dn.offset_dims)[kept.index(op_ax)]]


def _t_scatter(eqn, ins) -> List[Axis]:
    op_ax, idx_ax, up_ax = ins[0], ins[1], ins[2]
    if idx_ax is not None:
        raise _Mix("scatter indices are lane-dependent with a lane-shared "
                   "destination (lanes write into each other)")
    dn = eqn.params["dimension_numbers"]
    inserted = tuple(dn.inserted_window_dims)
    op_shape = _aval_shape(eqn.invars[0])
    axis = op_ax
    if up_ax is not None:
        # updates' population axis must land on the matching operand
        # window dim, covering it fully
        window = [d for d in range(len(op_shape)) if d not in inserted]
        up_window = tuple(dn.update_window_dims)
        if up_ax not in up_window:
            raise _Mix("scatter updates carry the population axis on a "
                       "scatter (non-window) dimension")
        op_dim = window[up_window.index(up_ax)]
        up_shape = _aval_shape(eqn.invars[2])
        if up_shape[up_ax] != op_shape[op_dim]:
            raise _Mix("scatter writes lane-dependent updates to a subset "
                       "of the population axis")
        if op_ax is not None and op_ax != op_dim:
            raise _Mix("scatter operand/updates disagree on the "
                       "population axis")
        axis = op_dim
    if axis in inserted:
        raise _Mix("scatter writes into the population axis at lane-"
                   "shared indices")
    return [axis]


def _t_dot_general(eqn, ins) -> List[Axis]:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs_ax, rhs_ax = ins[0], ins[1]
    lhs_shape = _aval_shape(eqn.invars[0])

    def out_free(side_ax, shape, contract, batch, offset):
        free = [d for d in range(len(shape))
                if d not in contract and d not in batch]
        return len(lb) + offset + free.index(side_ax)

    if lhs_ax is not None and lhs_ax in lc or \
            rhs_ax is not None and rhs_ax in rc:
        raise _Mix("dot_general contracts the population axis (every "
                   "output lane sums over all input lanes)")
    if lhs_ax is not None and lhs_ax in lb:
        k = lb.index(lhs_ax)
        if rhs_ax is not None and rhs_ax != rb[k]:
            raise _Mix("dot_general batch dims pair the population axis "
                       "of one operand with a different axis of the other")
        return [k]
    if rhs_ax is not None and rhs_ax in rb:
        if lhs_ax is not None:     # lhs tainted but not on the batch dim
            raise _Mix("dot_general pairs a batched population axis with "
                       "an unbatched lane-dependent operand")
        return [rb.index(rhs_ax)]
    # tainted axis is a free dim: the OTHER operand must be lane-shared,
    # else lane i of the output multiplies data from two different lanes
    if lhs_ax is not None and rhs_ax is not None:
        raise _Mix("dot_general outer-products two lane-dependent "
                   "operands (free-dim cross-lane mix)")
    if lhs_ax is not None:
        return [out_free(lhs_ax, lhs_shape, lc, lb, 0)]
    rhs_shape = _aval_shape(eqn.invars[1])
    n_lhs_free = len(lhs_shape) - len(lc) - len(lb)
    return [out_free(rhs_ax, rhs_shape, rc, rb, n_lhs_free)]


_RULES: Dict[str, Callable[..., List[Axis]]] = {
    "broadcast_in_dim": _t_broadcast_in_dim,
    "reshape": _t_reshape,
    "transpose": _t_transpose,
    "rev": _t_rev,
    "reduce_sum": _t_reduce, "reduce_max": _t_reduce,
    "reduce_min": _t_reduce, "reduce_prod": _t_reduce,
    "reduce_and": _t_reduce, "reduce_or": _t_reduce,
    "reduce_xor": _t_reduce, "argmax": _t_reduce, "argmin": _t_reduce,
    "cumsum": _t_cumulative, "cumprod": _t_cumulative,
    "cummax": _t_cumulative, "cummin": _t_cumulative,
    "cumlogsumexp": _t_cumulative,
    "sort": _t_sort,
    "concatenate": _t_concatenate,
    "pad": _t_pad,
    "slice": _t_slice,
    "dynamic_slice": _t_dynamic_slice,
    "dynamic_update_slice": _t_dynamic_update_slice,
    "squeeze": _t_squeeze,
    "expand_dims": _t_expand_dims,
    "gather": _t_gather,
    "scatter": _t_scatter, "scatter-add": _t_scatter,
    "scatter-mul": _t_scatter, "scatter-min": _t_scatter,
    "scatter-max": _t_scatter,
    "dot_general": _t_dot_general,
}

_IDENTITY = frozenset({"sharding_constraint", "copy_p", "optimization_barrier"})


# --------------------------------------------------------------------------
# the propagation engine


def _join(old: Sequence[Axis], new: Sequence[Axis]) -> List[Axis]:
    """Carry-taint join for scan/while fixpoints: taint wins over None;
    two different axes cannot be joined (caller turns that into a _Mix)."""
    out: List[Axis] = []
    for a, b in zip(old, new):
        if a is not None and b is not None and a != b:
            raise _Mix(f"loop carry changes its population axis across "
                       f"iterations ({a} -> {b})")
        out.append(a if a is not None else b)
    return out


class _Engine:
    def __init__(self):
        self.violations: List[AxisViolation] = []

    def fail(self, eqn, reason: str) -> None:
        self.violations.append(AxisViolation(
            primitive=eqn.primitive.name, reason=reason,
            shapes=tuple(_aval_shape(v) for v in eqn.invars),
            source=_source_of(eqn)))

    # -- sub-jaxpr plumbing ---------------------------------------------

    def run_jaxpr(self, jaxpr, in_axes: Sequence[Axis]) -> List[Axis]:
        """Propagate through one (open) jaxpr; constvars are lane-shared."""
        env: Dict[Any, Axis] = {}

        def read(v) -> Axis:
            return None if _is_literal(v) else env.get(v)

        for cv in jaxpr.constvars:
            env[cv] = None
        if len(jaxpr.invars) != len(in_axes):
            raise ValueError(f"in_axes has {len(in_axes)} entries for "
                             f"{len(jaxpr.invars)} jaxpr inputs")
        for v, a in zip(jaxpr.invars, in_axes):
            env[v] = a
        for eqn in jaxpr.eqns:
            ins = [read(v) for v in eqn.invars]
            outs = self.run_eqn(eqn, ins)
            for v, a in zip(eqn.outvars, outs):
                env[v] = a
        return [read(v) for v in jaxpr.outvars]

    def _closed(self, sub):
        """(inner jaxpr, n leading const-invars) for Jaxpr or ClosedJaxpr."""
        inner = getattr(sub, "jaxpr", sub)
        return inner

    def run_call(self, eqn, ins) -> List[Axis]:
        sub = eqn.params[_CALL_JAXPR_PARAM[eqn.primitive.name]]
        inner = self._closed(sub)
        ins = list(ins)
        if len(inner.invars) != len(ins):
            # custom_* calls may append tangent/residual args; pad shared
            ins = (ins + [None] * len(inner.invars))[:len(inner.invars)]
        return self.run_jaxpr(inner, ins)

    def run_scan(self, eqn, ins) -> List[Axis]:
        p = eqn.params
        nc, nk = p["num_consts"], p["num_carry"]
        inner = self._closed(p["jaxpr"])
        consts, carry, xs = ins[:nc], list(ins[nc:nc + nk]), ins[nc + nk:]
        for i, a in enumerate(xs):
            if a == 0:
                raise _Mix("scan iterates OVER the population axis (the "
                           "carry chains lane i into lane i+1)")
        xs_in = [a - 1 if a is not None else None for a in xs]
        body_out: List[Axis] = []
        for _ in range(nk + 1):
            probe = _Engine()          # fixpoint probes must not duplicate
            body_out = probe.run_jaxpr(inner, consts + carry + xs_in)
            joined = _join(carry, body_out[:nk])
            if joined == carry:
                break
            carry = joined
        # final authoritative pass records violations exactly once
        body_out = self.run_jaxpr(inner, consts + carry + xs_in)
        ys = body_out[nk:]
        return body_out[:nk] + [a + 1 if a is not None else None for a in ys]

    def run_while(self, eqn, ins) -> List[Axis]:
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond = self._closed(p["cond_jaxpr"])
        body = self._closed(p["body_jaxpr"])
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        for _ in range(len(carry) + 1):
            probe = _Engine()
            out = probe.run_jaxpr(body, body_consts + carry)
            joined = _join(carry, out)
            if joined == carry:
                break
            carry = joined
        self.run_jaxpr(cond, cond_consts + carry)
        return self.run_jaxpr(body, body_consts + carry)

    def run_cond(self, eqn, ins) -> List[Axis]:
        if ins[0] is not None:
            raise _Mix("cond branch index is lane-dependent")
        outs: Optional[List[Axis]] = None
        for branch in eqn.params["branches"]:
            b_out = self.run_jaxpr(self._closed(branch), list(ins[1:]))
            if outs is None:
                outs = b_out
            elif outs != b_out:
                raise _Mix("cond branches disagree on the population axis "
                           f"of an output ({outs} vs {b_out})")
        return outs or []

    # -- dispatch --------------------------------------------------------

    def run_eqn(self, eqn, ins: Sequence[Axis]) -> List[Axis]:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)
        if all(a is None for a in ins):
            # lane-shared in, lane-shared out — except for structured
            # control flow, whose bodies may close over tainted... they
            # cannot: sub-jaxpr consts arrive via invars, all None here.
            return [None] * n_out
        try:
            if name in _RULES:
                return _RULES[name](eqn, ins)
            if name in _ELEMENTWISE:
                return _t_elementwise(eqn, ins)
            if name in _IDENTITY:
                return list(ins[:n_out])
            if name in _CALL_JAXPR_PARAM:
                return self.run_call(eqn, ins)
            if name == "scan":
                return self.run_scan(eqn, ins)
            if name == "while":
                return self.run_while(eqn, ins)
            if name == "cond":
                return self.run_cond(eqn, ins)
            if name == "pallas_call":
                raise _Mix("opaque pallas_call consumes the population "
                           "axis — lane-independence inside kernels is "
                           "the K-rules' job, not provable here")
            raise _Mix("no axis-transfer rule for this primitive "
                       "(fail-closed: an unknown op is an unproven op)")
        except _Mix as m:
            self.fail(eqn, str(m))
            return [None] * n_out


# --------------------------------------------------------------------------
# public entry points


def prove_lane_independence(closed_jaxpr, in_axes: Sequence[Axis],
                            require_tainted_outputs: bool = True
                            ) -> LaneReport:
    """Prove every output lane of ``closed_jaxpr`` depends only on its own
    input lane. ``in_axes[i]`` is the population-axis position of invar
    *i* (``None`` = lane-shared). Consts are always lane-shared."""
    eng = _Engine()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    try:
        out_axes = eng.run_jaxpr(jaxpr, list(in_axes))
    except _Mix as m:   # top-level joins (shouldn't happen) fail the proof
        return LaneReport([AxisViolation("<jaxpr>", str(m), (), "<top>")],
                          [])
    if require_tainted_outputs and not eng.violations:
        for i, a in enumerate(out_axes):
            if a is None and len(_aval_shape(jaxpr.outvars[i])) > 0:
                eng.violations.append(AxisViolation(
                    "<output>",
                    f"population axis never reaches output #{i} — the "
                    "per-lane inputs were dropped somewhere upstream",
                    (_aval_shape(jaxpr.outvars[i]),), "<outputs>"))
    return LaneReport(eng.violations, out_axes)


def trace_and_prove(fn, *args, in_axes: Sequence[Axis],
                    require_tainted_outputs: bool = True) -> LaneReport:
    """``jax.make_jaxpr`` + ``prove_lane_independence`` in one call.

    ``in_axes`` is per *argument* (pytree args broadcast their entry onto
    every leaf), matching how the harness declares its population inputs.
    """
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    flat_axes: List[Axis] = []
    for arg, ax in zip(args, in_axes):
        flat_axes += [ax] * len(jax.tree_util.tree_leaves(arg))
    return prove_lane_independence(closed, flat_axes,
                                   require_tainted_outputs)
