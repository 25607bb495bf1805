"""Registry of contract-check harnesses, one per SearchTarget architecture.

The jaxpr contract checker (``tools/analysis/contracts.py``) verifies IR-
level invariants of the search hot path — banked forwards never re-quantize
weights, no f64 creeps into an eval jaxpr, the per-generation evaluator is
one donated dispatch, and every op of the banked forward (and the serving
decode step) is lane-independent along the population axis (C5, the jaxpr
dataflow prover). Those checks need a *tiny but real* instance of each
target: real params, real quant tables, shapes small enough that tracing is
instant. A ``ContractHarness`` packages exactly that, and this registry
maps architecture names to lazy harness builders so a new target inherits
the whole gate by registering one function.

Harness shape convention: every harness uses a time/sequence length of
``marker_dim`` (3) that appears in NO other dimension of the model — params,
population, hidden sizes, menu. Activation fake-quant ops inside the
forward therefore carry the marker dim in their operand shapes, while any
weight (re)quantization op cannot: the checker tells the two apart purely
structurally, with no source annotations.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, List, Optional, Sequence

MARKER_DIM = 3


@dataclasses.dataclass
class ContractHarness:
    """Everything the jaxpr contract checker needs for one architecture."""

    name: str
    target: Any                      # the SearchTarget instance
    feats: Any                       # tiny batch inputs (B, T=MARKER_DIM, ...)
    labels: Any
    layer_names: Sequence[str]       # quantizable layer order for allocations
    marker_dim: int                  # the unique activation-time dimension
    anchor_path: str                 # repo-relative file findings anchor to
    # forward_pop(params, feats, qp_stack, banks) -> population outputs;
    # banks=None must fall back to the requantizing lane (checker sanity).
    forward_pop: Callable[..., Any]
    # () -> a banked PopulationEvaluator for the dispatch/donation checks
    make_evaluator: Callable[[], Any]
    supports_requant: bool = True
    # forward_decode(params, feats_lane, qp_stack, banks) -> (P, T, out)
    # serving decode step, feats_lane (P, T, ...) one chunk PER LANE — the
    # population-axis-as-request-axis dispatch the C5 lane-independence
    # prover must also certify. None = architecture has no serving tier
    # yet (it still gets C5 on forward_pop).
    forward_decode: Optional[Callable[..., Any]] = None


_BUILTIN: Dict[str, str] = {
    "sru": "repro.core.sru_experiment:sru_contract_harness",
    "xlstm": "repro.core.xlstm_target:xlstm_contract_harness",
    "granite_hybrid": "repro.core.granite_target:granite_contract_harness",
}
_CUSTOM: Dict[str, Callable[[], ContractHarness]] = {}


def register_contract_target(name: str,
                             builder: Callable[[], ContractHarness]) -> None:
    """Register a harness builder for a new architecture. The static-
    analysis gate picks it up on its next run — no checker changes."""
    _CUSTOM[name] = builder


def list_contract_targets() -> List[str]:
    return sorted(set(_BUILTIN) | set(_CUSTOM))


def get_contract_harness(name: str) -> ContractHarness:
    if name in _CUSTOM:
        return _CUSTOM[name]()
    try:
        spec = _BUILTIN[name]
    except KeyError:
        raise KeyError(
            f"unknown contract target {name!r}; "
            f"known: {list_contract_targets()}") from None
    mod_name, func_name = spec.split(":")
    return getattr(importlib.import_module(mod_name), func_name)()
