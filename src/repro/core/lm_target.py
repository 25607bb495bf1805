"""What the search's language-model targets share: per-layer quantized
weight banks, the gather of a lane's bank rows, the hardware-objective
counts, the quantization-grid plumbing, calibration and the generic
``PopulationEvaluator`` binding.

A target (``core/xlstm_target.py``, ``core/granite_target.py``) subclasses
``LMTarget`` and keeps only what is its model's: the searchable leaves of
each layer (``_leaves``), the plain and the population forward
(``_forward_plain``, ``_forward_population``), ``fixed_ops``, and more
stats for each dispatch's ``evaluator.dispatch`` span where it has them.

Searchable layers share one weight grid (MMSE clip per bit-width, pooled
over the layer's matrices) and one activation grid calibrated at the
layer's input (median of per-batch max-abs). Each searchable leaf gets a
``(|menu|, *leaf.shape)`` bank built by the identical jitted
``fake_quant_triple`` expression (``Q.build_weight_bank``); the population
forward gathers each lane's row by menu index, recovered from the qp grid
tops (``Q.menu_index_from_hi``), instead of requantizing per lane — the
gather-don't-requantize contract of the SRU banks.

Error metric: next-token top-1 error % over the validation subsets, the
MAX over subsets (the paper's §4.2 ranking trick), as the SRU target has.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import batched_eval
from repro.core import quantization as Q

Alloc = Dict[str, Tuple[int, int]]


def gather_rows(bank: Dict[str, jnp.ndarray], idx, scope: str,
                skip: Tuple[str, ...] = ()) -> Dict[str, jnp.ndarray]:
    """One lane's copy of each leaf of a layer's bank: row ``idx`` (the
    lane's menu index), under the named scope ``scope``. Leaves in
    ``skip`` are not gathered (the forward reads their bank otherwise)."""
    with jax.named_scope(scope):
        return {k: jnp.take(b, idx, axis=0) for k, b in bank.items()
                if k not in skip}


def gathered_bytes(banks, lanes: int,
                   skip: Tuple[Tuple[str, str], ...] = ()) -> int:
    """Bytes of the per-lane weight copies a bucket program of ``lanes``
    lanes gathers from ``banks``: one bank row a lane for every leaf, less
    the (layer, leaf) pairs in ``skip``, which the forward contracts
    against the menu rows instead of copying."""
    return lanes * sum(int(np.prod(b.shape[1:])) * b.dtype.itemsize
                       for name, bank in (banks or {}).items()
                       for k, b in bank.items() if (name, k) not in skip)


def calibrate(forward_hooked, token_batches) -> Dict[str, float]:
    """Expected layer-input activation ranges = median of per-batch
    max-abs (the paper's calibration recipe). ``forward_hooked(tokens,
    q_act)`` runs the unquantized forward with the activation hook."""
    cal = Q.ActRangeCalibrator()

    def q_act(name, x):
        cal.observe(name, x)
        return x

    for toks in token_batches:
        forward_hooked(toks, q_act)
    return cal.expected_ranges()


def weight_grids(leaves_of, names):
    """(wclips, wranges): per-(layer, bits) MMSE clips pooled over the
    layer's matrices (``leaves_of(name)``), and per-layer abs-max ranges
    for the 16-bit rows."""
    wclips: Dict[Tuple[str, int], float] = {}
    wranges: Dict[str, float] = {}
    for name in names:
        flat = np.concatenate([np.asarray(v, np.float32).ravel()
                               for v in leaves_of(name).values()])
        wranges[name] = float(np.abs(flat).max())
        for bits in (2, 4, 8):
            wclips[(name, bits)] = Q.mmse_clip(flat, bits)
    return wclips, wranges


@dataclass
class LMTarget:
    """``SearchTarget`` over a calibrated language model with per-layer
    banks; subclasses supply the model (module docstring)."""
    cfg: object
    params: dict
    val_subsets: list               # S x (tokens, next-token labels)
    test_batches: list
    act_ranges: Dict[str, float]
    wclips: Dict[Tuple[str, int], float]
    wranges: Dict[str, float]
    baseline_val_error: float = 0.0
    baseline_test_error: float = 0.0

    supports_retrain = False
    # whether ``_forward_population`` takes the evaluator's live mask and
    # computes no padding lane (``PopulationEvaluator``'s skips_padding)
    skips_padding = False

    def __post_init__(self):
        self.shared_error_memo: Dict[tuple, float] = {}
        self._evaluators: Dict[tuple, batched_eval.PopulationEvaluator] = {}
        self._qp_tables = None
        self._layer_weights = None
        self._plain = jax.jit(self._forward_plain)
        self._pop = jax.jit(
            lambda p, t, stack: self._forward_population(p, t, stack, None))

    # ---- the model (subclasses) ----

    @property
    def layer_names(self) -> Tuple[str, ...]:
        raise NotImplementedError

    def _leaves(self, params, name: str) -> Dict[str, jnp.ndarray]:
        """The full-precision searchable leaves of one layer."""
        raise NotImplementedError

    def _forward_plain(self, params, tokens):
        raise NotImplementedError

    def _forward_population(self, params, tokens, qp_stack, banks):
        raise NotImplementedError

    def _dispatch_stats(self, traced: int, computed: int,
                        banks) -> Dict[str, int]:
        """Stats of the ``evaluator.dispatch`` span of a bucket program
        that traces ``traced`` lanes a shard and computes ``computed``
        lanes in all: ``gathered_bytes``, the per-lane weight copies of
        the computed lanes."""
        return {"gathered_bytes": gathered_bytes(banks, computed)}

    # ---- search-space description ----

    @property
    def menu(self) -> Tuple[int, ...]:
        return Q.SUPPORTED_BITS

    # ---- hardware-objective inputs ----

    @property
    def layer_weights(self) -> Dict[str, int]:
        """Weights of each layer's searchable matrices, from their shapes
        (worked out once: no device work per search)."""
        if self._layer_weights is None:
            shapes = jax.eval_shape(
                lambda p: {n: self._leaves(p, n) for n in self.layer_names},
                self.params)
            self._layer_weights = {
                name: sum(int(np.prod(v.shape)) for v in leaves.values())
                for name, leaves in shapes.items()}
        return dict(self._layer_weights)

    @property
    def layer_macs(self) -> Dict[str, int]:
        """Per-token MACs == matmul weights per layer (each matrix weight
        multiplies once per token, recurrent kernels once per step — the
        same weights==MACs identity the SRU layers have)."""
        return self.layer_weights

    @property
    def vector_weights(self) -> int:
        """Everything outside the searchable matrices (norms, gates,
        biases, conv, unsearched tables) — stored at 16 bits, never
        searched."""
        total = sum(int(np.prod(np.shape(leaf)))
                    for leaf in jax.tree.leaves(self.params))
        return total - sum(self.layer_weights.values())

    # ---- quantization-grid plumbing ----

    def qp_for(self, alloc: Alloc):
        qp = {}
        for name, (wb, ab) in alloc.items():
            wtrip = Q.quant_triple(
                wb, self.wclips[(name, wb)] if wb != 16
                else self.wranges[name])
            atrip = Q.quant_triple(ab, self.act_ranges[name])
            qp[name] = tuple(np.float32(v) for v in (wtrip + atrip))
        return qp

    def qp_menu_tables(self):
        if self._qp_tables is None:
            names = self.layer_names
            K = len(Q.SUPPORTED_BITS)
            w_t = np.empty((len(names), K, 3), np.float32)
            a_t = np.empty((len(names), K, 3), np.float32)
            for i, nm in enumerate(names):
                for k, b in enumerate(Q.SUPPORTED_BITS):
                    w_t[i, k] = Q.quant_triple(
                        b, self.wranges[nm] if b == 16
                        else self.wclips[(nm, b)])
                    a_t[i, k] = Q.quant_triple(b, self.act_ranges[nm])
            self._qp_tables = (w_t, a_t)
        return self._qp_tables

    def make_banks(self, params):
        """Per-layer, per-leaf quantized-weight banks against this target's
        frozen post-calibration grids (one build per parameter set)."""
        banks = {}
        for name in self.layer_names:
            trips = Q.menu_triples(
                Q.SUPPORTED_BITS,
                lambda b, _n=name: (self.wranges[_n] if b == 16
                                    else self.wclips[(_n, b)]))
            banks[name] = {k: Q.build_weight_bank(w, trips)
                           for k, w in self._leaves(params, name).items()}
        return banks

    # ---- error evaluation ----

    def batched_evaluator(self, mesh=None, partition: str = "shard_map",
                          use_banks: Optional[bool] = None
                          ) -> batched_eval.PopulationEvaluator:
        key = (mesh, partition if mesh is not None else "", use_banks)
        if key not in self._evaluators:
            self._evaluators[key] = batched_eval.PopulationEvaluator(
                self.layer_names, self.val_subsets, self.qp_for,
                self._forward_population, mesh=mesh, partition=partition,
                make_banks=self.make_banks, use_banks=use_banks,
                qp_tables=self.qp_menu_tables(), menu_bits=self.menu,
                dispatch_stats=self._dispatch_stats,
                skips_padding=self.skips_padding)
        return self._evaluators[key]

    def val_error_batch(self, allocs, params=None, *, mesh=None,
                        partition: str = "shard_map",
                        use_banks: Optional[bool] = None,
                        bank_format: str = "f32") -> List[float]:
        """Max-over-subsets next-token error % for every allocation in one
        dispatch (generic evaluator: buckets, folding, banks, mesh). Only
        f32 banks exist for these targets; another ``bank_format``
        raises."""
        if bank_format != "f32":
            raise ValueError(f"{type(self).__name__} has f32 banks only, "
                             f"not bank_format={bank_format!r}")
        params = self.params if params is None else params
        return self.batched_evaluator(mesh=mesh, partition=partition,
                                      use_banks=use_banks
                                      ).errors(allocs, params)

    def val_error(self, alloc: Optional[Alloc] = None,
                  params=None) -> float:
        params = self.params if params is None else params
        if alloc is not None:
            return self.val_error_batch([alloc], params=params)[0]
        errs = []
        for toks, labels in self.val_subsets:
            logits = self._plain(params, toks)
            e = int(jnp.sum(jnp.argmax(logits, -1) != labels))
            errs.append(100.0 * e / labels.size)
        return max(errs)

    def test_error(self, alloc: Optional[Alloc] = None,
                   params=None) -> float:
        params = self.params if params is None else params
        te = tn = 0
        for toks, labels in self.test_batches:
            if alloc is None:
                logits = self._plain(params, toks)
            else:
                stack = jnp.asarray(batched_eval.stack_qps(
                    [self.qp_for(alloc)], list(self.layer_names)))
                logits = self._pop(params, toks, stack)[0]
            te += int(jnp.sum(jnp.argmax(logits, -1) != labels))
            tn += labels.size
        return 100.0 * te / tn
