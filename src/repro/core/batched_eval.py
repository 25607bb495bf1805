"""Batched candidate evaluation for the MOHAQ search (GA hot loop).

Model-agnostic since PR 5: ``PopulationEvaluator`` owns the whole batched
pipeline (subset folding, compile buckets, qp-stack assembly, bank cache,
mesh sharding, donation, count→error% host math) against any
``SearchTarget``'s population forward (see ``repro.core.api``);
``BatchedSRUEvaluator`` is the SRU binding of it. The prose below
describes the pipeline in terms of the SRU model it was grown on — every
contract transfers to any lane-independent population forward.

The inference-only search scores each GA candidate with a full quantized
forward pass; the paper's settings (60 generations x 10 individuals, 40 in
generation 0) pay for hundreds of *serial* model evaluations. Because every
menu precision is already expressed as a dynamic (scale, lo, hi) triple
(``quantization.quant_triple`` — one jitted forward serves every allocation),
an entire population batches for free: stack the per-layer triples of P
candidates into a (P, L, 6) array and run the quantized forward with an
explicit population axis. One jitted call then scores P candidates — the MxV
einsums become single P-wide matmuls and the per-call dispatch overhead is
paid once instead of P times.

Population sizes are padded up to fixed buckets so the jitted evaluator
compiles once per bucket, not once per population size.

Population-axis layout: ``stack_qps`` produces the (P, L, 6) grid array —
population lane x layer (in ``cfg.layer_names()`` order) x the six
(w_scale, w_lo, w_hi, a_scale, a_lo, a_hi) floats. The SRU's
``forward_population`` keeps the P axis explicit end to end: P-batched MxV
matmuls, one recurrence scan per Bi-SRU direction, and (with
``use_kernel=True``) a Pallas kernel whose grid is (P, B/bb, n/bn) so the
population axis feeds the compute grid directly.

Quantized-weight banks (``make_banks``/``use_banks``): the per-layer menu
is tiny ({2,4,8,16} bits) and the quantization grids freeze after
calibration, so at most four distinct fake-quantized copies of any weight
tensor exist across a whole search. The evaluator builds the stacked banks
ONCE per full-precision parameter set (base model, and each retrained
beacon's params on first use — cached by parameter identity) and the
population forward gathers rows by menu index instead of requantizing
per lane per call. Bank rows are bitwise identical to on-the-fly
quantization, so every parity contract below is unchanged.

One-dispatch-per-generation contract: with equal-shaped validation subsets
(the standard case — they fold into the batch axis) a generation's whole
evaluation — bank gather, Bi-SRU scans, frame-error reduction down to
per-(candidate, subset) integer error counts — is ONE jitted call, keyed by
the existing population compile buckets. Only the O(P) count→percentage
division and subset max stay on the host (kept in float64 numpy so error
values match the scalar path exactly). The per-call (P, L, 6) grid stack is
donated to the dispatch on accelerator backends (donation is a no-op on
CPU, where XLA does not support buffer aliasing).

Beacon-grouping contract (core/beacon.py): the evaluator itself is
parameter-agnostic — ``errors(allocs, params)`` scores any candidate group
under any full-precision parameter set (base or retrained) with identical
integer error counts to the scalar path. Beacon search exploits this by
grouping a population by nearest beacon and issuing one ``errors`` call per
(beacon-params, candidate-group); correctness does not depend on which
params are passed, only bit-parity per call does, so grouped evaluation is
exactly the scalar sequence re-batched.

Device-mesh sharding (``mesh=``): the population axis additionally
partitions across a 1-D "pop" device mesh (``launch.mesh
.make_population_mesh`` / ``distributed.pop_sharding``): the qp grid stack
is sharded over P, parameters and the validation set (and the calibration
state baked into the grids) are replicated per shard, and the per-candidate
integer error counts are gathered back to the host. Populations pad up to a
multiple of the shard count on top of the compile buckets; padding lanes
duplicate the last candidate and are sliced off after the gather. Because
lanes are independent, the sharded evaluator keeps the bit-identical error
contract — beacon groups shard independently (each grouped ``errors`` call
is itself a sharded population).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import faults as fault_policies
from repro.distributed import pop_sharding
from repro.distributed import sharding as dist_sharding

Alloc = Dict[str, Tuple[int, int]]

# population-size buckets the batched forward is compiled for; sizes above
# the largest bucket round up to a multiple of it
_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def bucket_size(p: int) -> int:
    """Smallest compile bucket holding a population of ``p`` candidates."""
    for b in _BUCKETS:
        if p <= b:
            return b
    top = _BUCKETS[-1]
    return ((p + top - 1) // top) * top


def stack_qps(qp_list: Sequence[Dict[str, tuple]],
              layer_names: Sequence[str]) -> np.ndarray:
    """Stack per-candidate quantization-parameter dicts
    ({name: (w_scale, w_lo, w_hi, a_scale, a_lo, a_hi)}, as produced by
    ``sru.quant_triples_for``) into a (P, L, 6) float32 array in
    ``layer_names`` order — the population axis the batched forward vmaps
    over."""
    arr = np.empty((len(qp_list), len(layer_names), 6), np.float32)
    for p, qp in enumerate(qp_list):
        for i, name in enumerate(layer_names):
            arr[p, i, :] = qp[name]
    return arr


class PopulationEvaluator:
    """Model-agnostic population scorer: the generic half of the batched
    evaluation pipeline, shared by every ``SearchTarget`` implementation
    (see ``repro.core.api``). A target supplies the model-specific pieces —
    a population-parameterized forward and (optionally) bank construction —
    and this class owns everything else: validation-subset folding, compile
    buckets, qp-stack assembly (menu tables or per-candidate ``make_qp``),
    the per-parameter-set bank cache, mesh sharding, donation, and the
    count→max-error% host math.

    ``forward_pop(params, feats, qp_stack, banks)`` -> logits
    (P, B, T, n_out): the model's population forward. Lanes must be
    independent in P (required by the mesh sharding and the padding).

    ``make_qp``: Alloc -> {layer: 6-float grid} (numpy, per candidate —
    cheap; the jitted forward never recompiles across allocations).
    Error convention matches the scalar path: per candidate, the MAX
    frame-error % over the validation subsets (paper §4.2).

    ``make_banks`` (optional): params -> quantized-weight banks for
    ``forward_pop``. With ``use_banks=True`` (the default whenever
    ``make_banks`` is wired) the dispatch gathers each lane's weights from
    the banks instead of requantizing — banks are built once per distinct
    parameter set and cached, so beacon retrained parameters each get
    their own bank on first evaluation. ``extend_banks(banks, feats)``
    (optional) post-processes freshly built banks against the folded
    validation features (the SRU input-layer u-bank hook).

    ``bank_format``: ``"f32"`` (default) caches the fake-quant f32 bank
    stacks; ``"packed"`` caches packed-integer banks built by
    ``make_packed_banks`` instead — >= 4x smaller in memory, bit-identical
    error counts (the forward dequantizes containers to the exact f32 bank
    rows). The packed format skips the ``extend_banks`` hook: the u-bank
    specialization needs the f32 weight stacks, and precomputing |menu|^2
    f32 u-streams would defeat the packed lane's memory story.

    ``mesh`` (optional): a mesh with a "pop" axis shards the population
    across devices — ``partition="shard_map"`` (default, exact per-shard
    program) or ``"gspmd"`` (jit with PartitionSpecs). Banks replicate per
    shard (like params) and the row gather runs inside each shard's
    program, so single-device behaviour and error counts are unchanged.

    ``skips_padding``: ``forward_pop`` takes two more arguments, ``live``
    ((P,) bool, false on the padding lanes) and ``lane_out`` (one lane's
    logits -> that lane's wrong counts, so that a forward running lanes in
    turn never holds every lane's logits), returns each lane's counts, and
    computes no lane whose ``live`` is false (its counts are zeros). The
    granite target runs its lanes one at a time, so a padding lane skipped
    is device time saved; a forward that batches its lanes ignores the
    mask. Each dispatch's ``evaluator.dispatch`` span records
    ``lanes_computed``: the real lanes where the forward skips padding,
    else the whole padded bucket.

    ``dispatch_stats`` (optional): ``(traced, computed, banks) -> {stat:
    int}``, more stats for that span, given the lane count ``forward_pop``
    traces (per shard under ``shard_map``), ``lanes_computed`` and the
    banks it is passed (the LM targets record the weight copies their
    computed lanes gather, and the xLSTM target which sLSTM recurrence its
    forward takes).
    """

    def __init__(self, layer_names, val_subsets,
                 make_qp: Callable[[Alloc], dict],
                 forward_pop: Callable,
                 mesh=None, partition: str = "shard_map",
                 pop_axis: str = pop_sharding.POP_AXIS,
                 make_banks: Optional[Callable] = None,
                 use_banks: Optional[bool] = None,
                 qp_tables=None,
                 extend_banks: Optional[Callable] = None,
                 menu_bits=None,
                 bank_format: str = "f32",
                 make_packed_banks: Optional[Callable] = None,
                 dispatch_stats: Optional[Callable] = None,
                 skips_padding: bool = False):
        from repro.core import quantization as Q

        self.layer_names = list(layer_names)
        self.val_subsets = val_subsets
        self.make_qp = make_qp
        self.mesh = mesh
        # (L, |menu|, 3) weight/activation quant_triple tables: the banked
        # pipeline assembles qp stacks by numpy indexing (menu indexing)
        # instead of P x L Python quant_triple calls; rows are bitwise
        # identical, so this is a pure dispatch-overhead cut
        self._qp_tables = qp_tables
        # ``menu_bits``: the target's menu, in the same order its
        # qp_menu_tables/banks are built. NOTE: the banked dispatch
        # recovers bank rows from grid tops via ``Q.menu_index_from_hi``
        # inside the model forwards, which assumes the full
        # ``Q.SUPPORTED_BITS`` menu — targets with a reduced/permuted menu
        # must either keep ``use_banks=False`` or thread their menu
        # through ``menu_index_from_hi`` as well.
        self._menu_code = {b: k for k, b in
                           enumerate(menu_bits or Q.SUPPORTED_BITS)}
        if bank_format not in ("f32", "packed"):
            raise ValueError(f"unknown bank_format {bank_format!r} "
                             "(want 'f32' or 'packed')")
        if use_banks is None:
            use_banks = (make_packed_banks if bank_format == "packed"
                         else make_banks) is not None
        if use_banks and bank_format == "packed" \
                and make_packed_banks is None:
            raise ValueError("bank_format='packed' requires "
                             "make_packed_banks")
        if bank_format == "packed" and not use_banks:
            raise ValueError("bank_format='packed' requires use_banks=True "
                             "(the packed lane IS a bank lane)")
        if use_banks and bank_format == "f32" and make_banks is None:
            raise ValueError("use_banks=True requires make_banks")
        self.use_banks = use_banks
        self.bank_format = bank_format
        self._make_banks = make_banks
        self._make_packed_banks = make_packed_banks
        self._extend_banks = extend_banks
        self._dispatch_stats = dispatch_stats
        self._skips_padding = skips_padding
        # banks keyed by parameter-set identity; the params ref is kept so
        # a collected object's id can never alias a live cache entry
        self._banks: Dict[int, tuple] = {}
        self._n_shards = pop_sharding.pop_axis_size(mesh, pop_axis)
        # equal-shaped subsets additionally fold into the batch axis, so the
        # whole validation sweep is ONE call instead of one per subset
        shapes = {tuple(np.asarray(f).shape) for f, _ in val_subsets}
        self._folded = len(shapes) == 1 and len(val_subsets) > 1
        if self._folded:
            self._feats_all = jnp.concatenate(
                [f for f, _ in val_subsets], axis=0)
            self._labels_all = jnp.concatenate(
                [l for _, l in val_subsets], axis=0)
            self._n_subsets = len(val_subsets)
            self._subset_frames = int(np.asarray(val_subsets[0][1]).size)

        n_sub = len(val_subsets)

        # the per-generation dispatch: bank gather (or requant) -> model
        # population forward -> frame-error reduction to integer counts,
        # one jitted call per (bucket, subset-shape). ``lanes`` is the
        # (qp grid stack, live mask) pair of ``_stack`` (a bare qp stack
        # scores every lane); it is the only input consumed per call, so
        # it is donated where the backend supports aliasing (not CPU).
        def _batch_err(params, banks, feats, labels, lanes):
            qp_stack, live = lanes if isinstance(lanes, tuple) \
                else (lanes, None)

            def count(logits):
                with jax.named_scope("eval.count"):
                    wrong = jnp.argmax(logits, -1) != labels[None]
                    if self._folded:                        # (P,B*,T)
                        p, _, t = wrong.shape
                        return jnp.sum(wrong.reshape(p, n_sub, -1, t),
                                       axis=(2, 3))
                    return jnp.sum(wrong, axis=(1, 2))

            if skips_padding:
                return forward_pop(params, feats, qp_stack, banks, live,
                                   lambda logits: count(logits[None])[0])
            return count(forward_pop(params, feats, qp_stack, banks))

        self._batch_err_fn = _batch_err
        self._pop_axis = pop_axis
        self._partition = partition
        # graceful-degradation knobs: ``faults`` (a
        # ``repro.core.faults.FaultInjector``) injects deterministic
        # failures on the dispatch/result hooks; transient dispatch
        # exceptions are absorbed by a bounded exponential-backoff retry;
        # a simulated device loss rebinds the dispatch to the surviving
        # mesh and re-runs the generation (``fault_log`` records both)
        self.faults = None
        self.max_retries = 3
        self.retry_backoff_s = 0.005
        self.fault_log: List[dict] = []
        self._bind_mesh(mesh)

    def _bind_mesh(self, mesh) -> None:
        """(Re)build the jitted per-generation dispatch for ``mesh`` —
        called once at construction and again after a simulated device
        loss shrinks the mesh. ``_batch_err`` stays the single dispatch
        attribute (the C3/C4 contract checks lower and count it)."""
        self.mesh = mesh
        self._n_shards = pop_sharding.pop_axis_size(mesh, self._pop_axis)
        fn = self._batch_err_fn
        donate = (4,) if jax.default_backend() != "cpu" else ()
        if mesh is None:
            self._batch_err = jax.jit(fn, donate_argnums=donate)
        else:
            sharded = pop_sharding.shard_population(
                fn, mesh, n_replicated=4, axis=self._pop_axis,
                mode=self._partition)
            if self._partition == "gspmd":
                # activate the "pop" logical-axis rule so the constraints
                # inside forward_population bind to this mesh at trace time
                def call(params, banks, feats, labels, lanes,
                         _f=sharded, _m=mesh):
                    with dist_sharding.axis_rules(_m):
                        return _f(params, banks, feats, labels, lanes)
                self._batch_err = call
            else:
                self._batch_err = sharded

    def _banks_for(self, params):
        """Quantized-weight banks for a parameter set, built on first use.
        Keyed by object identity: the GA evaluates thousands of candidates
        against a handful of parameter sets (base + retrained beacons), so
        each set pays one bank build and every later generation gathers.
        With equal-shaped (folded) subsets the ``extend_banks`` hook (when
        wired) additionally specializes the fresh banks against the frozen
        validation fold (the SRU input-layer u-bank)."""
        if not self.use_banks:
            return None
        key = id(params)
        if key not in self._banks:
            if self.bank_format == "packed":
                # packed containers; no extend hook (see class docstring)
                banks = self._make_packed_banks(params)
            else:
                banks = self._make_banks(params)
                if self._folded and self._extend_banks is not None:
                    banks = self._extend_banks(banks, self._feats_all)
            self._banks[key] = (params, banks)
        return self._banks[key][1]

    def _stack(self, allocs: Sequence[Alloc]
               ) -> Tuple[np.ndarray, np.ndarray]:
        """The dispatch's per-lane input: the (B, L, 6) qp grid stack
        padded to the compile bucket B by repeating the last candidate,
        and the (B,) mask ``live`` that is false on the padding lanes."""
        if self.use_banks and self._qp_tables is not None:
            # menu indexing: gather the per-layer triple rows directly
            w_t, a_t = self._qp_tables
            code = self._menu_code
            wc = np.asarray([[code[a[nm][0]] for nm in self.layer_names]
                             for a in allocs])
            ac = np.asarray([[code[a[nm][1]] for nm in self.layer_names]
                             for a in allocs])
            li = np.arange(len(self.layer_names))[None]
            stack = np.concatenate([w_t[li, wc], a_t[li, ac]], -1)
        else:
            qps = [self.make_qp(a) for a in allocs]
            stack = stack_qps(qps, self.layer_names)
        target = pop_sharding.padded_pop(bucket_size(len(allocs)),
                                         self._n_shards)
        pad = target - len(allocs)
        if pad:
            stack = np.concatenate([stack, np.repeat(stack[-1:], pad, 0)])
        return stack, np.arange(target) < len(allocs)

    def _dispatch(self, params, banks, feats, labels, lanes):
        """The single jitted dispatch, with the fault-injection hook in
        front. With ``faults=None`` this is exactly one ``_batch_err``
        call — the C4 one-dispatch-per-generation contract."""
        if self.faults is not None:
            self.faults.on_dispatch(self)
        return self._batch_err(params, banks, feats, labels, lanes)

    def _subset_errors(self, params, banks, feats, labels, lanes, p: int,
                       frames: int) -> np.ndarray:
        """One dispatch and its readback; returns the real lanes' error %
        per subset. Three profiler spans split the host's part: the
        enqueue (``evaluator.dispatch``, with the real, padded and
        computed lane counts), the wait for the device, and the readback
        with the count-to-error% math. The copy to the host is queued with
        the program, as ``jax.device_get`` queues it, so waiting apart from
        the readback costs no second round trip to the device."""
        bucket = int(lanes[0].shape[0])
        computed = p if self._skips_padding else bucket
        stats = {"lanes_computed": computed}
        if self._dispatch_stats is not None:
            traced = (bucket // self._n_shards
                      if self._partition == "shard_map" else bucket)
            stats.update(self._dispatch_stats(traced, computed, banks))
        with TraceAnnotation("evaluator.dispatch", lanes=p, bucket=bucket,
                             **stats):
            out = self._dispatch(params, banks, feats, labels, lanes)
            if isinstance(out, jax.Array):
                out.copy_to_host_async()
        with TraceAnnotation("evaluator.wait"):
            jax.block_until_ready(out)
        with TraceAnnotation("evaluator.readback"):
            wrong = np.asarray(pop_sharding.gather_counts(out))
            return 100.0 * wrong[:p].astype(np.int64) / frames

    def _errors_once(self, allocs: Sequence[Alloc], params) -> np.ndarray:
        """One attempt at scoring a generation; returns the (P,) float
        max-over-subsets error array (real lanes only, padding sliced)."""
        with TraceAnnotation("evaluator.stack"):
            lanes = self._stack(allocs)
        banks = self._banks_for(params)
        p = len(allocs)
        if self._folded:
            errs = np.max(self._subset_errors(
                params, banks, self._feats_all, self._labels_all, lanes, p,
                self._subset_frames), axis=1)                # (P, S) -> (P,)
        else:
            errs = np.max(np.stack([
                self._subset_errors(params, banks, feats, labels, lanes, p,
                                    int(np.asarray(labels).size))
                for feats, labels in self.val_subsets]), axis=0)
        if self.faults is not None:
            errs = self.faults.on_result(self, errs)
        return errs

    def _survive_device_loss(self, keep: int) -> None:
        """Degrade to the surviving mesh: rebind the dispatch to the first
        ``keep`` devices of the population axis. Each loss must strictly
        shrink the mesh (a loss that doesn't is a schedule bug, not a
        recoverable fault). shard_map runs the exact per-shard program, so
        re-padding and re-dispatching on fewer shards keeps every real
        lane's error count bit-identical."""
        if self.mesh is None:
            raise RuntimeError(
                "device loss injected on an unsharded evaluator "
                "(no mesh to shrink)")
        if not 0 < keep < self._n_shards:
            raise RuntimeError(
                f"device loss to {keep} shards does not shrink the "
                f"current {self._n_shards}-shard mesh")
        self.fault_log.append({"event": "device_loss",
                               "from_shards": self._n_shards,
                               "to_shards": keep})
        self._bind_mesh(pop_sharding.shrink_mesh(self.mesh, keep,
                                                 axis=self._pop_axis))

    def errors(self, allocs: Sequence[Alloc], params) -> List[float]:
        """Max-over-subsets error % for each allocation (order-preserving).
        Error counts come back as a host array (gathered across the mesh
        when sharded); padding lanes are sliced off before the max.

        Degradation: transient dispatch failures
        (``faults.TRANSIENT_DISPATCH_ERRORS``) are retried up to
        ``max_retries`` times with exponential backoff; a
        ``DeviceLossError`` re-pads and re-dispatches the whole generation
        on the surviving mesh. Both paths preserve bit parity — a retry
        re-runs the identical program, and shard_map programs are exact
        per shard. The whole call, retries included, is the profiler span
        ``evaluator.errors``."""
        if not allocs:
            return []
        with TraceAnnotation("evaluator.errors"):
            attempt = 0
            while True:
                try:
                    return self._errors_once(allocs, params).tolist()
                except fault_policies.DeviceLossError as loss:
                    self._survive_device_loss(loss.keep)
                except fault_policies.TRANSIENT_DISPATCH_ERRORS as exc:
                    attempt += 1
                    if attempt > self.max_retries:
                        raise
                    delay = self.retry_backoff_s * (2 ** (attempt - 1))
                    self.fault_log.append({
                        "event": "retry", "attempt": attempt,
                        "delay_s": delay,
                        "error": f"{type(exc).__name__}: {exc}"})
                    time.sleep(delay)


class BatchedSRUEvaluator(PopulationEvaluator):
    """SRU binding of the generic ``PopulationEvaluator``: wires
    ``models.sru.forward_population`` (and the input-layer u-bank hook) into
    the shared pipeline. Kept under its historical name — every PR-1..4
    contract (scalar parity, bank parity, mesh parity) is carried by the
    generic base; this class only binds the SRU forward.

    Banks are gathered whenever a bank maker is wired (``use_banks=False``
    keeps the requantize-per-lane lane, the reference the banks are checked
    against); ``use_kernel=True`` streams the recurrence (and, with banks,
    the MxV) through the Pallas population kernels. All are bit-identical
    to the scalar path.
    """

    def __init__(self, cfg, val_subsets, make_qp: Callable[[Alloc], dict],
                 use_kernel: bool = False,
                 mesh=None, partition: str = "shard_map",
                 pop_axis: str = pop_sharding.POP_AXIS,
                 make_banks: Optional[Callable] = None,
                 use_banks: Optional[bool] = None,
                 qp_tables=None,
                 bank_format: str = "f32",
                 make_packed_banks: Optional[Callable] = None):
        from repro.models import sru

        self.cfg = cfg

        def forward_pop(params, feats, qp_stack, banks):
            return sru.forward_population(params, cfg, feats, qp_stack,
                                          use_kernel=use_kernel, banks=banks)

        extend = None
        if qp_tables is not None and cfg.input_dim != cfg.hidden:
            def extend(banks, feats):
                return sru.extend_banks_u0(banks, cfg, feats,
                                           qp_tables[1][0])

        super().__init__(list(cfg.layer_names()), val_subsets, make_qp,
                         forward_pop, mesh=mesh, partition=partition,
                         pop_axis=pop_axis, make_banks=make_banks,
                         use_banks=use_banks, qp_tables=qp_tables,
                         extend_banks=extend, bank_format=bank_format,
                         make_packed_banks=make_packed_banks)
