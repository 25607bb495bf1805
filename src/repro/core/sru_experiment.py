"""The paper's three experiments, end to end, on the synthetic speech task.

Scales: the *paper-exact* SRU-TIMIT config (Table 4) is used for all analytic
numbers (sizes, speedups, energies — reproduced exactly, see benchmarks/);
the *search* experiments run on a width-reduced SRU speech model trained on
the synthetic task, because TIMIT/Kaldi are unavailable offline and the
container is CPU-only. The search mechanics (NSGA-II settings, feasibility
areas, beacon logic, validation-subset max-error trick) follow the paper.
"""
from __future__ import annotations

import dataclasses
import warnings
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.core import batched_eval
from repro.core import quantization as Q
from repro.core.beacon import BeaconSearch
from repro.core.hardware import BITFUSION, SILAGO, HardwareModel
from repro.core.mohaq import Alloc, MOHAQProblem, MOHAQResult, run_search
from repro.data import synthetic
from repro.models import sru
from repro.models.sru import LAYER_NAMES, SRUModelConfig
from repro.training import optimizer as opt
from repro.training import qat


SEARCH_CFG = SRUModelConfig(name="sru_search", input_dim=23, hidden=96,
                            proj=48, n_sru_layers=4, n_outputs=64)
PAPER_CFG = SRUModelConfig()   # exact Table 4 model
FIXED_OPS_PAPER = 88000 + 10704   # element-wise + nonlinear (Table 4)


@dataclass
class TrainedSRU:
    """The paper's trained + calibrated Bi-SRU — and the first
    ``repro.core.api.SearchTarget`` implementation: everything the
    protocol names (layer geometry, hardware-objective counts, batched
    error evaluation, qp/menu/bank plumbing, beacon retraining) is served
    directly off this object, so ``SearchSession(trained, platform,
    objectives)`` runs the paper's experiments without the historical
    SRU-specific wiring."""
    cfg: SRUModelConfig
    params: dict
    task: synthetic.SpeechTask
    val_subsets: list          # 4 stacked batches (feats, labels)
    test_batches: list
    act_ranges: Dict[str, float]
    wclips: Dict[Tuple[str, int], float]
    wranges: Dict[str, float]
    baseline_val_error: float
    baseline_test_error: float

    supports_retrain = True            # SearchTarget: beacons available

    def __post_init__(self):
        cfg = self.cfg

        @jax.jit
        def _err(params, feats, labels, qp):
            logits = sru.forward(params, cfg, feats, qp=qp)
            return jnp.sum(jnp.argmax(logits, -1) != labels), labels.size

        @jax.jit
        def _err_plain(params, feats, labels):
            logits = sru.forward(params, cfg, feats)
            return jnp.sum(jnp.argmax(logits, -1) != labels), labels.size

        self._err = _err
        self._err_plain = _err_plain
        self._batched_eval = {}
        # shared across every base-params search built from this model
        # (multi-platform sweeps re-hit the same allocations for free);
        # beacon searches attach their own memo — see BeaconSearch.attach
        self.shared_error_memo: Dict[tuple, float] = {}

    # ---- SearchTarget: search-space / hardware-objective surface ----

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return tuple(self.cfg.layer_names())

    @property
    def menu(self) -> Tuple[int, ...]:
        return Q.SUPPORTED_BITS

    @property
    def layer_macs(self) -> Dict[str, int]:
        """MxV MACs per frame == matrix weights per layer (paper Table 4)."""
        return self.cfg.layer_weight_counts()

    @property
    def layer_weights(self) -> Dict[str, int]:
        return self.cfg.layer_weight_counts()

    @property
    def vector_weights(self) -> int:
        return self.cfg.vector_weight_count()

    @property
    def fixed_ops(self) -> int:
        """Element-wise + sigmoid op count per frame (runs at max precision;
        folded into the speedup normalization, Eq. 4)."""
        return 14 * self.cfg.hidden * 2 * self.cfg.n_sru_layers * 2

    def beacon_retrainer(self, retrain_steps: int = 60, *,
                         skip_retrains: int = 0):
        """One retraining context per search: the returned
        ``retrain_fn(alloc, base_params)`` draws successive batches from a
        single seeded stream, so the k-th retrain of any search sees the
        identical data regardless of which alloc triggered it — the exact
        historical experiment-3 wiring. ``skip_retrains`` fast-forwards
        the stream past the first N retrains (each consumes exactly
        ``retrain_steps`` batches), so a checkpoint-resumed search's next
        retrain sees the identical batches the uninterrupted run would."""
        data = synthetic.speech_batches(
            self.task, 8, 48, seed=3,
            start_step=skip_retrains * retrain_steps)

        def retrain_fn(alloc: Alloc, base_params):
            wclips = {n: self.wclips[(n, a[0])]
                      for n, a in alloc.items() if a[0] != 16}
            return qat.retrain_sru(base_params, self.cfg, alloc, data,
                                   steps=retrain_steps,
                                   act_ranges=self.act_ranges,
                                   wclips=wclips)
        return retrain_fn

    def retrain(self, alloc: Alloc, base_params=None, *, steps: int = 60):
        """One-off binary-connect retrain under ``alloc`` (fresh stream)."""
        base = self.params if base_params is None else base_params
        return self.beacon_retrainer(steps)(alloc, base)

    # ---- SearchTarget: quantization-grid plumbing ----

    def qp_for(self, alloc: Alloc):
        return sru.quant_triples_for(alloc, self.wclips, self.act_ranges,
                                     self.wranges)

    def make_banks(self, params):
        """Quantized-weight banks for ``params`` against this model's
        frozen post-calibration grids (MMSE clips / weight ranges). The
        batched evaluator calls this once per distinct parameter set (base
        model, each retrained beacon) and caches the result."""
        return sru.build_weight_banks(params, self.cfg, self.wclips,
                                      self.wranges)

    def make_packed_banks(self, params):
        """Packed-integer banks (int codes + scales) for ``params`` — same
        grids as ``make_banks``, >= 4x smaller, dequantizes to the f32 bank
        rows bitwise. Selected via ``bank_format='packed'`` on the batched
        evaluator; also what ``tools/convert_checkpoint.py`` ships."""
        return sru.build_weight_banks(params, self.cfg, self.wclips,
                                      self.wranges, packed=True)

    def qp_menu_tables(self):
        """Per-layer menu-indexed quantization-grid tables: two
        (L, |menu|, 3) float32 arrays of weight / activation
        ``quant_triple`` rows in ``Q.SUPPORTED_BITS`` order. Built once per
        trained model; the banked evaluator assembles each generation's
        (P, L, 6) qp stack by pure numpy indexing into these tables
        (bitwise-identical rows to per-candidate ``quant_triples_for``, at
        a fraction of the per-generation Python cost) and reads L0's
        activation row for the input-layer u-bank."""
        if getattr(self, "_qp_tables", None) is None:
            names = list(self.cfg.layer_names())
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

    def batched_evaluator(self, mesh=None,
                          partition: str = "shard_map",
                          use_banks: Optional[bool] = None,
                          bank_format: str = "f32"
                          ) -> batched_eval.BatchedSRUEvaluator:
        """Lazily-built population evaluator (one jitted call scores a
        whole GA generation; compiled per population-size bucket).
        ``use_banks`` controls the quantized-weight-bank gather (default:
        on; ``use_banks=False`` keeps the requantize-per-lane lane, the
        reference the banks are checked against). ``bank_format='packed'``
        gathers from packed-integer banks instead of f32 stacks
        (bit-identical errors, >= 4x less bank memory). ``mesh`` shards the
        population axis across its "pop" device axis (``partition`` picks
        the shard_map or GSPMD lowering, see distributed.pop_sharding)."""
        # Mesh hashes by devices + axis names, so equivalent meshes built
        # fresh per call share one compiled evaluator
        if use_banks is None:
            use_banks = True
        key = (use_banks, bank_format, mesh,
               partition if mesh is not None else "")
        if key not in self._batched_eval:
            self._batched_eval[key] = batched_eval.BatchedSRUEvaluator(
                self.cfg, self.val_subsets, self.qp_for,
                mesh=mesh, partition=partition,
                make_banks=self.make_banks, use_banks=use_banks,
                qp_tables=self.qp_menu_tables(), bank_format=bank_format,
                make_packed_banks=self.make_packed_banks)
        return self._batched_eval[key]

    def val_error_batch(self, allocs, params=None, *,
                        mesh=None, partition: str = "shard_map",
                        use_banks: Optional[bool] = None,
                        bank_format: str = "f32"):
        """Batched counterpart of ``val_error``: max error over the 4
        validation subsets for EVERY allocation in one call. Matches the
        scalar path exactly (integer error counts). ``params`` selects the
        full-precision parameter set (base or a retrained beacon's);
        ``use_banks`` picks bank-gather (the default — bitwise identical,
        one bank build per parameter set) vs requantize weight prep;
        ``mesh`` partitions the candidates across devices."""
        params = self.params if params is None else params
        return self.batched_evaluator(mesh=mesh, partition=partition,
                                      use_banks=use_banks,
                                      bank_format=bank_format
                                      ).errors(allocs, params)

    def val_error(self, alloc: Optional[Alloc] = None,
                  params=None) -> float:
        """MAX error over the 4 validation subsets (paper §4.2)."""
        params = self.params if params is None else params
        errs = []
        for feats, labels in self.val_subsets:
            if alloc is None:
                e, n = self._err_plain(params, feats, labels)
            else:
                e, n = self._err(params, feats, labels, self.qp_for(alloc))
            errs.append(100.0 * int(e) / int(n))
        return max(errs)

    def test_error(self, alloc: Optional[Alloc] = None,
                   params=None) -> float:
        params = self.params if params is None else params
        te = tn = 0
        for feats, labels in self.test_batches:
            if alloc is None:
                e, n = self._err_plain(params, feats, labels)
            else:
                e, n = self._err(params, feats, labels, self.qp_for(alloc))
            te += int(e); tn += int(n)
        return 100.0 * te / tn


def train_small_sru(steps: int = 400, *, cfg: SRUModelConfig = SEARCH_CFG,
                    batch: int = 8, seq: int = 48, lr: float = 3e-3,
                    verbose: bool = False) -> TrainedSRU:
    task = synthetic.SpeechTask(input_dim=cfg.input_dim,
                                n_states=cfg.n_outputs)
    params = sru.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=lr, schedule="cosine", warmup_steps=20,
                           total_steps=steps, weight_decay=0.0)
    ostate = opt.init_opt_state(params)

    def loss_fn(p, feats, labels):
        logits = sru.forward(p, cfg, feats)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    @jax.jit
    def step_fn(p, o, feats, labels):
        loss, g = jax.value_and_grad(loss_fn)(p, feats, labels)
        p2, o2, _ = opt.adamw_update(ocfg, p, g, o)
        return p2, o2, loss

    data = synthetic.speech_batches(task, batch, seq)
    for i in range(steps):
        b = next(data)
        params, ostate, loss = step_fn(params, ostate, b["feats"], b["labels"])
        if verbose and (i + 1) % 50 == 0:
            print(f"  [sru-train] step {i+1}/{steps} loss {float(loss):.3f}")

    raw_subsets, raw_test = synthetic.speech_eval_sets(task, batch=4, seq=48)
    stack = lambda bs: (jnp.concatenate([b["feats"] for b in bs]),
                        jnp.concatenate([b["labels"] for b in bs]))
    subsets = [stack(s) for s in raw_subsets]
    test = [stack(raw_test)]
    # activation calibration (paper: ~70 validation sequences)
    cal_feats = [b["feats"] for s in raw_subsets for b in s]
    act_ranges = sru.calibrate(params, cfg, cal_feats)
    wclips = {}
    for bits in (2, 4, 8):
        for name, c in sru.weight_clips(
                params, cfg, {n: bits for n in cfg.layer_names()}).items():
            wclips[(name, bits)] = c
    wranges = sru.weight_ranges(params, cfg)
    trained = TrainedSRU(cfg, params, task, subsets, test, act_ranges,
                         wclips, wranges, 0.0, 0.0)
    trained.baseline_val_error = trained.val_error()
    trained.baseline_test_error = trained.test_error()
    return trained




def _deprecated(old: str, new: str) -> None:
    warnings.warn(f"{old} is deprecated; use {new} (repro.core.api)",
                  DeprecationWarning, stacklevel=3)


def build_problem(trained: TrainedSRU, hardware: HardwareModel,
                  objectives, *, use_search_cfg_sizes: bool = True,
                  sram_override: Optional[int] = None,
                  batched: bool = True, mesh=None,
                  partition: str = "shard_map") -> MOHAQProblem:
    """Deprecated shim over ``api.build_problem_from_target`` (exact
    delegation — same problem wiring, same shared error memo). ``mesh``
    (a 1-D "pop" device mesh) shards every population-level error
    evaluation across devices; scalar fallbacks and the bit-identical
    Pareto-front contract are unchanged."""
    _deprecated("build_problem", "SearchSession(target, platform, "
                "objectives).build_problem()")
    return api.build_problem_from_target(
        trained, hardware, objectives, sram_override=sram_override,
        batched=batched, mesh=mesh, partition=partition)


# ------------------------------------------------------------- experiments
#
# The paper's three experiments are now thin deprecation shims over
# ``api.SearchSession`` — each keeps its historical signature, SRAM
# scaling and return type, and delegates the search itself.

def experiment1_memory(trained: TrainedSRU, *, generations=15, pop=10,
                       initial=24, seed=0, log=None,
                       batched: bool = True, mesh=None,
                       partition: str = "shard_map") -> MOHAQResult:
    """Paper §5.2: minimize (WER, memory); no hardware platform.
    Deprecated shim: ``SearchSession(trained, "mem-only",
    ("error", "memory")).run(...)``."""
    _deprecated("experiment1_memory",
                'SearchSession(target, "mem-only", ("error", "memory"))')
    sess = api.SearchSession(trained, "mem-only", ("error", "memory"),
                             batched=batched, mesh=mesh, partition=partition)
    return sess.run(generations=generations, pop=pop, initial=initial,
                    seed=seed, log=log).result


def experiment2_silago(trained: TrainedSRU, *, generations=15, pop=10,
                       initial=24, seed=0, log=None,
                       batched: bool = True, mesh=None,
                       partition: str = "shard_map") -> MOHAQResult:
    """Paper §5.3: SiLago, 3 objectives (WER, speedup, energy), 6MB-equiv
    SRAM constraint (scaled to the search model: 3.5x compression bound).
    Deprecated shim over ``SearchSession``."""
    _deprecated("experiment2_silago",
                'SearchSession(target, "silago", ..., sram_override=...)')
    total = sum(trained.layer_weights.values()) + trained.vector_weights
    sram = int(total * 32 / 8 / 3.5)
    sess = api.SearchSession(trained, "silago",
                             ("error", "speedup", "energy"),
                             sram_override=sram, batched=batched, mesh=mesh,
                             partition=partition)
    return sess.run(generations=generations, pop=pop, initial=initial,
                    seed=seed, log=log).result


def experiment3_bitfusion(trained: TrainedSRU, *, generations=15, pop=10,
                          initial=24, seed=0, beacon: bool = False,
                          retrain_steps: int = 60, log=None,
                          batched: bool = True, mesh=None,
                          partition: str = "shard_map"):
    """Paper §5.4: Bitfusion, (WER, speedup), small-SRAM constraint,
    inference-only then beacon-based. The paper's 10.6x bound is scaled to
    this model's weight mix: the 16-bit vectors are 2.2% of the search model
    (vs 0.3% of the paper model), so the equivalent "high compression"
    scenario allows ~3.2-bit average matrices + 16-bit vectors.
    Deprecated shim over ``SearchSession(..., beacons=...)``."""
    _deprecated("experiment3_bitfusion",
                'SearchSession(target, "bitfusion", ...).run(beacons=True)')
    mat = sum(trained.layer_weights.values())
    vec = trained.vector_weights
    sram = int((mat * 3.5 + vec * 16) / 8)
    sess = api.SearchSession(trained, "bitfusion", ("error", "speedup"),
                             sram_override=sram, batched=batched, mesh=mesh,
                             partition=partition)
    sr = sess.run(generations=generations, pop=pop, initial=initial,
                  seed=seed, log=log, beacons=beacon,
                  retrain_steps=retrain_steps)
    return sr.result, sr.beacon_search


def result_table(res: MOHAQResult, trained: TrainedSRU,
                 with_test: bool = True) -> List[dict]:
    return api.result_table(res, trained, with_test=with_test)


def format_rows(rows: List[dict], layer_names=None) -> str:
    """Layer names now come from the rows' allocations (i.e. from the
    target that produced them) instead of the hard-coded SRU
    ``LAYER_NAMES`` — tables render correctly for any architecture."""
    return api.format_rows(rows, layer_names=layer_names)


def sru_contract_harness():
    """Tiny-but-real SRU instance for the jaxpr contract checker (see
    ``repro.core.target_registry``). Every dimension is chosen to avoid the
    checker's activation marker dim (T=3): hidden=6 (bi-state 12), proj=4,
    input 5, outputs 7, two layers — so a ``round`` op whose shapes carry a
    3 can only be an activation fake-quant, and one that doesn't is a
    weight (re)quantization the banked lane must not contain."""
    from repro.core.target_registry import ContractHarness, MARKER_DIM

    cfg = SRUModelConfig(name="sru_contract", input_dim=5, hidden=6,
                         proj=4, n_sru_layers=2, n_outputs=7)
    params = sru.init_params(jax.random.PRNGKey(0), cfg)
    B, T = 2, MARKER_DIM
    feats = jnp.asarray(np.linspace(-1.0, 1.0, B * T * cfg.input_dim,
                                    dtype=np.float32
                                    ).reshape(B, T, cfg.input_dim))
    labels = jnp.zeros((B, T), jnp.int32)
    names = list(cfg.layer_names())
    act_ranges = {n: 1.0 for n in names}
    wclips = {(n, b): 0.5 for n in names for b in (2, 4, 8)}
    wranges = {n: 1.0 for n in names}
    trained = TrainedSRU(cfg, params, None, [(feats, labels)] * 4,
                         [(feats, labels)], act_ranges, wclips, wranges,
                         0.0, 0.0)

    def forward_pop(params, feats, qp_stack, banks=None):
        return sru.forward_population(params, cfg, feats, qp_stack,
                                      banks=banks)

    def forward_decode(params, feats_lane, qp_stack, banks=None):
        # the serving hot path: feats_lane (P, T, m), one request chunk per
        # population lane — C5 proves no op mixes the lanes
        return sru.forward_decode_step(params, cfg, feats_lane, qp_stack,
                                       banks=banks)

    return ContractHarness(
        name="sru", target=trained, feats=feats, labels=labels,
        layer_names=tuple(names), marker_dim=T,
        anchor_path="src/repro/models/sru.py", forward_pop=forward_pop,
        make_evaluator=lambda: trained.batched_evaluator(use_banks=True),
        forward_decode=forward_decode)
