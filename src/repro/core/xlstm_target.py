"""xLSTM ``SearchTarget`` — the second architecture behind the MOHAQ API.

Proves ``repro.core.api.SearchTarget`` end to end on a model the original
search stack could not reach: the registry xLSTM LM (``models/registry.py``
family "ssm": alternating mLSTM/sLSTM block pairs) searched for per-layer
(w_bits, a_bits) allocations through the *same* engine — NSGA-II,
``MOHAQProblem``, the generic ``PopulationEvaluator`` (compile buckets,
subset folding, quantized-weight banks, optional population-axis mesh
sharding) — with zero SRU code involved.

Quantization scheme (block granularity, mirroring the paper's §4.1
boundary): each searchable "layer" is one block's matmul weight set —

  ``m{g}``  mLSTM pair member g:  wq, wk, wv, wz, wo
  ``s{g}``  sLSTM pair member g:  wx, r (recurrent kernel), wo
  ``head``  the LM head projection

sharing one weight grid (MMSE clip per bit-width, pooled over the block's
matrices — the Bi-SRU pools fwd/bwd the same way) and one activation grid
calibrated at the block input (median of per-batch max-abs). Gate weights
(wi/wf/fbias/bias), norms and the embedding table are not searched; they
are counted as always-16-bit ``vector_weights`` for the memory/energy
objectives, like the SRU's recurrent vectors.

Per-layer quantized-weight banks: every quantizable leaf gets a
``(|menu|, *leaf.shape)`` stack built by the identical jitted
``fake_quant_triple`` expression (``Q.build_weight_bank``); the population
forward gathers each lane's row by menu index (recovered from the qp grid
tops via ``menu_index_from_hi``) instead of requantizing per lane — the
same gather-don't-requantize contract the SRU banks established (PR 4).

Error metric: next-token top-1 error % on a bigram-structured synthetic LM
task, MAX over 4 validation subsets (the paper's §4.2 ranking trick),
exactly the convention the SRU target uses — so hardware feasibility
margins behave identically.

Determinism: every stochastic site is an explicit jax PRNG key or seeded
synthetic-data stream; nothing touches ``np.random`` global state
(ROADMAP invariant; asserted by tests/test_api.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import batched_eval
from repro.core import quantization as Q
from repro.data import synthetic
from repro.models import common as cm
from repro.models import registry
from repro.models import transformer as tfm
from repro.models import xlstm
from repro.training import optimizer as opt

Alloc = Dict[str, Tuple[int, int]]

# quantizable matmul leaves per block kind (see module docstring)
QUANT_LEAVES = {"m": ("wq", "wk", "wv", "wz", "wo"),
                "s": ("wx", "r", "wo")}


def search_config() -> ArchConfig:
    """CPU-searchable miniature of the registry xlstm-350m: 2 (mLSTM,
    sLSTM) pairs -> 5 searchable layers, a 10-gene untied genome."""
    return dataclasses.replace(
        get_config("xlstm-350m").reduced(),
        name="xlstm_search", n_layers=4, d_model=64, n_heads=4,
        vocab_size=64)


def quant_layer_names(cfg: ArchConfig) -> Tuple[str, ...]:
    names: List[str] = []
    for g in range(cfg.n_layers // 2):
        names += [f"m{g}", f"s{g}"]
    return tuple(names + ["head"])


def _layer_leaves(params, cfg: ArchConfig, name: str) -> Dict[str, jnp.ndarray]:
    """The full-precision quantizable leaves of one searchable layer."""
    if name == "head":
        return {"lm_head": params["lm_head"]}
    g = int(name[1:])
    kind = "mlstm" if name[0] == "m" else "slstm"
    sub = jax.tree.map(lambda a, _g=g: a[_g], params["pairs"][kind])
    return {k: sub[k] for k in QUANT_LEAVES[name[0]]}


def forward(params, cfg: ArchConfig, tokens, get_w, q_act):
    """The block-pair forward with quantization hooks. ``get_w(name)`` ->
    replacement dict for the layer's quantizable leaves; ``q_act(name, x)``
    -> the (possibly fake-quantized) block-input activation. The group loop
    is unrolled in Python (G is tiny for search configs) so per-layer grids
    need no scan threading. Returns f32 logits (B, T, V)."""
    x = tfm.embed_tokens(params, cfg, tokens)
    for g in range(cfg.n_layers // 2):
        bp = jax.tree.map(lambda a, _g=g: a[_g], params["pairs"])
        m, s = f"m{g}", f"s{g}"
        xin = q_act(m, cm.rms_norm(x, bp["norm_m"], cfg.norm_eps))
        x = x + xlstm.mlstm_fwd({**bp["mlstm"], **get_w(m)}, cfg, xin)
        xin = q_act(s, cm.rms_norm(x, bp["norm_s"], cfg.norm_eps))
        x = x + xlstm.slstm_fwd({**bp["slstm"], **get_w(s)}, cfg, xin)
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    xq = q_act("head", x)
    return jnp.dot(xq, get_w("head")["lm_head"],
                   preferred_element_type=jnp.float32)


def forward_plain(params, cfg: ArchConfig, tokens):
    """Full-precision forward (identity hooks) — the baseline path."""
    return forward(params, cfg, tokens,
                   lambda name: _layer_leaves(params, cfg, name),
                   lambda name, x: x)


def forward_population(params, cfg: ArchConfig, tokens, qp_stack,
                       banks=None):
    """Score P quantization candidates in one call: vmap of the hooked
    forward over the (P, L, 6) qp grid stack (params/tokens broadcast).
    With ``banks`` each lane's quantized leaves are *gathered* by menu
    index — rows are built by the identical jitted ``fake_quant_triple``
    expression, so the gather lane matches the requant lane exactly."""
    names = quant_layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}

    def one(row):                                   # (L, 6) per lane
        def q_act(name, x):
            r = row[li[name]]
            return Q.fake_quant_triple(x, r[3], r[4], r[5])

        if banks is None:
            def get_w(name):
                # pure grid values (use_ste=False) — matches the bank rows
                r = row[li[name]]
                leaves = _layer_leaves(params, cfg, name)
                return {k: Q.fake_quant_triple(w, r[0], r[1], r[2],
                                               use_ste=False)
                        for k, w in leaves.items()}
        else:
            def get_w(name):
                idx = Q.menu_index_from_hi(row[li[name], 2])
                return {k: jnp.take(b, idx, axis=0)
                        for k, b in banks[name].items()}

        return forward(params, cfg, tokens, get_w, q_act)

    return jax.vmap(one)(qp_stack)


def calibrate(params, cfg: ArchConfig, token_batches) -> Dict[str, float]:
    """Expected block-input activation ranges = median of per-batch
    max-abs (the paper's calibration recipe)."""
    cal = Q.ActRangeCalibrator()

    def q_act(name, x):
        cal.observe(name, x)
        return x

    for toks in token_batches:
        forward(params, cfg, toks,
                lambda name: _layer_leaves(params, cfg, name), q_act)
    return cal.expected_ranges()


def weight_grids(params, cfg: ArchConfig):
    """(wclips, wranges): per-(layer, bits) MMSE clips pooled over the
    block's matrices, and per-layer abs-max ranges for the 16-bit rows."""
    wclips: Dict[Tuple[str, int], float] = {}
    wranges: Dict[str, float] = {}
    for name in quant_layer_names(cfg):
        leaves = _layer_leaves(params, cfg, name)
        flat = np.concatenate([np.asarray(v, np.float32).ravel()
                               for v in leaves.values()])
        wranges[name] = float(np.abs(flat).max())
        for bits in (2, 4, 8):
            wclips[(name, bits)] = Q.mmse_clip(flat, bits)
    return wclips, wranges


@dataclass
class XLSTMTarget:
    """``SearchTarget`` over a trained + calibrated registry xLSTM."""
    cfg: ArchConfig
    params: dict
    val_subsets: list               # 4 x (tokens, next-token labels)
    test_batches: list
    act_ranges: Dict[str, float]
    wclips: Dict[Tuple[str, int], float]
    wranges: Dict[str, float]
    baseline_val_error: float = 0.0
    baseline_test_error: float = 0.0

    supports_retrain = True            # SearchTarget: beacons available

    def __post_init__(self):
        self.shared_error_memo: Dict[tuple, float] = {}
        self._evaluators: Dict[tuple, batched_eval.PopulationEvaluator] = {}
        self._qp_tables = None
        cfg = self.cfg
        self._plain = jax.jit(lambda p, t: forward_plain(p, cfg, t))
        self._pop = jax.jit(
            lambda p, t, stack: forward_population(p, cfg, t, stack))

    # ---- search-space description ----

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return quant_layer_names(self.cfg)

    @property
    def menu(self) -> Tuple[int, ...]:
        return Q.SUPPORTED_BITS

    # ---- hardware-objective inputs ----

    @property
    def layer_weights(self) -> Dict[str, int]:
        return {name: sum(int(np.prod(v.shape)) for v in
                          _layer_leaves(self.params, self.cfg, name).values())
                for name in self.layer_names}

    @property
    def layer_macs(self) -> Dict[str, int]:
        """Per-token MACs == matmul weights per layer (each matrix weight
        multiplies once per token, recurrent kernels once per step — the
        same weights==MACs identity the SRU layers have)."""
        return self.layer_weights

    @property
    def vector_weights(self) -> int:
        """Everything outside the searchable matrices (embedding, norms,
        gate weights, biases) — stored at 16 bits, never searched."""
        total = sum(int(np.prod(np.shape(leaf)))
                    for leaf in jax.tree.leaves(self.params))
        return total - sum(self.layer_weights.values())

    @property
    def fixed_ops(self) -> int:
        """Max-precision op estimate per token (gating exponentials,
        norms, the mLSTM attention products — activation x activation, so
        never searchable): ~32 ops per inner-dim element per block. Only
        shifts the Eq. 4 speedup normalization."""
        return 32 * self.cfg.ssm_d_inner * self.cfg.n_layers

    # ---- beacon retraining ----

    def beacon_retrainer(self, retrain_steps: int = 60, *,
                         skip_retrains: int = 0):
        """One retraining context per search (the SRU target's contract,
        verbatim): the returned ``retrain_fn(alloc, base_params)`` draws
        successive batches from a single seeded token stream, so the k-th
        retrain of any search sees identical data regardless of which
        alloc triggered it. ``skip_retrains`` fast-forwards the stream
        past the first N retrains (each consumes exactly ``retrain_steps``
        batches) so checkpoint-resumed searches stay bit-deterministic."""
        from repro.training import qat
        data = synthetic.lm_batches(
            self.cfg.vocab_size, 8, 33, seed=3,
            start_step=skip_retrains * retrain_steps, n_noise=N_NOISE)

        def retrain_fn(alloc: Alloc, base_params):
            wclips = {n: self.wclips[(n, a[0])]
                      for n, a in alloc.items() if a[0] != 16}
            return qat.retrain_xlstm(base_params, self.cfg, alloc, data,
                                     steps=retrain_steps,
                                     act_ranges=self.act_ranges,
                                     wclips=wclips)
        return retrain_fn

    def retrain(self, alloc: Alloc, base_params=None, *, steps: int = 60):
        """One-off binary-connect retrain under ``alloc`` (fresh stream)."""
        base = self.params if base_params is None else base_params
        return self.beacon_retrainer(steps)(alloc, base)

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
                           for k, w in
                           _layer_leaves(params, self.cfg, name).items()}
        return banks

    # ---- error evaluation ----

    def batched_evaluator(self, mesh=None, partition: str = "shard_map",
                          use_banks: Optional[bool] = None
                          ) -> batched_eval.PopulationEvaluator:
        key = (mesh, partition if mesh is not None else "", use_banks)
        if key not in self._evaluators:
            cfg = self.cfg

            def forward_pop(params, feats, qp_stack, banks):
                return forward_population(params, cfg, feats, qp_stack,
                                          banks=banks)

            self._evaluators[key] = batched_eval.PopulationEvaluator(
                self.layer_names, self.val_subsets, self.qp_for,
                forward_pop, mesh=mesh, partition=partition,
                make_banks=self.make_banks, use_banks=use_banks,
                qp_tables=self.qp_menu_tables(), menu_bits=self.menu)
        return self._evaluators[key]

    def val_error_batch(self, allocs, params=None, *, mesh=None,
                        partition: str = "shard_map",
                        use_banks: Optional[bool] = None,
                        bank_format: str = "f32") -> List[float]:
        """Max-over-subsets next-token error % for every allocation in one
        dispatch (generic evaluator: buckets, folding, banks, mesh). Only
        f32 banks exist for this target; another ``bank_format`` raises."""
        if bank_format != "f32":
            raise ValueError(f"the xLSTM target has f32 banks only, not "
                             f"bank_format={bank_format!r}")
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


# ------------------------------------------------------------- training

# the task's noise fan-out: 2 equiprobable continuations -> a 50% top-1
# error floor, leaving a wide range for quantization to degrade across
# (the default bigram noise of 7 floors at ~86% and compresses the search)
N_NOISE = 2


def _eval_sets(cfg: ArchConfig, batch: int = 2, seq: int = 16,
               n_val: int = 4, n_test: int = 2):
    """Fixed validation subsets / test batches: (tokens[:-1], tokens[1:])
    next-token pairs from the seeded bigram stream (no ignore positions,
    so error counts are exact integers over every frame)."""
    def mk(seed, step):
        toks = synthetic.lm_batch(cfg.vocab_size, batch, seq + 1,
                                  seed=seed, step=step,
                                  n_noise=N_NOISE)["tokens"]
        return toks[:, :-1], toks[:, 1:]
    val = [mk(77, i) for i in range(n_val)]
    test = [mk(88, 1000 + i) for i in range(n_test)]
    return val, test


def train_small_xlstm(steps: int = 120, *, cfg: Optional[ArchConfig] = None,
                      batch: int = 8, seq: int = 32, lr: float = 1e-2,
                      seed: int = 0, verbose: bool = False) -> XLSTMTarget:
    """Train the miniature registry xLSTM on the synthetic bigram LM task,
    calibrate, and wrap it as a ``SearchTarget``. All randomness flows
    through explicit seeds (jax PRNG + the deterministic data streams)."""
    cfg = cfg or search_config()
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    ocfg = opt.AdamWConfig(lr=lr, schedule="cosine", warmup_steps=10,
                           total_steps=steps, weight_decay=0.0)
    ostate = opt.init_opt_state(params)

    @jax.jit
    def step_fn(p, o, b):
        loss, g = jax.value_and_grad(model.loss)(p, b)
        p2, o2, _ = opt.adamw_update(ocfg, p, g, o)
        return p2, o2, loss

    data = synthetic.lm_batches(cfg.vocab_size, batch, seq, seed=11,
                                n_noise=N_NOISE)
    for i in range(steps):
        b = next(data)
        params, ostate, loss = step_fn(params, ostate, b)
        if verbose and (i + 1) % 40 == 0:
            print(f"  [xlstm-train] step {i+1}/{steps} "
                  f"loss {float(loss):.3f}")

    val, test = _eval_sets(cfg)
    # calibrate on the validation token batches ONLY (the paper calibrates
    # on ~70 validation sequences; test data never touches the grids)
    act_ranges = calibrate(params, cfg, [t for t, _ in val])
    wclips, wranges = weight_grids(params, cfg)
    target = XLSTMTarget(cfg, params, val, test, act_ranges, wclips,
                         wranges)
    target.baseline_val_error = target.val_error()
    target.baseline_test_error = target.test_error()
    return target


def xlstm_contract_harness():
    """Tiny-but-real xLSTM instance for the jaxpr contract checker (see
    ``repro.core.target_registry``). The reduced registry config shrunk to
    two blocks / d_model 16 keeps every model dimension off the checker's
    activation marker dim (T=3), so marker-carrying ``round`` ops are
    activation fake-quants and any non-marker round is a weight requantize
    the banked lane must not contain."""
    from repro.core.target_registry import ContractHarness, MARKER_DIM

    cfg = dataclasses.replace(get_config("xlstm-350m").reduced(),
                              name="xlstm_contract", n_layers=2,
                              d_model=16, n_heads=2, vocab_size=32)
    model = registry.get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, T = 2, MARKER_DIM
    toks = jnp.asarray((np.arange(B * T).reshape(B, T)
                        % cfg.vocab_size).astype(np.int32))
    labels = toks
    names = quant_layer_names(cfg)
    act_ranges = {n: 1.0 for n in names}
    wclips = {(n, b): 0.5 for n in names for b in (2, 4, 8)}
    wranges = {n: 1.0 for n in names}
    target = XLSTMTarget(cfg, params, [(toks, labels)] * 4,
                         [(toks, labels)], act_ranges, wclips, wranges)

    def forward_pop(params, feats, qp_stack, banks=None):
        return forward_population(params, cfg, feats, qp_stack,
                                  banks=banks)

    return ContractHarness(
        name="xlstm", target=target, feats=toks, labels=labels,
        layer_names=names, marker_dim=T,
        anchor_path="src/repro/core/xlstm_target.py",
        forward_pop=forward_pop,
        make_evaluator=lambda: target.batched_evaluator(use_banks=True),
        # no serving decode step yet: C5 still proves lane independence of
        # the banked forward_population; forward_decode joins when the
        # serving tier grows an xLSTM lane
        forward_decode=None)
