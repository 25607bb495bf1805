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

with the grids, banks, evaluator binding and error metric every LM target
shares (``core/lm_target.py``). Gate weights (wi/wf/fbias/bias), norms and
the embedding table are not searched; they are counted as always-16-bit
``vector_weights`` for the memory/energy objectives, like the SRU's
recurrent vectors. The sLSTM recurrent kernel ``r`` is the one leaf not
gathered per lane once a dispatch traces more lanes than the bank has rows
(P > 4): each scan step then contracts all lanes' states against the
bank's rows and keeps each lane's own (``slstm_menu_engaged``,
``_menu_product``).

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
from repro.core import lm_target as LM
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


def forward(params, cfg: ArchConfig, tokens, get_w, q_act, get_rec=None):
    """The block-pair forward with quantization hooks. ``get_w(name)`` ->
    replacement dict for the layer's quantizable leaves; ``q_act(name, x)``
    -> the (possibly fake-quantized) block-input activation; ``get_rec``
    (optional), name -> the sLSTM layer's recurrent product ``h -> h @ r``
    in place of its ``r`` leaf. The group loop is unrolled in Python (G is
    tiny for search configs) so per-layer grids need no scan threading.
    Returns f32 logits (B, T, V). Named scopes (``xlstm.mlstm``,
    ``xlstm.slstm_scan``, ``xlstm.head``; inside them
    ``xlstm.weight_gather`` and ``xlstm.slstm_menu`` in
    ``forward_population``'s banked lane) carry into the compiled
    program's op metadata."""
    x = tfm.embed_tokens(params, cfg, tokens)
    for g in range(cfg.n_layers // 2):
        bp = jax.tree.map(lambda a, _g=g: a[_g], params["pairs"])
        m, s = f"m{g}", f"s{g}"
        xin = q_act(m, cm.rms_norm(x, bp["norm_m"], cfg.norm_eps))
        with jax.named_scope("xlstm.mlstm"):
            x = x + xlstm.mlstm_fwd({**bp["mlstm"], **get_w(m)}, cfg, xin)
        xin = q_act(s, cm.rms_norm(x, bp["norm_s"], cfg.norm_eps))
        with jax.named_scope("xlstm.slstm_scan"):
            x = x + xlstm.slstm_fwd({**bp["slstm"], **get_w(s)}, cfg, xin,
                                    rec_fn=get_rec(s) if get_rec else None)
    with jax.named_scope("xlstm.head"):
        x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        xq = q_act("head", x)
        return jnp.dot(xq, get_w("head")["lm_head"],
                       preferred_element_type=jnp.float32)


def forward_plain(params, cfg: ArchConfig, tokens):
    """Full-precision forward (identity hooks) — the baseline path."""
    return forward(params, cfg, tokens,
                   lambda name: _layer_leaves(params, cfg, name),
                   lambda name, x: x)


def slstm_menu_engaged(banks, lanes: int) -> bool:
    """Whether ``forward_population`` contracts the sLSTM recurrence
    against the menu rows of its bank: banks are given and the traced lane
    count is above the bank's row count K. At ``lanes <= K`` gathering each
    lane's ``r`` reads no more rows and skips the K-wide product."""
    rows = [b["r"].shape[0] for b in (banks or {}).values() if "r" in b]
    return bool(rows) and lanes > rows[0]


def _menu_product(bank, idx):
    """The sLSTM recurrent product of one lane whose ``r`` is row ``idx``
    of ``bank`` (K, H, dh, 4*dh), without gathering that row: one dot
    contracts the lane's state against all K rows (under ``jax.vmap`` the
    bank stays unbatched, so that is one dot for every lane's rows at
    once), then an exact select keeps row ``idx``. The product the lane
    keeps is its ``h`` times its ``r``, the same operands as the gathered
    einsum; the select, unlike a one-hot sum, leaves a lane finite when
    another row overflows."""
    def rec(h):
        with jax.named_scope("xlstm.slstm_menu"):
            # the bank as the dot's first operand: the CPU then sums each
            # product in the per-lane einsum's order (bit for bit at the
            # tests' widths)
            every = jnp.einsum("khde,bhd->kbhe", bank, h)
            return jax.lax.select_n(idx, *every)
    return rec


def forward_population(params, cfg: ArchConfig, tokens, qp_stack,
                       banks=None):
    """Score P quantization candidates in one call: vmap of the hooked
    forward over the (P, L, 6) qp grid stack (params/tokens broadcast).
    With ``banks`` each lane's quantized leaves are *gathered* by menu
    index — rows are built by the identical jitted ``fake_quant_triple``
    expression, so the gather lane matches the requant lane exactly —
    except the sLSTM recurrent kernel ``r`` once P exceeds the bank's K
    menu rows (``slstm_menu_engaged``): each step then contracts every
    lane's state against the K rows and selects the lane's own
    (``_menu_product``), so the scan carries the (K, H, dh, 4*dh) bank
    instead of P gathered copies. P is the lane count this function
    traces, per shard under a ``shard_map`` pop mesh."""
    names = quant_layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}
    menu_rec = slstm_menu_engaged(banks, qp_stack.shape[0])

    def one(row):                                   # (L, 6) per lane
        def q_act(name, x):
            r = row[li[name]]
            return Q.fake_quant_triple(x, r[3], r[4], r[5])

        get_rec = None
        if banks is None:
            def get_w(name):
                # pure grid values (use_ste=False) — matches the bank rows
                r = row[li[name]]
                leaves = _layer_leaves(params, cfg, name)
                return {k: Q.fake_quant_triple(w, r[0], r[1], r[2],
                                               use_ste=False)
                        for k, w in leaves.items()}
        else:
            def idx_of(name):
                return Q.menu_index_from_hi(row[li[name], 2])

            def get_w(name):
                return LM.gather_rows(banks[name], idx_of(name),
                                      "xlstm.weight_gather",
                                      skip=("r",) if menu_rec else ())

            if menu_rec:
                def get_rec(name):
                    return _menu_product(banks[name]["r"], idx_of(name))

        return forward(params, cfg, tokens, get_w, q_act, get_rec)

    return jax.vmap(one)(qp_stack)


def calibrate(params, cfg: ArchConfig, token_batches) -> Dict[str, float]:
    """Expected block-input activation ranges (``lm_target.calibrate``)."""
    return LM.calibrate(
        lambda toks, q_act: forward(
            params, cfg, toks,
            lambda name: _layer_leaves(params, cfg, name), q_act),
        token_batches)


def weight_grids(params, cfg: ArchConfig):
    """(wclips, wranges) pooled over each block's matrices
    (``lm_target.weight_grids``)."""
    return LM.weight_grids(lambda name: _layer_leaves(params, cfg, name),
                           quant_layer_names(cfg))


@dataclass
class XLSTMTarget(LM.LMTarget):
    """``SearchTarget`` over a trained + calibrated registry xLSTM."""

    supports_retrain = True            # SearchTarget: beacons available

    @property
    def layer_names(self) -> Tuple[str, ...]:
        return quant_layer_names(self.cfg)

    def _leaves(self, params, name):
        return _layer_leaves(params, self.cfg, name)

    def _forward_plain(self, params, tokens):
        return forward_plain(params, self.cfg, tokens)

    def _forward_population(self, params, tokens, qp_stack, banks):
        return forward_population(params, self.cfg, tokens, qp_stack,
                                  banks=banks)

    def _dispatch_stats(self, lanes, banks, shards=1):
        """``slstm_menu``: 1 where the sLSTM recurrence of a shard's
        ``lanes`` lanes contracts against the bank's menu rows;
        ``gathered_bytes`` counts no copy of ``r`` there."""
        menu = slstm_menu_engaged(banks, lanes)
        skip = tuple((n, "r") for n, b in (banks or {}).items()
                     if menu and "r" in b)
        return {"slstm_menu": int(menu),
                "gathered_bytes": LM.gathered_bytes(banks, lanes * shards,
                                                    skip)}

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
