"""granite-4.0-h ``SearchTarget`` — a Mamba-2 + attention hybrid language
model searched per layer through the same engine as the xLSTM (NSGA-II,
``MOHAQProblem``, the generic ``PopulationEvaluator``), on the plumbing
every LM target shares (``core/lm_target.py``).

The model (HF ``granitemoehybrid``; ``configs/granite_4_0_h_micro.py``):
Mamba-2 mixers (``models/mamba2.py``) with a NoPE GQA attention layer at
``cfg.attn_layers``, a SwiGLU MLP in every layer, a tied embedding table,
and four scalars. For each layer,

    h += r * mixer(rmsnorm(h));  h += r * mlp(rmsnorm(h))

with h_0 = e * table[tokens], logits = rmsnorm(h) table^T / s, attention
scores scaled by ``attention_multiplier`` (r = ``residual_multiplier``,
e = ``embedding_multiplier``, s = ``logits_scaling``).

Searchable layers, one weight grid and one activation grid each (the
activation grid at the layer's normed input):

  ``mix{i}``  layer i's mixer: Mamba-2 in_proj, out_proj; or attention
              wq, wk, wv, wo
  ``mlp{i}``  layer i's MLP: w_gate, w_up, w_down
  ``head``    the tied table, quantized in the lookup and in the head

Conv weights and biases, A_log, D, dt_bias and the norms are
``vector_weights`` at 16 bits.

Banked lane: each lane's copy of a layer's leaves is gathered from the
bank only once the layer's input exists (an ``optimization_barrier`` ties
the gather to it), so one layer's copies are live at a time and not the
whole model's for every lane. The embedding lookup reads the lane's rows of
the head's bank directly, without a copy of the table. Every matmul reads
each weight for all of a lane's tokens, so a gathered copy costs far less
than contracting against all 4 menu rows (4x the FLOPs). Named scopes:
``granite.mamba2`` (``granite.ssd_chunk`` and ``granite.ssd_state`` inside
it), ``granite.attention``, ``granite.mlp``, ``granite.head`` and
``granite.weight_gather``.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.core import lm_target as LM
from repro.core import quantization as Q
from repro.models import common as cm
from repro.models import mamba2

F32, BF16 = jnp.float32, jnp.bfloat16

# searchable leaves of each kind of layer
QUANT_LEAVES = {"mamba": ("in_proj", "out_proj"),
                "attention": ("wq", "wk", "wv", "wo"),
                "mlp": ("w_gate", "w_up", "w_down")}


def search_config() -> ArchConfig:
    """CPU-searchable miniature of granite-4.0-h-micro: 3 layers with
    attention in the middle (7 searchable layers), d_model 64, 2 Mamba-2
    heads of 64, vocab 64."""
    return dataclasses.replace(
        get_config("granite-4.0-h-micro"), name="granite_search",
        n_layers=3, attn_layers=(1,), d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=64, ssm_n_heads=2,
        ssm_d_state=16, ssm_chunk=8, attention_multiplier=1.0 / 64)


def mixer_kind(cfg: ArchConfig, i: int) -> str:
    return "attention" if i in cfg.attn_layers else "mamba"


def quant_layer_names(cfg: ArchConfig) -> Tuple[str, ...]:
    names: List[str] = []
    for i in range(cfg.n_layers):
        names += [f"mix{i}", f"mlp{i}"]
    return tuple(names + ["head"])


def _layer_leaves(params, cfg: ArchConfig, name: str
                  ) -> Dict[str, jnp.ndarray]:
    """The full-precision searchable leaves of one layer."""
    if name == "head":
        return {"embed": params["embed"]}
    i = int(name[3:])
    layer = params["layers"][i]
    if name.startswith("mix"):
        keys = QUANT_LEAVES[mixer_kind(cfg, i)]
        return {k: layer["mixer"][k] for k in keys}
    return {k: layer["mlp"][k] for k in QUANT_LEAVES["mlp"]}


def init_params(key, cfg: ArchConfig, dtype=BF16) -> dict:
    """Seeded weights in the served types: matrices and table in ``dtype``
    (bf16 as served; N(0, 1/fan_in) matrices, the table N(0, 0.09/d_model)
    so that the embedding's multiplier does not make every argmax the
    input token), float32 vectors, unit norms."""
    ks = jax.random.split(key, cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        km, kf = jax.random.split(ks[i + 1])
        if mixer_kind(cfg, i) == "attention":
            mixer = cm.init_attn(km, cfg.d_model, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim, dtype)
        else:
            mixer = mamba2.init_mamba2(km, cfg, dtype)
        layers.append({"norm_mix": jnp.ones((cfg.d_model,), F32),
                       "mixer": mixer,
                       "norm_mlp": jnp.ones((cfg.d_model,), F32),
                       "mlp": cm.init_mlp(kf, cfg.d_model, cfg.d_ff,
                                          dtype)})
    return {"embed": cm.normal_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                    0.3 / math.sqrt(cfg.d_model), dtype),
            "layers": layers,
            "final_norm": jnp.ones((cfg.d_model,), F32)}


def attention(p, cfg: ArchConfig, x):
    """Causal GQA attention without positional embedding when ``cfg.nope``,
    scores scaled by ``cfg.attention_multiplier`` (1/sqrt(d) if 0)."""
    positions = jnp.arange(x.shape[1])[None]
    q, k, v = cm.attn_qkv(p, x, positions, cfg.rope_theta, nope=cfg.nope)
    o = cm.gqa_attention(q, k, v, causal=True,
                         scale=cfg.attention_multiplier or None)
    return cm.attn_out(p, o)


def _residual(h, out, cfg: ArchConfig):
    return (h.astype(F32) + cfg.residual_multiplier * out.astype(F32)
            ).astype(h.dtype)


def forward(params, cfg: ArchConfig, tokens, get_w, q_act, lookup):
    """The hybrid forward with quantization hooks: ``get_w(name, x)`` ->
    the layer's (quantized) searchable leaves, given its input ``x``;
    ``q_act(name, x)`` -> the layer's (fake-quantized) normed input;
    ``lookup(tokens)`` -> the rows of the (quantized) table. The
    activation stream has the table's type (bf16 as served). Returns f32
    logits (B, T, vocab)."""
    rows = lookup(tokens)
    h = (rows.astype(F32) * cfg.embedding_multiplier).astype(rows.dtype)
    for i, layer in enumerate(params["layers"]):
        mix, mlp = f"mix{i}", f"mlp{i}"
        x = q_act(mix, cm.rms_norm(h, layer["norm_mix"], cfg.norm_eps))
        w = {**layer["mixer"], **get_w(mix, x)}
        if mixer_kind(cfg, i) == "attention":
            with jax.named_scope("granite.attention"):
                out = attention(w, cfg, x)
        else:
            with jax.named_scope("granite.mamba2"):
                out = mamba2.mamba2_mixer(w, cfg, x)
        h = _residual(h, out, cfg)
        x = q_act(mlp, cm.rms_norm(h, layer["norm_mlp"], cfg.norm_eps))
        w = get_w(mlp, x)
        with jax.named_scope("granite.mlp"):
            h = _residual(h, cm.mlp(w, x), cfg)
    with jax.named_scope("granite.head"):
        x = q_act("head", cm.rms_norm(h, params["final_norm"], cfg.norm_eps))
        table = get_w("head", x)["embed"]
        logits = jnp.einsum("btd,vd->btv", x, table,
                            preferred_element_type=F32)
        return logits / cfg.logits_scaling


def forward_plain(params, cfg: ArchConfig, tokens):
    """Full-precision forward (identity hooks) — the baseline path."""
    return forward(params, cfg, tokens,
                   lambda name, x: _layer_leaves(params, cfg, name),
                   lambda name, x: x,
                   lambda toks: jnp.take(params["embed"], toks, axis=0))


def forward_population(params, cfg: ArchConfig, tokens, qp_stack,
                       banks=None):
    """Score P quantization candidates in one call: vmap of the hooked
    forward over the (P, L, 6) qp grid stack. With ``banks`` each lane's
    leaves are gathered by menu index one layer at a time (module
    docstring) and the lookup reads the head's bank; rows are built by the
    identical ``fake_quant_triple`` expression, so the gather lane matches
    the requant lane exactly."""
    names = quant_layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}

    def one(row):                                   # (L, 6) per lane
        def q_act(name, x):
            r = row[li[name]]
            return Q.fake_quant_triple(x, r[3], r[4], r[5], use_ste=False)

        if banks is None:
            def get_w(name, x):
                r = row[li[name]]
                return {k: Q.fake_quant_triple(w, r[0], r[1], r[2],
                                               use_ste=False)
                        for k, w in _layer_leaves(params, cfg, name).items()}

            def lookup(toks):
                return jnp.take(get_w("head", None)["embed"], toks, axis=0)
        else:
            def idx_of(name):
                return Q.menu_index_from_hi(row[li[name], 2])

            def get_w(name, x):
                _, idx = jax.lax.optimization_barrier((x, idx_of(name)))
                return LM.gather_rows(banks[name], idx,
                                      "granite.weight_gather")

            def lookup(toks):
                bank = banks["head"]["embed"]
                K, V, D = bank.shape
                with jax.named_scope("granite.weight_gather"):
                    return jnp.take(bank.reshape(K * V, D),
                                    idx_of("head") * V + toks, axis=0)

        return forward(params, cfg, tokens, get_w, q_act, lookup)

    return jax.vmap(one)(qp_stack)


@dataclass
class GraniteTarget(LM.LMTarget):
    """``SearchTarget`` over a calibrated granite-4.0-h hybrid."""

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

    @property
    def fixed_ops(self) -> int:
        """Max-precision op estimate per token (the SSD's decays, products
        and state, the gated norm, attention scores, norms — activation x
        activation, never searchable): ~32 ops per inner-dim element per
        layer. Only shifts the Eq. 4 speedup normalization."""
        return 32 * self.cfg.ssm_d_inner * self.cfg.n_layers


def make_target(params, cfg: ArchConfig, val_subsets, test_batches=()
                ) -> GraniteTarget:
    """Calibrate on the validation tokens only, set the weight grids and
    score the unquantized baseline. One jitted unquantized pass a batch
    gives both its layer-input max-abs and its argmax."""
    @jax.jit
    def calib_pass(toks):
        out = {}

        def q_act(name, x):
            out[name] = jnp.max(jnp.abs(x))
            return x

        logits = forward(params, cfg, toks,
                         lambda name, x: _layer_leaves(params, cfg, name),
                         q_act, lambda t: jnp.take(params["embed"], t, axis=0))
        return out, jnp.argmax(logits, -1)

    passes = [calib_pass(t) for t, _ in val_subsets]
    act_ranges = LM.calibrate(
        lambda maxes, q_act: [q_act(n, m) for n, m in maxes.items()],
        [maxes for maxes, _ in passes])
    wclips, wranges = LM.weight_grids(
        lambda name: _layer_leaves(params, cfg, name),
        quant_layer_names(cfg))
    target = GraniteTarget(cfg, params, list(val_subsets),
                           list(test_batches), act_ranges, wclips, wranges)
    target.baseline_val_error = max(
        100.0 * int(jnp.sum(pred != labels)) / labels.size
        for (_, pred), (_, labels) in zip(passes, val_subsets))
    if target.test_batches:
        target.baseline_test_error = target.test_error()
    return target


def granite_contract_harness():
    """Tiny-but-real hybrid for the jaxpr contract checker (see
    ``repro.core.target_registry``): one Mamba-2 layer and one attention
    layer at widths that keep every model dimension off the marker dim
    (T=3). The checks read jaxprs, so the weights are drawn on the host at
    ``init_params``' shapes and types (nothing compiles for them), and the
    (32, 16) out_proj, w_down and table share one bank program."""
    from repro.core.target_registry import ContractHarness, MARKER_DIM

    cfg = dataclasses.replace(search_config(), name="granite_contract",
                              n_layers=2, attn_layers=(1,),
                              d_model=16, n_heads=2, n_kv_heads=1, d_ff=32,
                              vocab_size=32, ssm_n_heads=2,
                              ssm_d_state=8)
    rng = np.random.default_rng(0)
    params = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(0.0, 0.25, s.shape).astype(s.dtype)),
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)))
    B, T = 2, MARKER_DIM
    toks = jnp.asarray((np.arange(B * T).reshape(B, T)
                        % cfg.vocab_size).astype(np.int32))
    names = quant_layer_names(cfg)
    act_ranges = {n: 1.0 for n in names}
    wclips = {(n, b): 0.5 for n in names for b in (2, 4, 8)}
    wranges = {n: 1.0 for n in names}
    target = GraniteTarget(cfg, params, [(toks, toks)] * 4, [(toks, toks)],
                           act_ranges, wclips, wranges)

    def forward_pop(params, feats, qp_stack, banks=None):
        return forward_population(params, cfg, feats, qp_stack, banks=banks)

    return ContractHarness(
        name="granite_hybrid", target=target, feats=toks, labels=toks,
        layer_names=names, marker_dim=T,
        anchor_path="src/repro/core/granite_target.py",
        forward_pop=forward_pop,
        make_evaluator=lambda: target.batched_evaluator(use_banks=True),
        forward_decode=None)
