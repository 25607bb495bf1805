"""The granite-4.0-h hybrid search target at a small size on the CPU: the
chunked SSD against its sequential recurrence, the banked population
forward against the plain reference (``models/granite_ref.py``) and the
requantizing lane, the NoPE attention and the multipliers against a hand
computation, a search through ``SearchSession``, and the contract gate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import api
from repro.core import batched_eval
from repro.core import granite_target as GT
from repro.core import target_registry as tr
from repro.data import synthetic
from repro.models import granite_ref
from repro.models import mamba2

MENU = (2, 4, 8, 16)
plain = jax.jit(GT.forward_plain, static_argnums=1)
reference = jax.jit(granite_ref.forward, static_argnums=1)
population = jax.jit(GT.forward_population, static_argnums=1)


def _target(dtype):
    """The small hybrid with labels from its own unquantized argmax (the
    weights are untrained), 4 validation subsets of 2 x 20 tokens: T is
    2.5 chunks of the small config's 8."""
    cfg = GT.search_config()
    params = GT.init_params(jax.random.PRNGKey(0), cfg, dtype)

    def subset(step):
        toks = synthetic.lm_batch(cfg.vocab_size, 2, 20, seed=77, step=step,
                                  n_noise=2)["tokens"]
        return toks, jnp.argmax(plain(params, cfg, toks), -1)

    return GT.make_target(params, cfg, [subset(i) for i in range(4)])


@pytest.fixture(scope="module")
def bf16_target():
    return _target(jnp.bfloat16)


@pytest.fixture(scope="module")
def f32_target(bf16_target):
    """The same draws kept in float32, on the bf16 target's grids."""
    t = bf16_target
    params = GT.init_params(jax.random.PRNGKey(0), t.cfg, jnp.float32)
    return GT.GraniteTarget(t.cfg, params, t.val_subsets, [], t.act_ranges,
                            t.wclips, t.wranges)


def _allocs(names, n, seed):
    rng = np.random.default_rng(seed)
    return [{nm: (int(rng.choice(MENU)), int(rng.choice(MENU)))
             for nm in names} for _ in range(n)]


def _stack(target, allocs):
    return jnp.asarray(batched_eval.stack_qps(
        [target.qp_for(a) for a in allocs], list(target.layer_names)))


def test_chunked_ssd_equals_sequential_recurrence():
    """T = 20 is 2.5 chunks of 8, with two groups of two heads. Float32
    on both sides; only the order of the sums differs, so 1e-5 of the
    output's scale."""
    rng = np.random.default_rng(3)
    b, T, H, P, G, N = 2, 20, 4, 6, 2, 5
    x, B, C = (jnp.asarray(rng.normal(size=s), jnp.float32)
               for s in ((b, T, H, P), (b, T, G, N), (b, T, G, N)))
    dt = jnp.asarray(np.log1p(np.exp(rng.normal(size=(b, T, H)))),
                     jnp.float32)                               # softplus
    A = jnp.asarray(-np.exp(rng.normal(size=H)), jnp.float32)
    got = jax.jit(mamba2.ssd_chunked, static_argnums=5)(x, dt, A, B, C, 8)
    want = jax.jit(mamba2.ssd_sequential)(x, dt, A, B, C)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * scale, rtol=0)


@pytest.mark.parametrize("P", [3, 8])
def test_banked_population_matches_reference(f32_target, P):
    """Every lane of a banked dispatch against the plain reference of its
    allocation. With float32 weights the program keeps a float32 stream,
    so both sides are float32 and differ only in the order of their sums
    (chunked vs sequential SSD): 1e-4 of each lane's logits, and the same
    argmax at every position. P = 8 crosses the bank's K = 4 rows."""
    t = f32_target
    allocs = _allocs(t.layer_names, P, seed=P)
    stack = _stack(t, allocs)
    toks = t.val_subsets[0][0]
    lanes = population(t.params, t.cfg, toks, stack,
                       banks=t.make_banks(t.params))
    for i in range(P):
        ref = reference(t.params, t.cfg, toks, qp=stack[i])
        gap = float(jnp.linalg.norm(lanes[i] - ref) / jnp.linalg.norm(ref))
        assert gap < 1e-4, (i, gap)
        assert bool(jnp.all(jnp.argmax(lanes[i], -1)
                            == jnp.argmax(ref, -1))), i


def test_plain_bf16_forward_near_reference(bf16_target):
    """As served (bf16 weights and stream) the unquantized program stays
    within bfloat16's rounding of the float32 reference: 1e-2 of the
    logits (the stream rounds to 8 bits of mantissa at each layer)."""
    t = bf16_target
    toks = t.val_subsets[0][0]
    ref = reference(t.params, t.cfg, toks)
    got = plain(t.params, t.cfg, toks)
    assert float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)) < 1e-2


def test_banked_lane_equals_requant_lane(bf16_target):
    """Bank rows are built by the requantizing lane's own expression, so
    the gathered lane is bit for bit the requantized one (bf16 as served;
    P = 8 crosses the bank's K = 4 rows)."""
    t = bf16_target
    stack = _stack(t, _allocs(t.layer_names, 8, seed=11))
    toks = t.val_subsets[0][0]
    banked = population(t.params, t.cfg, toks, stack,
                        banks=t.make_banks(t.params))
    requant = population(t.params, t.cfg, toks, stack)
    assert bool(jnp.all(banked == requant))


def test_nope_attention_and_multipliers_by_hand():
    """One attention layer (4 query heads over 2 KV heads of 16) and its
    MLP at float32 against the same model written out in float64 numpy: no
    positional embedding, scores x 1/64 (not 1/sqrt(16)), embedding x 12,
    each residual branch x 0.22 and the logits / 8. 1e-5 relative: float32
    against float64."""
    cfg = dataclasses.replace(GT.search_config(), n_layers=1,
                              attn_layers=(0,))
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling, cfg.nope) == (
                1 / 64, 12.0, 0.22, 8.0, True)
    params = GT.init_params(jax.random.PRNGKey(5), cfg, jnp.float32)
    toks = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    got = np.asarray(plain(params, cfg, toks))[0]

    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    lay, table = p["layers"][0], p["embed"]
    rms = lambda v: v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-5)
    silu = lambda v: v / (1 + np.exp(-v))
    h = 12.0 * table[np.asarray(toks[0])]                  # (T, D)
    x = rms(h)
    a = lay["mixer"]
    q = np.einsum("td,dhk->thk", x, a["wq"])
    k = np.einsum("td,dhk->thk", x, a["wk"])
    v = np.einsum("td,dhk->thk", x, a["wv"])
    o = np.zeros_like(q)
    for t in range(len(h)):
        for head in range(4):
            kv = head // 2                         # 2 query heads a KV head
            s = np.array([q[t, head] @ k[j, kv] / 64 for j in range(t + 1)])
            w = np.exp(s - s.max())
            o[t, head] = (w / w.sum()) @ v[:t + 1, kv]
    h = h + 0.22 * np.einsum("thk,hkd->td", o, a["wo"])
    x = rms(h)
    m = lay["mlp"]
    h = h + 0.22 * (silu(x @ m["w_gate"]) * (x @ m["w_up"])) @ m["w_down"]
    want = rms(h) @ table.T / 8
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_search_session_returns_front(bf16_target):
    res = api.SearchSession(bf16_target, "bitfusion",
                            ("error", "speedup")).run(
        generations=2, pop=4, initial=4, seed=1)
    assert res.result.pareto
    for row in res.table(with_test=False):
        assert set(row["alloc"]) == set(bf16_target.layer_names)


def test_dispatch_records_gathered_bytes(bf16_target):
    """``gathered_bytes``: one bank row a lane for every leaf, for the
    lanes the program computes (11 real lanes of a 16-lane bucket)."""
    t = bf16_target
    banks = t.make_banks(t.params)
    per_lane = sum(2 * n for n in t.layer_weights.values())   # bf16 rows
    assert t._dispatch_stats(16, 11, banks) == {
        "gathered_bytes": 11 * per_lane}


@pytest.mark.parametrize("P,bucket", [(3, 4), (5, 8)])
def test_padded_dispatch_skips_padding_lanes(f32_target, monkeypatch, P,
                                             bucket):
    """P allocations padded to a larger bucket, through the evaluator:
    every real lane's per-subset counts equal those of its allocation
    scored alone and those of the plain reference (float32, as in
    ``test_banked_population_matches_reference``); the padding lanes are
    not computed and return zeros; the dispatch's span records the real
    lanes as ``lanes_computed`` and gathers their copies only."""
    t = f32_target
    ev = t.batched_evaluator(use_banks=True)
    banks = ev._banks_for(t.params)
    spans = []

    class Span:
        def __init__(self, name, **stats):
            spans.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(batched_eval, "TraceAnnotation", Span)
    allocs = _allocs(t.layer_names, P, seed=20 + P)

    def counts(allocs):
        lanes = ev._stack(allocs)
        return np.asarray(ev._batch_err(t.params, banks, ev._feats_all,
                                        ev._labels_all, lanes)), lanes

    got, (stack, live) = counts(allocs)
    assert got.shape == (bucket, len(t.val_subsets))
    assert live.tolist() == [True] * P + [False] * (bucket - P)
    assert not got[P:].any()                       # the skip branch's zeros
    for i, a in enumerate(allocs):
        alone, _ = counts([a])
        assert got[i].tolist() == alone[0].tolist(), i
        want = [int(jnp.sum(jnp.argmax(reference(t.params, t.cfg, toks,
                                                 qp=stack[i]), -1)
                            != labels))
                for toks, labels in t.val_subsets]
        assert got[i].tolist() == want, i

    errs = ev.errors(allocs, t.params)
    assert errs == [max(100.0 * c / ev._subset_frames for c in row)
                    for row in got[:P].tolist()]
    stats = [st for name, st in spans if name == "evaluator.dispatch"][-1]
    per_lane = sum(4 * n for n in t.layer_weights.values())   # f32 rows
    assert (stats["lanes"], stats["bucket"], stats["lanes_computed"],
            stats["gathered_bytes"]) == (P, bucket, P, P * per_lane)


def test_skipped_lane_returns_zeros(bf16_target):
    """At the forward: a lane whose ``live`` is false returns zero logits,
    and every other lane is bit for bit the lane of an all-live call."""
    t = bf16_target
    stack = _stack(t, _allocs(t.layer_names, 4, seed=31))
    toks = t.val_subsets[0][0]
    banks = t.make_banks(t.params)
    every = population(t.params, t.cfg, toks, stack, banks=banks)
    live = jnp.asarray([True, False, True, False])
    some = population(t.params, t.cfg, toks, stack, banks=banks, live=live)
    assert bool(jnp.all(some[::2] == every[::2]))
    assert not bool(jnp.any(some[1::2]))


def test_published_config_and_benchmark_cut():
    """The config states the published model whole (3.19B parameters,
    attention at 5, 15, 25, 35); its first 10 layers with a quarter of the
    vocabulary hold the benchmark's 797.6M searchable weights in 21
    layers."""
    cfg = get_config("granite-4.0-h-micro")
    assert cfg.n_layers == 40 and cfg.attn_layers == (5, 15, 25, 35)
    assert cfg.ssm_n_heads * cfg.ssm_head_dim == cfg.ssm_d_inner == 4096
    assert round(cfg.n_params() / 1e9, 2) == 3.19
    cut = dataclasses.replace(cfg, n_layers=10, attn_layers=(5,),
                              vocab_size=25088)
    shapes = jax.eval_shape(lambda: GT.init_params(jax.random.PRNGKey(0),
                                                   cut))
    counts = {n: sum(int(np.prod(v.shape)) for v in
                     GT._layer_leaves(shapes, cut, n).values())
              for n in GT.quant_layer_names(cut)}
    assert len(counts) == 21
    assert counts["mix0"] == 2048 * 8512 + 4096 * 2048
    assert counts["mix5"] == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert sum(counts.values()) == 797_573_120


def test_granite_hybrid_contracts_hold():
    from tools.analysis.contracts import run_contracts
    assert "granite_hybrid" in tr.list_contract_targets()
    assert run_contracts(["granite_hybrid"]) == []
