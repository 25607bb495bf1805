"""Checks of the granite-4.0-h family that need no chip: its weight counts
and pytree against the program's, its plain reference at the stated
precision against the program's forward at a small size, a sound small run
read as correct, the control one step below the stated precision and each
planted fault read as not correct, and the reader of
``search.gather_mb_per_eval``.

    JAX_PLATFORMS=cpu python -m pytest -q \
        benchmarks/chip/test_granite_family.py
"""
import importlib.util
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import cellrun  # noqa: E402
import control  # noqa: E402
import progspans  # noqa: E402
from faults import FAULTS  # noqa: E402
from families import common as C  # noqa: E402

CELL = "granite_4_0_h_micro.search"
# Limits at test size, from my CPU readings (control.readings, seeds 7-9,
# and whole runs of 4 seeds, 64-token fold): the program at most 0 pp /
# 1.6% / 0.02% (float32 on both sides; one position in a few runs, from the
# chunked SSD's order of sums), its bfloat16 control at least 4.7 pp / 6.3%
# / 1.45%, half_batch at least 7.8 pp / 14.1% / 2.65%, answer_altered 37.5
# pp. Each limit lies between.
SMALL_LIMITS = {"answer_gap_widest_pp": 2.0, "subset_gap_widest_pct": 3.0,
                "subset_gap_mean_pct": 0.5}


def cell_files():
    """(config, mix) of the cell, as ``BENCHMARK.json`` names them."""
    return cellrun.load_cell(CELL)[2:]


def family():
    return cellrun.load_family("granite_hybrid")


def program_config(cfg):
    return family().build_target.__globals__["program_config"](cfg)


def small():
    """The cell's config and mix cut to a size a CPU test holds: three
    layers with attention in the middle at tiny widths, float32 weights
    (the program's stream then is float32 too, and the CPU computes its
    matmuls exactly), so float32 is the stated precision and bfloat16 the
    control's. The embedding multiplier is 3: at width 64 a layer adds a
    sixth of what it adds at 2048, and with 12 the table's own row would
    decide nearly every argmax."""
    cfg, mix = cell_files()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               shared_intermediate_size=128, vocab_size=64, mamba_n_heads=2,
               mamba_d_head=64, mamba_d_state=16, mamba_chunk_size=8,
               n_layers=3, layer_types=["mamba", "attention", "mamba"],
               dtype="float32", embedding_multiplier=3)
    cfg["precision"].update(
        reference={"operands": "float32", "activations": "float32"},
        control={"operands": "bfloat16", "activations": "bfloat16"})
    cfg["limits"] = SMALL_LIMITS
    mix["fold"].update(length=64)
    mix["ga"].update(generations=3)
    return cfg, mix


# ------------------------------------------------------------ counts

def test_weight_counts_and_tree_match_program():
    from repro.core import granite_target as GT
    cfg, mix = cell_files()
    fam = family()
    ours = jax.eval_shape(lambda: fam.init_weights(cfg, 0))
    pcfg = program_config(cfg)
    theirs = jax.eval_shape(lambda: GT.init_params(jax.random.PRNGKey(0),
                                                   pcfg))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    params = jax.tree.map(                      # zero-copy stand-ins
        lambda s: np.broadcast_to(np.zeros((), s.dtype), s.shape), ours)
    names = fam.layer_names(cfg)
    grids = {n: 1.0 for n in names}
    target = GT.GraniteTarget(pcfg, params, [], [], grids, {}, grids)
    assert list(target.layer_names) == names and len(names) == 21
    assert fam.weight_counts(cfg) == target.layer_weights
    assert sum(target.layer_weights.values()) == 797_573_120
    # 2 x weights x 2048 tokens (3.267e12), the SSD of 9 layers (5.9e10)
    # and one attention layer (4.3e9)
    w = fam.work(cfg, mix)
    assert 3.32e12 < w["flops_per_lane"] < 3.34e12
    assert w["bytes_per_lane"] == 2 * 797_573_120


# ------------------------------------------------------------ reference

def _small_setup(cfg, mix, seed=3):
    from repro.core import granite_target as GT
    fam = family()
    params = fam.init_weights(cfg, seed)
    tokens = fam.make_inputs(cfg, mix, seed)
    labels, ranges = C.calibrate(fam, cfg, params, tokens)
    clips, wranges = C.weight_grids(fam, cfg, params)
    grids = C.Grids(ranges, clips, wranges)
    pcfg = program_config(cfg)
    target = fam.build_target(cfg, params, [(tokens, labels)], grids,
                              score_baseline=False)
    banks = target.make_banks(params)
    lane = jax.jit(lambda qp: GT.forward_population(
        params, pcfg, tokens, qp[None], banks=banks)[0])
    ref = jax.jit(lambda qp, p: fam.forward(params, cfg, tokens, qp=qp,
                                            prec=p), static_argnums=1)
    plain = GT.forward_plain(params, pcfg, tokens)
    names = fam.layer_names(cfg)
    allocs = cellrun.random_allocs(names, 4, np.random.default_rng(0))
    return plain, lane, ref, [jnp.asarray(C.qp_rows(a, names, grids))
                              for a in allocs]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))


def test_reference_matches_program_on_cpu():
    """At the small config's stated precision, float32 throughout, the
    reference and the program differ only in the order of their sums (the
    chunked SSD against the stepped one): 1e-4 of the logits on the plain
    lane; 1e-3 on quantized lanes, where such a difference can tip one
    activation across a grid step (my CPU readings: under 1e-5 plain,
    1.9e-4 at most quantized)."""
    cfg, mix = small()
    plain, lane, ref, qps = _small_setup(cfg, mix)
    prec = C.precision_of(cfg, "reference")
    assert _rel(ref(None, prec), plain) < 1e-4
    for qp in qps:
        assert _rel(ref(qp, prec), lane(qp)) < 1e-3


def test_reference_rounds_where_the_bfloat16_program_does():
    """With bfloat16 weights, as the cell has them, the reference with the
    bfloat16 activation stream (and float32 matmuls, which the CPU computes
    exactly) sits nearer the program's logits than the float32 reference
    does, on the plain lane and on every quantized one: the rounding points
    are the program's. What is left are bfloat16 roundings that
    float32-level differences tip the other way, which a coarse activation
    grid turns into a whole step (my CPU readings: 0.8% plain, 3% to 30%
    quantized, the float32 reference 1.0% and 6% to 52%). 2% bounds the
    plain lane; on quantized lanes, where one tipped step of a 2-bit grid
    moves a logit by more than any rounding, the check is that the stated
    rounding is the nearer one."""
    cfg, mix = small()
    cfg.update(dtype="bfloat16")
    plain, lane, ref, qps = _small_setup(cfg, mix)
    prec = C.Precision("float32", "bfloat16")
    near, far = _rel(ref(None, prec), plain), _rel(ref(None, C.EXACT), plain)
    assert near < 2e-2 and near < far, (near, far)
    for qp in qps:
        got = lane(qp)
        near, far = _rel(ref(qp, prec), got), _rel(ref(qp, C.EXACT), got)
        assert near < far, (near, far)


# ------------------------------------------------------------ correctness

def test_sound_run_is_correct():
    cfg, mix = small()
    r = cellrun.run(CELL, 12345 + 2 ** 31, 0.2, False,
                    t_start=time.perf_counter(), require_tpu=False,
                    cfg=cfg, mix=mix)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.fixture(scope="module")
def readings():
    cfg, mix = small()
    return control.readings(CELL, 7, require_tpu=False, cfg=cfg, mix=mix)


def test_program_is_correct_and_control_is_not(readings):
    assert readings["program"]["correct"], readings
    assert readings["control"]["answers_without_number"] == 0
    assert not readings["control"]["correct"], readings


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_program_is_not_correct(readings, fault):
    assert not readings[fault]["correct"], readings[fault]


# ------------------------------------------------------------ metric

def _metric():
    path = os.path.join(HERE, "metrics", "search.gather_mb_per_eval.py")
    spec = importlib.util.spec_from_file_location("gather_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_gather_metric_reads_the_dispatch_stat():
    m = _metric()
    spans = [("bench.window", 0, 1000, {}),
             ("evaluator.dispatch", 10, 20,
              {"lanes": 5, "bucket": 8, "gathered_bytes": 8_000_000}),
             ("evaluator.dispatch", 30, 40,
              {"lanes": 3, "bucket": 4, "gathered_bytes": 4_000_000}),
             ("evaluator.dispatch", 1100, 1200,        # after the window
              {"lanes": 3, "bucket": 4, "gathered_bytes": 4_000_000})]
    value, extra = m.per_eval(spans, 1000 * 1e-9)
    assert value == pytest.approx(12.0 / 8)
    assert extra == {"dispatches": 2, "gathered_gb": pytest.approx(0.012)}
    assert m.per_eval(spans, 999e-9) is None        # not the reduced window


def test_gather_metric_silent_where_the_program_has_no_stat():
    """The small chip trace was recorded by a program without the stat."""
    path = os.path.join(HERE, "testdata", "spans.xplane.pb")
    spans = progspans.load_spans(path)
    wins = [(s, e) for n, s, e, _ in spans if n == "bench.window"]
    window_s = (wins[0][1] - wins[0][0]) * 1e-9
    assert _metric().per_eval(spans, window_s) is None
    assert _metric().read({"trace": None}) is None
