"""Checks of the chip benchmark that need no chip: work counts against the
program's own counts, the weights' pytree against the program's, the plain
references against the program's forwards at a small size, the trace
reduction on a small trace recorded on a TPU v5e, the control and the
program's faults seen as not correct, and the exit without a TPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""
import json
import os
import subprocess
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
import xplane  # noqa: E402
from faults import FAULTS, planted  # noqa: E402
from families import common as C  # noqa: E402

CELLS = {"sru": "sru_timit.search", "xlstm": "xlstm_350m.search"}
# Limits at test size, from my CPU readings (control.readings, seeds 1-3):
# the SRU program reads 0 on every number (exact float32 on both sides), its
# bfloat16 control at least 4.2 pp / 8.3% / 0.87%; the xLSTM program at most
# 1.6 pp / 3.1% / 0.51% (ties of coarse grids broken by rounding), its
# float8 control at least 18.8 pp / 21.9% / 3.7%, half_batch at least
# 15.6% widest and 2.1% mean. Each limit lies between, nearer the program.
SMALL_LIMITS = {
    "sru": {"answer_gap_widest_pp": 2.0, "subset_gap_widest_pct": 2.0,
            "subset_gap_mean_pct": 0.5},
    "xlstm": {"answer_gap_widest_pp": 6.0, "subset_gap_widest_pct": 9.0,
              "subset_gap_mean_pct": 1.2}}


def cell_files(family):
    _, _, cfg, mix = cellrun.load_cell(CELLS[family])
    return cfg, mix


def small(family):
    """The cell's config and mix cut to a size a CPU test holds. On the CPU
    the program's float32 matmuls are exact, so the small configs state
    float32 operands (the SRU program is then exact float32 throughout,
    the xLSTM program keeps its bfloat16 activation stream); their
    controls are one step below. Limits set from readings at this size
    (``SMALL_LIMITS``)."""
    cfg, mix = cell_files(family)
    if family == "sru":
        cfg.update(hidden=16, proj=8, n_sru_layers=2, n_outputs=32)
        cfg["precision"].update(
            reference={"operands": "float32", "activations": "float32"},
            control={"operands": "bfloat16", "activations": "float32"})
        mix["fold"].update(rows=2, length=12)
    else:
        cfg.update(d_model=32, n_layers=2, vocab_size=64)
        cfg["precision"].update(
            reference={"operands": "float32", "activations": "bfloat16"},
            control={"operands": "float8_e4m3fn",
                     "activations": "float8_e4m3fn"})
        mix["fold"].update(length=64)
    cfg["limits"] = SMALL_LIMITS[family]
    mix["ga"].update(generations=3)
    return cfg, mix


# ------------------------------------------------------------ counts

def test_sru_weight_counts_match_program():
    from repro.core.sru_experiment import PAPER_CFG
    cfg, mix = cell_files("sru")
    fam = cellrun.load_family("sru")
    assert fam.weight_counts(cfg) == PAPER_CFG.layer_weight_counts()
    assert sum(fam.weight_counts(cfg).values()) == 5_549_500
    w = fam.work(cfg, mix)
    assert w["flops_per_lane"] == 2 * 5_549_500 * 4 * 4 * 300


def test_xlstm_weight_counts_match_program():
    from repro.core.xlstm_target import XLSTMTarget
    cfg, mix = cell_files("xlstm")
    fam = cellrun.load_family("xlstm")
    shapes = jax.eval_shape(lambda: fam.init_weights(cfg, 0))
    params = jax.tree.map(                      # zero-copy stand-ins
        lambda s: np.broadcast_to(np.zeros((), s.dtype), s.shape), shapes)
    names = fam.layer_names(cfg)
    grids = {n: 1.0 for n in names}
    target = XLSTMTarget(fam.build_target.__globals__["program_config"](cfg),
                         params, [], [], grids, {}, grids)
    assert fam.weight_counts(cfg) == target.layer_weights
    # 2 x (10,485,760 mLSTM + 14,680,064 sLSTM) + 1024 x 50,432 padded head
    assert sum(target.layer_weights.values()) == 101_974_016


@pytest.mark.parametrize("family", ["sru", "xlstm"])
def test_weights_have_the_programs_tree(family):
    cfg, _ = cell_files(family)
    fam = cellrun.load_family(family)
    ours = jax.eval_shape(lambda: fam.init_weights(cfg, 0))
    if family == "sru":
        from repro.models import sru
        pcfg = sru.SRUModelConfig()
        theirs = jax.eval_shape(lambda: sru.init_params(
            jax.random.PRNGKey(0), pcfg))
    else:
        from repro.models import registry
        pcfg = fam.build_target.__globals__["program_config"](cfg)
        theirs = jax.eval_shape(registry.get_model(pcfg).init,
                                jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


# ------------------------------------------------------------ references

def _grids(family, cfg, mix, seed=3):
    fam = cellrun.load_family(family)
    params = fam.init_weights(cfg, seed)
    inputs = fam.make_inputs(cfg, mix, seed)
    labels, ranges = C.calibrate(fam, cfg, params, inputs)
    clips, wranges = C.weight_grids(fam, cfg, params)
    return fam, params, inputs, C.Grids(ranges, clips, wranges)


def test_sru_reference_equals_program_forward_on_cpu():
    """On the CPU both run exact float32, so every logit agrees."""
    from repro.models import sru
    cfg, mix = small("sru")
    cfg["n_sru_layers"] = 3
    fam, params, feats, grids = _grids("sru", cfg, mix)
    pcfg = sru.SRUModelConfig(input_dim=23, hidden=16, proj=8,
                              n_sru_layers=3, n_outputs=32)
    names = fam.layer_names(cfg)
    rng = np.random.default_rng(0)
    for alloc in cellrun.random_allocs(names, 3, rng):
        qp = C.qp_rows(alloc, names, grids)
        ref = fam.forward(params, cfg, feats, qp=jnp.asarray(qp))
        got = sru.forward(params, pcfg, feats,
                          qp={n: tuple(qp[i]) for i, n in enumerate(names)})
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_xlstm_reference_at_stated_precision_matches_program_on_cpu():
    """At the small config's stated precision (the program's bfloat16
    activation stream; float32 matmuls, which the CPU computes exactly)
    the reference's logits agree with the program's to within the odd
    bfloat16 rounding that float32-level differences tip the other way (my
    CPU readings: 3.4e-8 relative at 2 layers, 4.8e-4 at 4), quantized
    lanes included; the plain float32 reference sits over ten times as far
    away, so the rounding points are the program's."""
    from repro.core import xlstm_target as XT
    cfg, mix = small("xlstm")
    cfg.update(n_layers=4)
    mix["fold"].update(rows=2, length=16)
    fam, params, tokens, grids = _grids("xlstm", cfg, mix)
    pcfg = fam.build_target.__globals__["program_config"](cfg)
    prec = C.precision_of(cfg, "reference")
    names = fam.layer_names(cfg)
    target = fam.build_target(cfg, params, [(tokens, tokens)], grids,
                              score_baseline=False)
    banks = target.make_banks(params)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
    got = XT.forward_plain(params, pcfg, tokens)
    near = rel(fam.forward(params, cfg, tokens, prec=prec), got)
    far = rel(fam.forward(params, cfg, tokens), got)
    assert near < 2e-3 and near < far / 10, (near, far)
    rng = np.random.default_rng(0)
    for alloc in cellrun.random_allocs(names, 3, rng):
        qp = jnp.asarray(C.qp_rows(alloc, names, grids))
        ref = fam.forward(params, cfg, tokens, qp=qp, prec=prec)
        lane = XT.forward_population(params, pcfg, tokens, qp[None],
                                     banks=banks)[0]
        assert rel(ref, lane) < 2e-3


# ------------------------------------------------------------ trace

def test_trace_reduction_on_recorded_chip_trace():
    path = os.path.join(HERE, "testdata", "small.xplane.pb")
    red = xplane.reduce(xplane.load(path), "_batch_err")
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["program_s"] <= red["busy_s"] + 1e-9
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    labels = {g[0] for g in red["idle_gaps"]}
    assert labels <= {"bench.evaluator", "bench.search",
                      "bench.between_searches"}
    # four gaps (before, between and after three calls) make up all idle
    # time; the 2 ms host waits outside the evaluator show as search gaps
    assert len(red["idle_gaps"]) == 4
    idle = sum(s for _, s in red["idle_gaps"])
    assert abs(idle - (red["window_s"] - red["busy_s"])) < 1e-9
    assert "bench.search" in labels
    assert red["device_ops"][0][0] == "%while"


def test_merge_and_clip_intervals():
    assert xplane._merge([(3, 5), (0, 2), (1, 4)]) == [[0, 5]]
    assert xplane._clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


# ------------------------------------------------------------ correctness

def _run(family, *, sample=None):
    cfg, mix = small(family)
    if sample:
        mix["check"]["sample"] = sample
    return cellrun.run(CELLS[family], 12345 + 2 ** 31, 0.2, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       cfg=cfg, mix=mix)


@pytest.mark.parametrize("family", ["sru", "xlstm"])
def test_sound_run_is_correct(family):
    r = _run(family)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "search_evals_per_s"}


@pytest.fixture(scope="module", params=["sru", "xlstm"])
def readings(request):
    """The control and the planted faults, read as ``control.py`` reads
    them on the chip, at test size."""
    cfg, mix = small(request.param)
    return control.readings(CELLS[request.param], 7, require_tpu=False,
                            cfg=cfg, mix=mix)


def test_program_is_correct_and_control_is_not(readings):
    assert readings["program"]["correct"], readings
    assert readings["control"]["answers_without_number"] == 0
    assert not readings["control"]["correct"], readings


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_program_is_not_correct(readings, fault):
    assert not readings[fault]["correct"], readings[fault]


@pytest.mark.parametrize("family", ["sru", "xlstm"])
def test_fault_in_a_whole_run_is_not_correct(family):
    """The same faults through ``cellrun.run``, the benchmark's own run."""
    for fault in sorted(FAULTS):
        with planted(fault):
            r = _run(family, sample=100000)
        assert not r["correct"], (fault, r["checks"])


# ------------------------------------------------------------ entry point

def test_exits_without_result_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "sru_timit.search", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_are_found_by_name():
    bench = cellrun.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for cell in bench["workloads"]:
        _, _, cfg, mix = cellrun.load_cell(cell["name"])
        cellrun.load_family(cfg["family"])
        assert mix["check"]["sample"] > 0
    for m in bench["per_layer"]:
        assert callable(cellrun.load_metric(m["name"]).read)
    json.dumps(bench)
