"""The search's profiler spans: a tiny SRU search and a tiny xLSTM search of
three generations run under ``jax.profiler.trace`` on the CPU, and the
host plane of the trace is read back with ``ProfileData``.

Checked: every span of the search path appears, nested as the program
states; one ``ga.generation`` per generation with its ``gen``; the real
lanes of the window's dispatches add up to the allocations scored, and each
dispatch's padded size is the compile bucket; the xLSTM's dispatches say
which sLSTM recurrence they took (``slstm_menu``); the unfolded per-subset
path splits each subset's dispatch the same way; and tracing changes no
result (the Pareto front equals an untraced run's of the same seed).
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import quantization as Q
from repro.core import sru_experiment as X
from repro.core import xlstm_target as XT
from repro.core.api import SearchSession
from repro.core.batched_eval import BatchedSRUEvaluator, bucket_size

GENERATIONS = 3
MENU_ROWS = len(Q.SUPPORTED_BITS)      # K, the rows of each weight bank

# span -> the program spans it may sit directly inside
PARENTS = {
    "search.run": (),
    "search.build": ("search.run",),
    "ga.initial": ("search.run",),
    "ga.generation": ("search.run",),
    "ga.rank": ("ga.generation",),
    "ga.offspring": ("ga.generation",),
    "ga.survive": ("ga.generation",),
    "mohaq.evaluate": ("ga.initial", "ga.generation"),
    "mohaq.objectives": ("mohaq.evaluate",),
    "evaluator.errors": ("mohaq.evaluate",),
    "evaluator.stack": ("evaluator.errors",),
    "evaluator.dispatch": ("evaluator.errors",),
    "evaluator.wait": ("evaluator.errors",),
    "evaluator.readback": ("evaluator.errors",),
}


def host_spans(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the program's spans, from the
    host plane of the trace written under ``trace_dir``."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats))
                        for e in line.events if e.name in PARENTS]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def parent_of(span, spans):
    """The innermost other program span that covers ``span``."""
    inside = [s for s in spans if s is not span
              and s[1] <= span[1] and span[2] <= s[2]]
    return min(inside, key=lambda s: s[2] - s[1])[0] if inside else None


@pytest.fixture(scope="module")
def sru_target():
    return X.train_small_sru(steps=0)


@pytest.fixture(scope="module")
def xlstm_target():
    return XT.train_small_xlstm(steps=0)


@pytest.fixture(scope="module", params=["sru", "xlstm"])
def searched(request, tmp_path_factory):
    """(problem, spans, traced front, untraced front) of one family."""
    target = request.getfixturevalue(f"{request.param}_target")
    session = SearchSession(target, "bitfusion",
                            ("error", "speedup"), sram_override=10 ** 9,
                            share_memo=False)
    run = dict(generations=GENERATIONS, pop=6, initial=10, seed=5)
    untraced = session.run(**run)
    trace_dir = str(tmp_path_factory.mktemp(f"trace_{request.param}"))
    with jax.profiler.trace(trace_dir):
        traced = session.run(**run)
    return (traced.problem, host_spans(trace_dir), traced.front_key(),
            untraced.front_key())


def test_every_span_appears_nested_as_stated(searched):
    _, spans, _, _ = searched
    assert {s[0] for s in spans} == set(PARENTS)
    for span in spans:
        parent = parent_of(span, spans)
        assert (parent in PARENTS[span[0]] if PARENTS[span[0]]
                else parent is None), (span[0], parent)


def test_one_generation_span_per_generation(searched):
    _, spans, _, _ = searched
    gens = [s[3] for s in spans if s[0] == "ga.generation"]
    assert gens == [{"gen": g} for g in range(GENERATIONS)]
    assert [s[3] for s in spans if s[0] == "search.run"] == [{"seed": 5}]


def test_dispatch_lanes_add_up_to_scored_allocations(searched):
    problem, spans, _, _ = searched
    lanes = [s[3]["lanes"] for s in spans if s[0] == "evaluator.dispatch"]
    assert 0 < len(lanes) <= GENERATIONS + 1      # one per generation
    assert sum(lanes) == problem.n_error_evals > 0


def test_dispatch_bucket_is_the_compile_bucket(searched):
    _, spans, _, _ = searched
    stats = [s[3] for s in spans if s[0] == "evaluator.dispatch"]
    assert stats and all(st["bucket"] == bucket_size(st["lanes"])
                         for st in stats)


def test_dispatch_records_the_slstm_recurrence_taken(searched, request):
    """Each xLSTM dispatch says whether its sLSTM recurrence contracted
    against the bank's K menu rows: ``slstm_menu`` is 1 exactly for buckets
    above K. The SRU's dispatches carry no such stat."""
    _, spans, _, _ = searched
    stats = [s[3] for s in spans if s[0] == "evaluator.dispatch"]
    if request.node.callspec.params["searched"] == "xlstm":
        assert all(st["slstm_menu"] == int(st["bucket"] > MENU_ROWS)
                   for st in stats)
        assert any(st["slstm_menu"] for st in stats)
    else:
        assert stats and all("slstm_menu" not in st for st in stats)


def test_slstm_menu_stat_follows_bucket_and_banks(xlstm_target, tmp_path):
    """Buckets on both sides of K, and the requant lane (no banks, so no
    menu contraction at any bucket)."""
    target = xlstm_target
    allocs = [{n: (b, b) for n in target.layer_names}
              for b in (2, 4, 8, 16, 2)]
    banked = target.batched_evaluator()
    requant = target.batched_evaluator(use_banks=False)
    with jax.profiler.trace(str(tmp_path)):
        banked.errors(allocs[:3], target.params)
        banked.errors(allocs, target.params)
        requant.errors(allocs, target.params)
    got = [(s[3]["bucket"], s[3]["slstm_menu"])
           for s in host_spans(str(tmp_path)) if s[0] == "evaluator.dispatch"]
    assert got == [(4, 0), (8, 1), (8, 0)]


def test_traced_front_equals_untraced(searched):
    _, _, traced, untraced = searched
    assert traced == untraced and traced


def test_unfolded_path_splits_each_subset_dispatch(sru_target, tmp_path):
    """Subsets of unequal shapes are scored one dispatch each; each gets
    its own dispatch, wait and readback spans inside ``evaluator.errors``,
    and the errors equal an untraced call's."""
    trained = sru_target
    (f0, l0), (f1, l1) = trained.val_subsets[:2]
    subsets = [(f0, l0), (f1[:-1], l1[:-1])]
    ev = BatchedSRUEvaluator(trained.cfg, subsets, trained.qp_for,
                             make_banks=trained.make_banks,
                             qp_tables=trained.qp_menu_tables())
    rng = np.random.default_rng(0)
    allocs = [{n: (int(b), int(b)) for n, b in zip(
        trained.layer_names, rng.choice((2, 4, 8, 16), len(
            trained.layer_names)))} for _ in range(3)]
    untraced = ev.errors(allocs, trained.params)
    with jax.profiler.trace(str(tmp_path)):
        traced = ev.errors(allocs, trained.params)
    assert traced == untraced
    spans = host_spans(str(tmp_path))
    names = [s[0] for s in spans]
    assert names == ["evaluator.errors", "evaluator.stack"] + [
        "evaluator.dispatch", "evaluator.wait", "evaluator.readback"] * 2
    # the SRU forward batches its lanes, so it computes the padding lane
    assert all(s[3] == {"lanes": 3, "bucket": 4, "lanes_computed": 4}
               for s in spans if s[0] == "evaluator.dispatch")
