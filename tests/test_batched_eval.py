"""Batched population evaluation: bit-identical objectives and Pareto front
vs the per-candidate scalar path on a seeded small SRU problem."""
import numpy as np
import pytest

from repro.core import batched_eval as BE
from repro.core import sru_experiment as X
from repro.core.mohaq import run_search
from repro.core.nsga2 import NSGA2


@pytest.fixture(scope="module")
def trained():
    return X.train_small_sru(steps=60)


@pytest.fixture(scope="module")
def problem(trained):
    return X.build_problem(trained, X.BITFUSION, ("error", "speedup"))


def _random_allocs(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    return [problem.decode(problem._snap(rng.integers(1, 5, problem.n_var)))
            for _ in range(n)]


class TestStacking:
    def test_bucket_size(self):
        assert BE.bucket_size(1) == 1
        assert BE.bucket_size(3) == 4
        assert BE.bucket_size(16) == 16
        assert BE.bucket_size(17) == 32
        assert BE.bucket_size(65) == 128
        assert BE.bucket_size(130) == 192

    def test_stack_qps_layout(self, trained):
        allocs = _random_allocs_from_bits()
        qps = [trained.qp_for(a) for a in allocs]
        names = list(trained.cfg.layer_names())
        arr = BE.stack_qps(qps, names)
        assert arr.shape == (len(allocs), len(names), 6)
        assert arr.dtype == np.float32
        for p, qp in enumerate(qps):
            for i, n in enumerate(names):
                assert np.allclose(arr[p, i], np.asarray(qp[n], np.float32))


def _random_allocs_from_bits():
    from repro.models.sru import LAYER_NAMES
    return [{n: (b, b) for n in LAYER_NAMES} for b in (2, 4, 8, 16)]


class TestErrorParity:
    def test_batched_errors_bit_identical(self, trained, problem):
        """Every candidate's max-subset error matches the scalar path
        exactly — error counts are integers, so equality is exact."""
        allocs = _random_allocs(problem, 11, seed=2)   # odd n: exercises padding
        scalar = [trained.val_error(a) for a in allocs]
        batched = trained.val_error_batch(allocs)
        assert scalar == batched

    def test_all_population_lowerings_bit_identical(self, trained, problem):
        """The population forward — its XLA scan and its Pallas
        population-axis kernel lane — reproduces the scalar error counts."""
        import jax.numpy as jnp
        from repro.models import sru

        allocs = _random_allocs(problem, 5, seed=9)
        scalar = [trained.val_error(a) for a in allocs]
        assert trained.val_error_batch(allocs) == scalar
        # kernel path (interpret mode): logits must match the XLA scan
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        l_xla = sru.forward_population(trained.params, trained.cfg, feats,
                                       qp_stack)
        l_kern = sru.forward_population(trained.params, trained.cfg, feats,
                                        qp_stack, use_kernel=True)
        np.testing.assert_allclose(np.asarray(l_kern), np.asarray(l_xla),
                                   rtol=1e-5, atol=1e-5)

    def test_evaluate_population_matches_evaluate(self, problem):
        rng = np.random.default_rng(5)
        genomes = [rng.integers(1, 5, problem.n_var) for _ in range(6)]
        scalar = [problem.evaluate(g) for g in genomes]
        batched = problem.evaluate_population(genomes)
        for (so, sv), (bo, bv) in zip(scalar, batched):
            assert list(so) == list(bo)
            assert sv == bv

    def test_infeasible_screened_identically(self, trained):
        """Memory-infeasible genomes never reach the error evaluator and
        still pack identical (inf-error) objectives + violations."""
        mat = sum(trained.cfg.layer_weight_counts().values())
        vec = trained.cfg.vector_weight_count()
        sram = int((mat * 2.5 + vec * 16) / 8)    # tight: most allocs fail
        prob = X.build_problem(trained, X.BITFUSION, ("error", "speedup"),
                               sram_override=sram)
        prob.error_memo = {}          # isolate from the shared memo
        calls = []
        orig = prob.batch_error_fn
        prob.batch_error_fn = lambda allocs: (calls.append(len(allocs)),
                                              orig(allocs))[1]
        rng = np.random.default_rng(7)
        genomes = [rng.integers(1, 5, prob.n_var) for _ in range(8)]
        batched = prob.evaluate_population(genomes)
        scalar = [prob.evaluate(g) for g in genomes]
        for (so, sv), (bo, bv) in zip(scalar, batched):
            assert list(so) == list(bo) and sv == bv
        n_feasible = sum(1 for _, v in scalar if v == 0.0)
        # only feasible candidates occupied vmap lanes
        assert sum(calls) == n_feasible


class TestSearchParity:
    def test_pareto_front_identical(self, trained):
        """Full NSGA-II runs (scalar vs evaluate_batch) visit the same
        genomes and return the identical Pareto front under a fixed seed."""
        kw = dict(n_generations=4, pop_size=6, initial_pop_size=10, seed=3)
        prob_s = X.build_problem(trained, X.BITFUSION, ("error", "speedup"),
                                 batched=False)
        prob_b = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_s.error_memo = {}
        prob_b.error_memo = {}
        rs = run_search(prob_s, **kw)
        rb = run_search(prob_b, **kw)
        assert rs.n_evals == rb.n_evals
        key = lambda res: sorted((tuple(i.genome.tolist()),
                                  tuple(i.objectives.tolist()),
                                  float(i.violation)) for i in res.pareto)
        assert key(rs) == key(rb)

    def test_memoized_search_matches_pr1_evaluator(self, trained):
        """The memoized banked pipeline returns a bit-identical Pareto front
        to the requantize-per-lane evaluator, and the run logs a consistent
        cache-hit count (requested = unique evals + genome cache hits)."""
        kw = dict(n_generations=5, pop_size=6, initial_pop_size=10, seed=7)
        prob_rq = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_rq.batch_error_fn = \
            lambda allocs: trained.val_error_batch(allocs, use_banks=False)
        prob_rq.error_memo = {}
        prob_bk = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_bk.error_memo = {}
        logs = []
        r1 = run_search(prob_rq, **kw)
        r2 = run_search(prob_bk, log=logs.append, **kw)
        key = lambda res: sorted((tuple(i.genome.tolist()),
                                  tuple(i.objectives.tolist()),
                                  float(i.violation)) for i in res.pareto)
        assert key(r1) == key(r2)
        requested = 10 + 5 * 6
        assert r2.n_evals + r2.n_cache_hits == requested
        assert any("cache_hits=" in line for line in logs)

    def test_shared_memo_across_platform_sweep(self, trained):
        """Base-params error evals are shared across searches built from
        one trained model: a second platform's search re-hits memoized
        allocations instead of re-scoring them."""
        memo_before = dict(trained.shared_error_memo)
        prob_a = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        genomes = [np.asarray([g] * prob_a.n_var) for g in (1, 2, 3)]
        prob_a.evaluate_population(genomes)
        prob_b = X.build_problem(trained, X.BITFUSION, ("error", "memory"))
        prob_b.evaluate_population(genomes)
        assert prob_b.memo_hits >= len(genomes)
        trained.shared_error_memo.clear()
        trained.shared_error_memo.update(memo_before)


class TestBeaconGroupedSearch:
    def test_grouped_matches_detached(self, trained):
        """Beacon-grouped batched evaluation reproduces the detached
        per-candidate path exactly on a seeded search: identical retrain
        count AND bit-identical Pareto front."""
        kw = dict(generations=2, pop=6, initial=8, seed=0, retrain_steps=3)
        r_det, bs_det = X.experiment3_bitfusion(trained, beacon=True,
                                                batched=False, **kw)
        r_grp, bs_grp = X.experiment3_bitfusion(trained, beacon=True,
                                                batched=True, **kw)
        assert bs_det.n_retrains == bs_grp.n_retrains
        assert len(bs_det.beacons) == len(bs_grp.beacons)
        key = lambda res: sorted((tuple(i.genome.tolist()),
                                  tuple(i.objectives.tolist()),
                                  float(i.violation)) for i in res.pareto)
        assert key(r_det) == key(r_grp)
        assert r_det.n_evals == r_grp.n_evals


class TestNSGA2BatchHook:
    def test_evaluate_batch_equals_scalar(self):
        """The GA's batch hook is a pure drop-in: identical history and
        front on an analytic problem."""
        def ev(g):
            return [float(g.sum()), float((4 - g).sum())], 0.0

        def ev_batch(gs):
            return [ev(g) for g in gs]

        runs = []
        for batch in (None, ev_batch):
            ga = NSGA2(n_var=6, var_lo=1, var_hi=4, evaluate=ev,
                       evaluate_batch=batch, pop_size=8, initial_pop_size=12,
                       n_generations=6, seed=11)
            front = ga.run()
            runs.append((len(ga.history),
                         sorted(tuple(i.genome.tolist()) for i in front)))
        assert runs[0] == runs[1]

    def test_batch_dedup_within_generation(self):
        """Duplicate genomes in one batch are evaluated once (cache parity
        with the scalar path)."""
        seen = []

        def ev(g):
            seen.append(tuple(g.tolist()))
            return [float(g.sum())], 0.0
        ga = NSGA2(n_var=3, var_lo=1, var_hi=1, evaluate=ev,
                   evaluate_batch=lambda gs: [ev(g) for g in gs],
                   pop_size=4, initial_pop_size=8, n_generations=1, seed=0)
        ga.run()
        assert len(seen) == 1          # all genomes identical -> one eval
        assert len(ga.history) == 1
