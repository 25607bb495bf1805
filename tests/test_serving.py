"""Serving tier (PR 9 tentpole): Pareto-front-as-a-service.

Contract coverage:
  (a) routing — SLO classes map onto the artifact's objective rows;
      degenerate fronts (empty, single-allocation) and infeasible classes
      degrade predictably (error at construction / fallback decision),
      never crash mid-serve; admission control sheds at the bound and
      load-shed degrades to the cheapest feasible allocation; the spread
      sampler is a pure function of its seed;
  (b) the batcher — per-chunk served logits are BITWISE equal to the
      scalar ``forward(qp=)`` path on the same frames, including ragged
      lane counts (pad lanes) and ragged tail chunks (never time-padded);
      the serial per-allocation-group baseline computes identical logits
      through strictly more dispatches;
  (c) the artifact — ``front_from_store`` packs a real finished search's
      front (allocs + objective rows) and the loaded artifact reproduces
      it; ``kernels.ops.bank_step`` dispatches both bank formats.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import quantization as Q
from repro.core import sru_experiment as X
from repro.kernels import ops
from repro.models import sru
from repro import serving as S
from tools import convert_checkpoint as CC


@pytest.fixture(scope="module")
def trained():
    return X.train_small_sru(steps=4)


@pytest.fixture(scope="module")
def artifact(trained, tmp_path_factory):
    """Three-allocation front with strictly ordered (error, cost) rows:
    cheap/high-error .. expensive/low-error."""
    out = tmp_path_factory.mktemp("art")
    names = list(trained.layer_names)
    allocs = [{n: (b, 8) for n in names} for b in (2, 4, 8)]
    objs = [{"error": 9.0, "speedup": 30.0}, {"error": 5.0, "speedup": 9.0},
            {"error": 2.0, "speedup": 3.0}]
    CC.pack_deployment(trained, allocs, str(out), objectives=objs)
    return S.DeploymentArtifact.load(str(out))


@pytest.fixture(scope="module")
def engine(artifact):
    return S.ServingEngine(artifact)


def _requests(artifact, sizes, seed=0, slos=("premium", "standard",
                                             "economy")):
    rng = np.random.default_rng(seed)
    m = artifact.cfg.input_dim
    return [S.Request(rid=i, slo=slos[i % len(slos)],
                      feats=rng.normal(size=(n, m)).astype(np.float32))
            for i, n in enumerate(sizes)]


def _scalar_chunked(trained, alloc, feats, chunk):
    """The parity reference: scalar forward(qp=) per chunk (fresh state
    per chunk — the serving tier's chunk-synchronous decode contract)."""
    qp = trained.qp_for(alloc)
    outs = []
    for s in range(0, feats.shape[0], chunk):
        c = feats[s:s + chunk]
        outs.append(np.asarray(sru.forward(trained.params, trained.cfg,
                                           c[None], qp=qp))[0])
    return np.concatenate(outs)


class TestRouter:
    def test_empty_front_rejected(self, trained, tmp_path):
        CC.pack_deployment(trained, [], str(tmp_path / "empty"))
        art = S.DeploymentArtifact.load(str(tmp_path / "empty"))
        assert art.n_allocs == 0
        with pytest.raises(ValueError, match="empty front"):
            S.Router(art)

    def test_single_allocation_front(self, trained, tmp_path):
        names = list(trained.layer_names)
        CC.pack_deployment(trained, [{n: (8, 8) for n in names}],
                           str(tmp_path / "one"))
        art = S.DeploymentArtifact.load(str(tmp_path / "one"))
        router = S.Router(art)
        for c in router.classes:
            d = router.route(c.name)
            assert d.alloc == 0 and not d.shed

    def test_slo_tiers_map_to_distinct_allocs(self, artifact):
        router = S.Router(artifact)
        assert router.route("premium").alloc == 2    # lowest error
        assert router.route("standard").alloc == 1
        assert router.route("economy").alloc == 0    # cheapest
        assert not any(router.route(c.name).fallback
                       for c in router.classes)

    def test_infeasible_class_falls_back(self, artifact):
        classes = [S.SLOClass("impossible", max_error=0.1,
                              max_cost_bits=1.0)]
        router = S.Router(artifact, classes)
        d = router.route("impossible")
        assert d.fallback and not d.shed
        assert 0 <= d.alloc < artifact.n_allocs

    def test_unknown_class_raises(self, artifact):
        with pytest.raises(KeyError, match="unknown SLO class"):
            S.Router(artifact).route("gold-plated")

    def test_load_shed_degrades_to_cheapest(self, artifact):
        router = S.Router(artifact, max_queue=8, shed_depth=2)
        assert router.route("premium", queue_depth=0).alloc == 2
        d = router.route("premium", queue_depth=3)
        assert d.degraded and d.alloc == 0           # cheapest feasible
        assert router.route("premium", queue_depth=8).shed

    def test_spread_deterministic_under_seed(self, artifact):
        def draw(seed):
            r = S.Router(artifact, seed=seed, spread=True)
            return [r.route("premium").alloc for _ in range(32)]
        assert draw(7) == draw(7)
        assert set(draw(7)) <= {0, 1, 2}

    def test_no_global_numpy_rng(self, artifact):
        state = np.random.get_state()
        r = S.Router(artifact, seed=3, spread=True)
        for _ in range(8):
            r.route("standard")
        after = np.random.get_state()
        assert state[0] == after[0] and np.array_equal(state[1], after[1])


class TestBatcherParity:
    def test_ragged_lanes_and_tails_bitwise(self, trained, artifact,
                                            engine):
        """3 live lanes in a 4-bucket + an 11-frame request (8+3 ragged
        tail): every served logit bitwise equals the chunked scalar
        path."""
        router = S.Router(artifact)
        bat = S.ContinuousBatcher(engine, router, max_lanes=4, chunk=8,
                                  collect=True)
        reqs = _requests(artifact, [8, 11, 16], seed=1)
        for r in reqs:
            bat.submit(r)
        log = bat.run_until_idle()
        assert len(log.completed()) == 3
        for r in reqs:
            alloc = artifact.allocs[log.requests[r.rid].alloc]
            ref = _scalar_chunked(trained, alloc, r.feats, 8)
            assert np.array_equal(bat.results[r.rid], ref), r.rid

    def test_serial_baseline_same_logits_more_dispatches(self, artifact,
                                                         engine):
        router = S.Router(artifact)
        reqs = _requests(artifact, [16] * 6, seed=2)
        cont = S.ContinuousBatcher(engine, router, max_lanes=8, chunk=8,
                                   collect=True)
        ser = S.SerialGroupBatcher(engine, router, max_lanes=8, chunk=8,
                                   collect=True)
        for b in (cont, ser):
            for r in reqs:
                b.submit(S.Request(rid=r.rid, slo=r.slo, feats=r.feats))
        lc, ls = cont.run_until_idle(), ser.run_until_idle()
        for r in reqs:
            assert np.array_equal(cont.results[r.rid], ser.results[r.rid])
        # 3 SLO classes -> 3 live allocations -> 3x the dispatches
        nd_c = sum(s.n_dispatches for s in lc.steps)
        nd_s = sum(s.n_dispatches for s in ls.steps)
        assert nd_s == 3 * nd_c
        # steady state: continuous batching is ONE dispatch per step
        assert all(s.n_dispatches == 1 for s in lc.steps)

    def test_queue_overflow_sheds(self, artifact, engine):
        router = S.Router(artifact, max_queue=2)
        bat = S.ContinuousBatcher(engine, router, max_lanes=2, chunk=8)
        reqs = _requests(artifact, [8] * 5, seed=3)
        decisions = [bat.submit(r) for r in reqs]
        assert [d.shed for d in decisions] == [False, False, True, True,
                                               True]
        log = bat.run_until_idle()
        assert log.shed_count() == 3
        assert len(log.completed()) == 2

    def test_per_step_retire_admit(self, artifact, engine):
        """A short request retires and frees its lane for the next queued
        request while long requests keep flowing — the continuous part of
        continuous batching."""
        router = S.Router(artifact)
        bat = S.ContinuousBatcher(engine, router, max_lanes=2, chunk=8,
                                  collect=True)
        for r in _requests(artifact, [8, 24, 16], seed=4):
            bat.submit(r)
        n_live = []
        while bat.queue or bat.lanes:
            n_live.append(bat.step())
        # step 1: rids 0+1; rid 0 retires, rid 2 admitted into its lane
        assert n_live[0] == 2 and n_live[1] == 2
        assert len(bat.log.completed()) == 3

    def test_metrics_summary_consistent(self, artifact, engine):
        router = S.Router(artifact)
        bat = S.ContinuousBatcher(engine, router, max_lanes=4, chunk=8)
        reqs = _requests(artifact, [16, 8, 11], seed=5)
        for r in reqs:
            bat.submit(r)
        s = bat.run_until_idle().summary()
        assert s["n_completed"] == 3 and s["n_shed"] == 0
        assert s["tokens"] == 16 + 8 + 11
        assert s["tokens_per_s"] > 0 and s["p99_s"] >= s["p50_s"] > 0
        assert s["total_mean_s"] >= s["compute_mean_s"] > 0
        assert sum(s["by_slo"].values()) == 3


class TestArtifact:
    def test_objective_rows_merged(self, artifact):
        assert artifact.n_allocs == 3
        for i, row in enumerate(artifact.objectives):
            assert "cost_bits" in row and "error" in row
        assert artifact.cost_bits(0) < artifact.cost_bits(2)
        assert artifact.error(0) == 9.0

    def test_qp_rows_gather(self, artifact):
        rows = artifact.qp_rows([2, 0, 2])
        assert rows.shape == (3, len(artifact.layer_names), 6)
        assert np.array_equal(rows[0], artifact.qp[2])
        assert np.array_equal(rows[1], artifact.qp[0])

    def test_front_from_store_round_trip(self, trained, tmp_path):
        """A real checkpointed search's front packs into an artifact whose
        allocations and objective rows match the finished search."""
        from repro.core import api
        root = str(tmp_path / "ckpt")
        sess = api.SearchSession(trained, "bitfusion",
                                 ("error", "speedup"),
                                 share_memo=False).run(
            generations=1, pop=4, initial=4, seed=0, checkpoint_dir=root)
        allocs, rows = CC.front_from_store(root, trained)
        assert allocs and len(allocs) == len(rows)
        assert all(set(a) == set(trained.layer_names) for a in allocs)
        errs = [r["error"] for r in rows]
        assert errs == sorted(errs)
        assert all(r["speedup"] > 0 for r in rows)   # un-negated
        out = str(tmp_path / "art")
        CC.pack_deployment(trained, allocs, out, objectives=rows)
        art = S.DeploymentArtifact.load(out)
        assert art.allocs == allocs
        assert [r["error"] for r in art.objectives] == errs

    def test_front_from_store_no_match(self, trained, tmp_path):
        with pytest.raises(FileNotFoundError, match="no loadable"):
            CC.front_from_store(str(tmp_path / "nothing"), trained)

    def test_objectives_length_validated(self, trained, tmp_path):
        names = list(trained.layer_names)
        with pytest.raises(ValueError, match="objective rows"):
            CC.pack_deployment(trained, [{n: (8, 8) for n in names}],
                               str(tmp_path / "x"),
                               objectives=[{}, {}])


class TestDecodeStepAndKernel:
    def test_forward_decode_step_per_alloc_bitwise(self, trained,
                                                   artifact):
        """Engine-level parity: each lane of one decode step == the scalar
        forward on that lane's chunk under that lane's allocation."""
        rng = np.random.default_rng(6)
        P, T, m = artifact.n_allocs, 8, artifact.cfg.input_dim
        feats = rng.normal(size=(P, T, m)).astype(np.float32)
        logits = np.asarray(sru.forward_decode_step(
            artifact.serving_params(), artifact.cfg, jnp.asarray(feats),
            jnp.asarray(artifact.qp), banks=artifact.banks))
        for lane, alloc in enumerate(artifact.allocs):
            ref = np.asarray(sru.forward(
                trained.params, trained.cfg, feats[lane][None],
                qp=trained.qp_for(alloc)))[0]
            assert np.array_equal(logits[lane], ref), lane

    def test_decode_step_rejects_batched_feats(self, artifact):
        with pytest.raises(ValueError, match=r"\(P, T, m\)"):
            sru.forward_decode_step(
                artifact.serving_params(), artifact.cfg,
                jnp.zeros((2, 1, 8, artifact.cfg.input_dim)),
                jnp.asarray(artifact.qp[:2]), banks=artifact.banks)

    def test_per_lane_feats_must_match_population(self, trained):
        """Per-lane feats carry one input per population lane, so their
        lead axis must be the qp stack's P."""
        with pytest.raises(ValueError, match="lead axis"):
            sru.forward_population(
                trained.params, trained.cfg,
                jnp.zeros((3, 1, 4, trained.cfg.input_dim)),
                jnp.zeros((2, len(trained.layer_names), 6)))

    def test_engine_step_is_provably_lane_independent(self, engine):
        """The pad-lane/neighbor-isolation argument in the batcher's
        docstring, machine-checked: the C5 dataflow prover walks the
        jaxpr of a real loaded engine's step at a serving bucket and
        certifies no op contracts or permutes the lane axis."""
        from tools.analysis import dataflow as df
        jx = engine.step_jaxpr(lanes=4, chunk=8)
        rep = df.prove_lane_independence(jx, [0, 0])
        assert rep.ok, "\n".join(v.format() for v in rep.violations)
        assert rep.out_axes == [0]      # logits stay lane-major

    def test_bank_step_dispatches_both_formats(self):
        rng = np.random.default_rng(7)
        m, N, P, T = 16, 24, 3, 5
        w = jnp.asarray(rng.normal(size=(m, N)).astype(np.float32))
        trips = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 1.5)
        packed = Q.build_packed_weight_bank(w, trips)
        bank = Q.dequant_packed_bank(packed)
        x = jnp.asarray(rng.normal(size=(P, T, m)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 4, P).astype(np.int32))
        via_f32 = np.asarray(ops.bank_step(x, bank, idx))
        via_packed = np.asarray(ops.bank_step(x, packed, idx))
        assert via_f32.shape == (P, T, N)
        ref = np.asarray(ops.bank_mxv_pop(x, bank, idx))
        assert np.array_equal(via_f32, ref)
        np.testing.assert_allclose(via_packed, ref, rtol=1e-6, atol=1e-6)