"""Quantized-weight banks: bitwise parity of the gather-don't-requantize
population evaluation (PR 4 tentpole).

Contract: bank rows are built by the exact ``fake_quant_triple`` expression
the on-the-fly paths execute, so a gathered row — and everything downstream
of it: population logits, per-candidate integer error counts, beacon-grouped
errors, Pareto fronts — is bitwise identical to per-lane requantization.
Every assertion here is exact (``==`` / ``array_equal``), never tolerance.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import batched_eval as BE
from repro.core import quantization as Q
from repro.core import sru_experiment as X
from repro.models import sru


@pytest.fixture(scope="module")
def trained():
    return X.train_small_sru(steps=60)


@pytest.fixture(scope="module")
def problem(trained):
    return X.build_problem(trained, X.BITFUSION, ("error", "speedup"))


@pytest.fixture(scope="module")
def banks(trained):
    return trained.make_banks(trained.params)


def _random_allocs(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    return [problem.decode(problem._snap(rng.integers(1, 5, problem.n_var)))
            for _ in range(n)]


# the parity target is the pure-grid fake-quant expression (use_ste=False):
# eval lanes never take weight gradients, and the STE wrapper's float
# round-trip ``x + (q - x)`` can differ from ``q`` in the last ulp at
# clipped elements — pure ``q`` is what every eval weight lane (scalar qp,
# requant, f32 bank, packed bank) computes
_fq = jax.jit(lambda x, s, lo, hi: Q.fake_quant_triple(x, s, lo, hi,
                                                       use_ste=False))


class TestBankRows:
    def test_rows_bitwise_equal_direct_fake_quant(self, trained, banks):
        """Every layer x every menu entry (2/4/8-bit int grids AND the
        16-bit fixed-point grid): the stored bank row equals the direct
        ``fake_quant_triple`` of the weight, bit for bit."""
        cfg = trained.cfg
        for name in cfg.layer_names():
            for k, bits in enumerate(Q.SUPPORTED_BITS):
                clip = (trained.wranges[name] if bits == 16
                        else trained.wclips[(name, bits)])
                s, lo, hi = Q.quant_triple(bits, clip)
                subs = (("fwd", "bwd") if name.startswith("L") else (None,))
                for sub in subs:
                    leaf = (trained.params[name][sub]
                            if sub else trained.params[name])
                    node = banks[name][sub] if sub else banks[name]
                    direct = _fq(leaf["W"], jnp.float32(s), jnp.float32(lo),
                                 jnp.float32(hi))
                    assert np.array_equal(np.asarray(node["W"][k]),
                                          np.asarray(direct)), (name, bits)

    def test_vectors_bitwise_equal_fixed_point(self, trained, banks):
        for i in range(trained.cfg.n_sru_layers):
            for sub in ("fwd", "bwd"):
                dp = trained.params[f"L{i}"][sub]
                node = banks[f"L{i}"][sub]
                assert np.array_equal(np.asarray(node["v"]),
                                      np.asarray(Q.fixed_point_16(dp["v"])))
                assert np.array_equal(np.asarray(node["b"]),
                                      np.asarray(Q.fixed_point_16(dp["b"])))

    @given(st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_bank_rows_property_random_weights(self, seed):
        """Property: for ANY weight tensor and any menu clip, building a
        bank and gathering row k equals quantizing with menu entry k."""
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(size=(7, 13)).astype(np.float32))
        clip = float(rng.uniform(0.1, 4.0))
        trips = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: clip)
        bank = Q.build_weight_bank(w, trips)
        for k, (s, lo, hi) in enumerate(trips):
            direct = _fq(w, jnp.float32(s), jnp.float32(lo),
                         jnp.float32(hi))
            assert np.array_equal(np.asarray(bank[k]), np.asarray(direct))


class TestMenuIndexing:
    def test_menu_index_roundtrip(self):
        """The grid-top value of every menu entry maps back to its slot."""
        for k, bits in enumerate(Q.SUPPORTED_BITS):
            _s, _lo, hi = Q.quant_triple(bits, 1.7)
            assert int(Q.menu_index_from_hi(jnp.float32(hi))) == k

    def test_menu_table_stack_bitwise_equal(self, trained, problem):
        """The banked evaluator's menu-indexed (P, L, 6) stack equals the
        per-candidate ``quant_triples_for`` stack bit for bit."""
        allocs = _random_allocs(problem, 9, seed=4)
        names = list(trained.cfg.layer_names())
        ref = BE.stack_qps([trained.qp_for(a) for a in allocs], names)
        ev = trained.batched_evaluator(use_banks=True)
        fast = ev._stack(allocs)[0][:len(allocs)]
        assert np.array_equal(ref, fast)


class TestPopulationParity:
    @pytest.mark.parametrize("pop", [5, 16])
    def test_forward_population_banked_vs_scalar_bitwise(
            self, trained, problem, banks, pop):
        """``forward_population`` on the bank-gather lane reproduces the
        scalar ``forward(qp=)`` logits bit for bit, lane by lane."""
        allocs = _random_allocs(problem, pop, seed=pop)
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        lb = np.asarray(jax.jit(
            lambda p, f, q, b: sru.forward_population(p, trained.cfg, f, q,
                                                      banks=b))(
            trained.params, feats, qp_stack, banks))
        scalar = jax.jit(
            lambda p, f, qp: sru.forward(p, trained.cfg, f, qp=qp))
        for lane, alloc in enumerate(allocs):
            ls = np.asarray(scalar(trained.params, feats,
                                   trained.qp_for(alloc)))
            assert np.array_equal(lb[lane], ls), f"lane {lane}"

    def test_banked_vs_requant_logits_bitwise(self, trained, problem, banks):
        allocs = _random_allocs(problem, 7, seed=3)
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        lb = jax.jit(lambda p, f, q, b: sru.forward_population(
            p, trained.cfg, f, q, banks=b))(
            trained.params, feats, qp_stack, banks)
        lr = jax.jit(lambda p, f, q: sru.forward_population(
            p, trained.cfg, f, q))(trained.params, feats, qp_stack)
        assert np.array_equal(np.asarray(lb), np.asarray(lr))

    def test_evaluator_banked_errors_bit_identical(self, trained, problem):
        """val_error_batch(use_banks=True) — including the input-layer
        u-bank and the menu-table stack — equals the scalar path exactly
        (odd population exercises bucket padding)."""
        allocs = _random_allocs(problem, 11, seed=8)
        scalar = [trained.val_error(a) for a in allocs]
        assert trained.val_error_batch(allocs, use_banks=True) == scalar
        assert trained.val_error_batch(allocs, use_banks=False) == scalar

    def test_u0_bank_engaged_and_exact(self, trained, problem):
        """The folded evaluator extends banks with the L0 u-bank; its rows
        equal the on-the-fly quantize+matmul bit for bit."""
        ev = trained.batched_evaluator(use_banks=True)
        banks = ev._banks_for(trained.params)
        assert "U" in banks["L0"]["fwd"]          # engaged on this model
        w_t, a_t = trained.qp_menu_tables()
        feats = ev._feats_all
        K = len(Q.SUPPORTED_BITS)

        @jax.jit
        def u_ref(feats, s, lo, hi, w):
            xq = Q.fake_quant_triple(feats, s, lo, hi)
            return jnp.matmul(xq.reshape(-1, xq.shape[-1]), w)

        for ka in range(K):
            s, lo, hi = (jnp.float32(v) for v in a_t[0, ka])
            for kw in range(K):
                ref = u_ref(feats, s, lo, hi, banks["L0"]["fwd"]["W"][kw])
                got = banks["L0"]["fwd"]["U"][ka * K + kw]
                assert np.array_equal(
                    np.asarray(got),
                    np.asarray(ref.reshape(got.shape))), (ka, kw)

    def test_beacon_params_get_their_own_banks(self, trained, problem):
        """errors(allocs, params) under a second parameter set must gather
        from THAT set's banks (parity vs the scalar path under the same
        params), and the evaluator caches one bank per parameter set."""
        import jax
        noisy = jax.tree.map(lambda x: x * 1.01, trained.params)
        allocs = _random_allocs(problem, 5, seed=6)
        scalar = [trained.val_error(a, params=noisy) for a in allocs]
        got = trained.val_error_batch(allocs, params=noisy, use_banks=True)
        assert got == scalar
        ev = trained.batched_evaluator(use_banks=True)
        ev.errors(allocs, trained.params)
        assert len(ev._banks) == 2


class TestBankKernel:
    def test_bank_mxv_pop_matches_gather_matmul(self):
        """The scalar-prefetch Pallas kernel (gather-in-grid) equals the
        jnp take + matmul reference on padded and unpadded shapes."""
        from repro.kernels import ops
        rng = np.random.default_rng(0)
        for P, M, m, K, N in ((4, 8, 16, 4, 128), (3, 5, 23, 4, 288)):
            x = jnp.asarray(rng.normal(size=(P, M, m)).astype(np.float32))
            bank = jnp.asarray(rng.normal(size=(K, m, N)).astype(np.float32))
            idx = jnp.asarray(rng.integers(0, K, P).astype(np.int32))
            got = ops.bank_mxv_pop(x, bank, idx)
            ref = jnp.matmul(x, jnp.take(bank, idx, axis=0))
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-6, atol=1e-6)

    def test_kernel_lane_with_banks(self, trained, problem, banks):
        """use_kernel=True with banks routes the MxV through the bank
        kernel and stays on the XLA lane's numbers."""
        allocs = _random_allocs(problem, 3, seed=11)
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        lk = sru.forward_population(trained.params, trained.cfg, feats,
                                    qp_stack, use_kernel=True, banks=banks)
        lf = sru.forward_population(trained.params, trained.cfg, feats,
                                    qp_stack, banks=banks)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lf),
                                   rtol=1e-5, atol=1e-5)


class TestSearchLevelParity:
    def test_search_front_identical_banked_vs_requant(self, trained):
        """Full NSGA-II: banked evaluator vs requant evaluator — identical
        Pareto fronts, eval counts, bit-identical objectives."""
        kw = dict(n_generations=3, pop_size=6, initial_pop_size=10, seed=5)
        prob_b = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_r = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_b.error_memo = {}
        prob_r.error_memo = {}
        prob_r.batch_error_fn = \
            lambda allocs: trained.val_error_batch(allocs, use_banks=False)
        rb = X.run_search(prob_b, **kw)
        rr = X.run_search(prob_r, **kw)
        key = lambda res: sorted((tuple(i.genome.tolist()),
                                  tuple(i.objectives.tolist()),
                                  float(i.violation)) for i in res.pareto)
        assert key(rb) == key(rr)
        assert rb.n_evals == rr.n_evals
