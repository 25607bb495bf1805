"""Packed-integer bank lane (PR 8 tentpole): int codes + scales on disk
and in HBM, f32 fake-quant rows after dequantization — bit for bit.

Contract under test: ``build_packed_weight_bank`` / ``dequant_packed_bank``
reproduce the f32 ``build_weight_bank`` stack exactly (int grids trivially;
the 16-bit row because |codes| < 2^24 round-trips int16 -> f32 losslessly),
so the packed evaluator lane, the ``bank_qmm_pop`` kernel lane and the
``tools/convert_checkpoint.py`` artifact all sit on the same numbers as the
scalar ``forward(qp=)`` path. Weight-row and error-count assertions are
exact; only the Pallas-kernel logits comparison is float-tolerance (its f32
accumulation order differs from jnp.matmul).
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.core import batched_eval as BE
from repro.core import quantization as Q
from repro.core import sru_experiment as X
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.quant_matmul import unpack_codes
from repro.models import sru
from tools import convert_checkpoint as CC


@pytest.fixture(scope="module")
def trained():
    return X.train_small_sru(steps=40)


@pytest.fixture(scope="module")
def problem(trained):
    return X.build_problem(trained, X.BITFUSION, ("error", "speedup"))


@pytest.fixture(scope="module")
def banks_f32(trained):
    return trained.make_banks(trained.params)


@pytest.fixture(scope="module")
def banks_packed(trained):
    return trained.make_packed_banks(trained.params)


def _random_allocs(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    return [problem.decode(problem._snap(rng.integers(1, 5, problem.n_var)))
            for _ in range(n)]


def _w_nodes(cfg, banks):
    for name in cfg.layer_names():
        if name.startswith("L"):
            for d in ("fwd", "bwd"):
                yield f"{name}/{d}", banks[name][d]
        else:
            yield name, banks[name]


class TestPackedBankParity:
    def test_dequant_bitwise_equals_f32_bank(self, trained, banks_f32,
                                             banks_packed):
        """Per layer x per menu entry (2/4/8-bit int grids AND the 16-bit
        fixed-point row): dequantized packed rows == f32 bank rows, bit
        for bit."""
        f32_nodes = dict(_w_nodes(trained.cfg, banks_f32))
        for key, node in _w_nodes(trained.cfg, banks_packed):
            rows = np.asarray(Q.dequant_packed_bank(node["W"]))
            ref = np.asarray(f32_nodes[key]["W"])
            for k, bits in enumerate(Q.SUPPORTED_BITS):
                assert np.array_equal(rows[k], ref[k]), (key, bits)

    def test_container_dtypes_and_shapes(self, trained, banks_packed):
        """Codes live in their natural containers, packed along K."""
        for key, node in _w_nodes(trained.cfg, banks_packed):
            w = node["W"]
            k_dim, n = w["q8"].shape
            assert w["q2"].dtype == jnp.int8 and w["q4"].dtype == jnp.int8
            assert w["q8"].dtype == jnp.int8
            assert w["q16"].dtype == jnp.int16
            assert w["q2"].shape == (-(-k_dim // 4), n), key
            assert w["q4"].shape == (-(-k_dim // 2), n), key
            assert w["q16"].shape == (k_dim, n), key
            assert w["scale"].shape == (len(Q.SUPPORTED_BITS), 1), key

    def test_vectors_stay_fixed_point(self, trained, banks_packed):
        """16-bit recurrent vectors/biases are format-independent."""
        for i in range(trained.cfg.n_sru_layers):
            for sub in ("fwd", "bwd"):
                dp = trained.params[f"L{i}"][sub]
                node = banks_packed[f"L{i}"][sub]
                assert np.array_equal(np.asarray(node["v"]),
                                      np.asarray(Q.fixed_point_16(dp["v"])))
                assert np.array_equal(np.asarray(node["b"]),
                                      np.asarray(Q.fixed_point_16(dp["b"])))

    def test_packed_at_least_4x_smaller(self, trained, banks_f32,
                                        banks_packed):
        """ISSUE acceptance: packed weight banks >= 4x smaller in bytes."""
        f32_nodes = dict(_w_nodes(trained.cfg, banks_f32))
        tot_p = tot_f = 0
        for key, node in _w_nodes(trained.cfg, banks_packed):
            tot_p += Q.packed_bank_nbytes(node["W"])
            f = f32_nodes[key]["W"]
            tot_f += f.size * f.dtype.itemsize
        assert tot_f / tot_p >= 4.0, (tot_f, tot_p)

    def test_build_packed_validates(self):
        trips = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 1.0)
        with pytest.raises(ValueError, match="2-D"):
            Q.build_packed_weight_bank(jnp.zeros((3,)), trips)
        with pytest.raises(ValueError, match="menu"):
            Q.build_packed_weight_bank(jnp.zeros((4, 4)), trips[:2])


class TestUnpackRoundTrip:
    @given(st.integers(0, 2 ** 31 - 1), st.sampled_from([2, 4, 8]))
    @settings(max_examples=15, deadline=None)
    def test_unpack_block_roundtrips_ref_packing(self, seed, bits):
        """Property: for any codes in the bits-range (most-negative code
        forced present), ``ref.pack_weights`` then the kernel-side
        ``_unpack_block`` (run in a Pallas call) recovers them exactly —
        and agrees with ``ref.unpack_weights``."""
        rng = np.random.default_rng(seed)
        lo, hi = Q.INT_RANGES[bits]
        K = int(rng.integers(1, 6)) * (8 // bits)
        N = int(rng.integers(1, 9))
        codes = rng.integers(lo, hi + 1, (K, N)).astype(np.int8)
        codes[rng.integers(0, K), rng.integers(0, N)] = lo  # most-negative
        packed = kref.pack_weights(jnp.asarray(codes), bits)
        via_block = np.asarray(
            unpack_codes(packed, bits, interpret=True))[:K]
        via_ref = np.asarray(kref.unpack_weights(packed, bits, K))
        assert np.array_equal(via_block, codes)
        assert np.array_equal(via_ref, codes)

    def test_most_negative_code_survives_16_bit(self):
        """int16 container: the full code range round-trips through the
        f32 dequant (|codes| <= 32768 < 2^24)."""
        codes = jnp.asarray([[-32768], [32767]], jnp.int16)
        back = codes.astype(jnp.float32).astype(jnp.int32)
        assert np.array_equal(np.asarray(back).ravel(), [-32768, 32767])


class TestPackedForwardParity:
    @pytest.mark.parametrize("pop", [5, 16])
    def test_forward_population_packed_vs_f32_bitwise(
            self, trained, problem, banks_f32, banks_packed, pop):
        """ISSUE acceptance: packed lane bit-identical to the fake-quant
        bank lane at pop 5 and 16."""
        allocs = _random_allocs(problem, pop, seed=pop)
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        fwd = jax.jit(lambda p, f, q, b: sru.forward_population(
            p, trained.cfg, f, q, banks=b))
        lp = np.asarray(fwd(trained.params, feats, qp_stack, banks_packed))
        lf = np.asarray(fwd(trained.params, feats, qp_stack, banks_f32))
        assert np.array_equal(lp, lf)

    def test_packed_kernel_lane_matches_fused(self, trained, problem,
                                              banks_packed):
        """use_kernel=True routes the packed MxV through ``bank_qmm_pop``
        (in-kernel dequant); float tolerance vs the fused packed lane."""
        allocs = _random_allocs(problem, 3, seed=11)
        qp_stack = jnp.asarray(BE.stack_qps(
            [trained.qp_for(a) for a in allocs],
            list(trained.cfg.layer_names())))
        feats = trained.val_subsets[0][0]
        lk = sru.forward_population(trained.params, trained.cfg, feats,
                                    qp_stack, use_kernel=True,
                                    banks=banks_packed)
        lf = sru.forward_population(trained.params, trained.cfg, feats,
                                    qp_stack, banks=banks_packed)
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lf),
                                   rtol=1e-5, atol=1e-5)

    def test_bank_qmm_pop_matches_dequant_gather(self):
        """The kernel equals gather-from-dequantized-bank + matmul on
        padded and unpadded shapes (exact: same f32 products)."""
        rng = np.random.default_rng(2)
        for P, M, m, N in ((4, 8, 16, 128), (3, 5, 24, 40)):
            w = jnp.asarray(rng.normal(size=(m, N)).astype(np.float32))
            trips = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 1.5)
            packed = Q.build_packed_weight_bank(w, trips)
            bank = Q.dequant_packed_bank(packed)
            x = jnp.asarray(rng.normal(size=(P, M, m)).astype(np.float32))
            idx = jnp.asarray(rng.integers(0, 4, P).astype(np.int32))
            got = ops.bank_qmm_pop(x, packed, idx)
            ref = ops.bank_mxv_pop(x, bank, idx)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=1e-6, atol=1e-6)

    def test_bank_qmm_pop_validates(self):
        from repro.kernels import sru_scan as SS
        trips = Q.menu_triples(Q.SUPPORTED_BITS, lambda b: 1.0)
        packed = Q.build_packed_weight_bank(jnp.zeros((8, 16)), trips)
        x = jnp.zeros((2, 4, 8))
        idx = jnp.zeros((2,), jnp.int32)
        bad = dict(packed, q8=packed["q8"][:, :8])
        with pytest.raises(ValueError):
            SS.bank_qmm_pop(x, bad, idx, block=(4, 8))
        with pytest.raises(ValueError):
            SS.bank_qmm_pop(x, packed, idx, block=(3, 16))


class TestPackedEvaluator:
    def test_bank_format_packed_errors_bit_identical(self, trained,
                                                     problem):
        """val_error_batch(bank_format='packed') == f32-banked == scalar,
        per candidate (odd population exercises bucket padding)."""
        allocs = _random_allocs(problem, 7, seed=9)
        scalar = [trained.val_error(a) for a in allocs]
        assert trained.val_error_batch(
            allocs, bank_format="packed") == scalar
        assert trained.val_error_batch(allocs, use_banks=True) == scalar

    @pytest.mark.parametrize("beacons", [False, True])
    def test_search_session_packed_front_identical(self, trained, beacons):
        """SearchSession(bank_format='packed') scores through the packed
        banks (beacon groups too) and finds the f32 session's front."""
        from repro.core.api import SearchSession
        kw = dict(generations=2, pop=6, initial=8, seed=3, beacons=beacons,
                  retrain_steps=4)
        fronts = {}
        for fmt in ("f32", "packed"):
            sess = SearchSession(trained, "bitfusion", ("error", "speedup"),
                                 share_memo=False, bank_format=fmt)
            res = sess.run(**kw)
            fronts[fmt] = res.front_key()
        assert fronts["packed"] == fronts["f32"]

    def test_bank_format_validation(self, trained):
        common = dict(layer_names=list(trained.layer_names),
                      val_subsets=trained.val_subsets,
                      make_qp=trained.qp_for,
                      forward_pop=lambda *a, **k: None)
        with pytest.raises(ValueError, match="bank_format"):
            BE.PopulationEvaluator(bank_format="int3", **common)
        with pytest.raises(ValueError, match="make_packed_banks"):
            BE.PopulationEvaluator(bank_format="packed", use_banks=True,
                                   **common)   # no make_packed_banks
        with pytest.raises(ValueError, match="packed"):
            BE.PopulationEvaluator(bank_format="packed", use_banks=False,
                                   make_packed_banks=lambda p: {},
                                   **common)


class TestConvertCheckpoint:
    @pytest.fixture(scope="class")
    def artifact(self, trained, tmp_path_factory):
        out = tmp_path_factory.mktemp("deploy")
        names = list(trained.layer_names)
        allocs = [{n: (b, 8) for n in names} for b in (2, 4, 8, 16)]
        manifest = CC.pack_deployment(trained, allocs, str(out))
        return out, allocs, manifest

    def test_reload_bit_identical(self, trained, banks_packed, artifact):
        out, _allocs, _manifest = artifact
        _m, banks, _x = CC.load_deployment(str(out))
        fresh = jax.tree_util.tree_leaves_with_path(banks_packed)
        got = jax.tree_util.tree_leaves_with_path(banks)
        assert len(fresh) == len(got)
        for (pf, lf), (pg, lg) in zip(fresh, got):
            assert jax.tree_util.keystr(pf) == jax.tree_util.keystr(pg)
            a, b = np.asarray(lf), np.asarray(lg)
            assert a.dtype == b.dtype and np.array_equal(a, b), pf

    def test_manifest_bytes_ratio(self, artifact):
        _out, _allocs, manifest = artifact
        assert manifest["bytes"]["ratio"] >= 4.0

    def test_serve_from_artifact_matches_scalar(self, trained, artifact):
        """ISSUE acceptance end-to-end: the shipped artifact + its minimal
        serving params reproduce the scalar path's logits bit for bit."""
        out, allocs, _manifest = artifact
        m, banks, extras = CC.load_deployment(str(out))
        params = CC.serving_params(m, extras)
        qp = jnp.asarray(CC.qp_stack(m))
        feats = trained.val_subsets[0][0]
        lb = np.asarray(sru.forward_population(params, trained.cfg, feats,
                                               qp, banks=banks))
        for lane, alloc in enumerate(allocs):
            ls = np.asarray(sru.forward(trained.params, trained.cfg, feats,
                                        qp=trained.qp_for(alloc)))
            assert np.array_equal(lb[lane], ls), f"lane {lane}"

    def test_corrupt_payload_detected(self, trained, artifact, tmp_path):
        import shutil
        from repro.core import durable_io
        out, _allocs, manifest = artifact
        bad = tmp_path / "bad"
        shutil.copytree(out, bad)
        p = bad / manifest["payload"]
        data = bytearray(p.read_bytes())
        data[-1] ^= 0xFF
        p.write_bytes(bytes(data))
        with pytest.raises(durable_io.CorruptFileError):
            CC.load_deployment(str(bad))


class TestQuantMatmulErrors:
    """Satellite: shape/packing violations raise ValueError (survive
    ``python -O``), naming the offending shape and block."""

    def test_block_mismatch_raises_value_error(self):
        from repro.kernels import quant_matmul as QM
        x = jnp.zeros((4, 16))
        w = kref.pack_weights(jnp.zeros((16, 8), jnp.int8), 8)
        s = jnp.ones((8,))
        with pytest.raises(ValueError, match="divide the block"):
            QM.quant_matmul(x, w, s, bits=8, block=(3, 8, 16))

    def test_packing_misalignment_raises_value_error(self):
        from repro.kernels import quant_matmul as QM
        x = jnp.zeros((4, 16))
        w = kref.pack_weights(jnp.zeros((16, 8), jnp.int8), 4)
        s = jnp.ones((8,))
        with pytest.raises(ValueError, match="codes/byte"):
            QM.quant_matmul(x, w, s, bits=4, block=(4, 8, 1))

    def test_not_assertion_error(self):
        """The old bare asserts vanished under -O; ValueError cannot."""
        from repro.kernels import quant_matmul as QM
        x = jnp.zeros((4, 16))
        w = kref.pack_weights(jnp.zeros((16, 8), jnp.int8), 8)
        s = jnp.ones((8,))
        try:
            QM.quant_matmul(x, w, s, bits=8, block=(3, 8, 16))
        except ValueError:
            pass
        except AssertionError:  # pragma: no cover
            pytest.fail("shape check is still a bare assert")


class TestInterpretDefault:
    """Satellite: ops wrappers pick interpret from the backend instead of
    hard-coding True."""

    def test_resolve_follows_backend(self):
        expect = jax.default_backend() == "cpu"
        assert ops._resolve_interpret(None) is expect

    def test_explicit_override_wins(self):
        assert ops._resolve_interpret(True) is True
        assert ops._resolve_interpret(False) is False

    def test_wrappers_default_none(self):
        import inspect
        for fn in (ops.quant_matmul, ops.sru_scan, ops.bank_mxv_pop,
                   ops.bank_qmm_pop):
            sig = inspect.signature(fn)
            assert sig.parameters["interpret"].default is None, fn
