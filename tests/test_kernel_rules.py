"""Tests for the Pallas kernel verifier (tools/analysis/kernel_rules).

Positive direction: every pallas_call site in src/repro/kernels is
enumerated, exercised by a driver, and passes K1-K4 with zero findings.
Negative direction (detector liveness): seeded defects — a grid that does
not divide the shape, an out-of-bounds bank-row gather, a blown VMEM
budget, a producer that packs high-bits-first — must each be caught by
the matching K-rule.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from tools.analysis import kernel_rules as kr

KERNEL_FUNCS = {"quant_matmul", "unpack_codes", "sru_scan",
                "sru_scan_pop", "bank_mxv_pop", "bank_qmm_pop"}


@pytest.fixture(scope="module")
def kernel_result():
    return kr.run_kernel_checks()


# ---------------------------------------------------------------- clean

def test_sites_enumerated():
    sites = kr.enumerate_sites()
    assert {s.func for s in sites} == KERNEL_FUNCS
    assert all(s.path.startswith("src/repro/kernels/") for s in sites)


def test_real_kernels_pass_all_k_rules(kernel_result):
    findings, report = kernel_result
    assert findings == [], "\n".join(f.format() for f in findings)
    assert {r["function"] for r in report} == KERNEL_FUNCS


def test_report_carries_grid_and_vmem(kernel_result):
    _, report = kernel_result
    for r in report:
        assert r["grid"] and all(g >= 1 for g in r["grid"])
        assert 0 < r["vmem_bytes_est"] <= r["vmem_budget_bytes"]
    by_fn = {r["function"]: r for r in report}
    # the bank kernels ride on a scalar-prefetched gather index
    assert by_fn["bank_mxv_pop"]["num_scalar_prefetch"] == 1
    assert by_fn["bank_qmm_pop"]["num_scalar_prefetch"] == 1
    assert by_fn["sru_scan"]["num_scalar_prefetch"] == 0


def test_k_findings_are_kernel_layer():
    from tools.analysis.core import Finding
    f = Finding("K1", "src/repro/kernels/sru_scan.py", 84, "m")
    assert f.layer == "kernel" and f.to_json()["layer"] == "kernel"


# --------------------------------------------------- seeded defects

def _capture(grid, in_specs, out_specs, out_shapes, operands, nsp=0):
    return kr.PallasCapture(
        path="src/repro/kernels/sru_scan.py", line=160, func="seeded",
        kernel_name="k", grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shapes=out_shapes, num_scalar_prefetch=nsp, operands=operands,
        driver="test")


def test_k1_catches_bad_grid_divisor():
    """End-to-end seeded defect: a real pallas_call traced through the
    capture context with a block that does not divide the shape."""
    with kr.capture_pallas_calls() as caps:
        # 5 % 2 != 0: the last tile would read out of bounds
        pl.pallas_call(
            lambda x_ref, o_ref: None,
            grid=(3,),
            in_specs=[pl.BlockSpec((2,), lambda i: (i,))],
            out_specs=pl.BlockSpec((2,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((5,), jnp.float32),
        )(jnp.zeros((5,), jnp.float32))
    (cap,) = caps
    msgs = kr.check_k1(cap)
    assert msgs and all("not divisible" in m for m in msgs)


def test_k1_catches_rank_and_spec_count_mismatch():
    cap = _capture(
        grid=(2,),
        in_specs=[pl.BlockSpec((2, 2), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((2,), lambda i: (i,))],
        out_shapes=[jax.ShapeDtypeStruct((4,), jnp.float32)],
        operands=(np.zeros((4,), np.float32), np.zeros((4,), np.float32)))
    msgs = kr.check_k1(cap)
    assert any("rank" in m for m in msgs)
    assert any("in_specs" in m for m in msgs)


def test_k2_catches_out_of_range_bank_gather():
    """A scalar-prefetched menu index >= the bank's row count must be
    flagged — this is the exact failure mode of serving a corrupted
    allocation id."""
    bank = np.zeros((4, 6), np.float32)          # 4 menu rows
    idx = np.array([0, 1, 9], np.int32)          # lane 2 gathers row 9
    cap = _capture(
        grid=(3,),
        in_specs=[pl.BlockSpec((1, 6), lambda p, i: (int(i[p]), 0))],
        out_specs=[pl.BlockSpec((1, 6), lambda p, i: (p, 0))],
        out_shapes=[jax.ShapeDtypeStruct((3, 6), jnp.float32)],
        operands=(idx, bank), nsp=1)
    msgs = kr.check_k2(cap)
    assert any("out-of-bounds" in m and "grid (2,)" in m for m in msgs)


def test_k2_in_bounds_gather_is_clean():
    bank = np.zeros((4, 6), np.float32)
    idx = np.array([3, 0, 2], np.int32)
    cap = _capture(
        grid=(3,),
        in_specs=[pl.BlockSpec((1, 6), lambda p, i: (int(i[p]), 0))],
        out_specs=[pl.BlockSpec((1, 6), lambda p, i: (p, 0))],
        out_shapes=[jax.ShapeDtypeStruct((3, 6), jnp.float32)],
        operands=(idx, bank), nsp=1)
    assert kr.check_k2(cap) == []


def test_k2_catches_nondeterministic_index_map():
    state = {"n": 0}

    def impure(i):
        state["n"] += 1
        return (state["n"],)

    cap = _capture(
        grid=(2,),
        in_specs=[pl.BlockSpec((2,), impure)],
        out_specs=[pl.BlockSpec((2,), lambda i: (i,))],
        out_shapes=[jax.ShapeDtypeStruct((4,), jnp.float32)],
        operands=(np.zeros((4,), np.float32),))
    msgs = kr.check_k2(cap)
    assert any("non-deterministic" in m or "out-of-bounds" in m
               for m in msgs)


def test_k3_flags_oversized_working_set():
    big = np.zeros((1024, 1024), np.float32)     # 4 MiB block
    cap = _capture(
        grid=(1,),
        in_specs=[pl.BlockSpec((1024, 1024), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((1024, 1024), lambda i: (0, 0))],
        out_shapes=[jax.ShapeDtypeStruct((1024, 1024), jnp.float32)],
        operands=(big,))
    est = kr.estimate_vmem_bytes(cap)
    assert est == 2 * 2 * big.nbytes            # in+out, double-buffered
    assert kr.check_k3(cap, budget_bytes=2**20)  # 1 MiB budget: blown
    assert kr.check_k3(cap, budget_bytes=32 * 2**20) == []


def test_k4_catches_high_bits_first_producer():
    """Seeded layout defect: a pack_weights that stores codes high-bits-
    first. The kernel's _unpack_block reads low-bits-first, so K4 must
    fail both the round-trip and the bank-parity checks."""

    def bad_pack(q, bits):
        if bits == 8:
            return q.astype(jnp.int8)
        per = 8 // bits
        K, N = q.shape
        pad = (-K) % per
        if pad:
            q = jnp.concatenate([q, jnp.zeros((pad, N), q.dtype)])
        u = (q.astype(jnp.int32) & ((1 << bits) - 1)).astype(jnp.uint8)
        u = u.reshape(-1, per, N)
        shifts = jnp.arange(per - 1, -1, -1, dtype=jnp.uint8) * bits
        return jnp.bitwise_or.reduce(
            (u << shifts[None, :, None]).astype(jnp.uint8),
            axis=1).astype(jnp.int8)

    findings = kr.check_k4(pack_fn=bad_pack)
    assert findings and all(f.rule == "K4" for f in findings)
    assert any("round-trip broken" in f.message for f in findings)
    assert all(f.path == "src/repro/kernels/quant_matmul.py"
               for f in findings)


def test_k4_real_producers_agree():
    assert kr.check_k4() == []


def test_k0_fires_when_drivers_are_missing():
    findings, report = kr.run_kernel_checks(drivers=[])
    assert report == []
    assert {f.rule for f in findings if "not exercised" in f.message} \
        == {"K0"}
    assert len([f for f in findings if f.rule == "K0"]) \
        >= len(KERNEL_FUNCS)


def test_k0_fires_on_crashing_driver():
    def boom():
        raise RuntimeError("driver exploded")

    findings, _ = kr.run_kernel_checks(drivers=[("boom", boom)])
    assert any(f.rule == "K0" and "crashed" in f.message for f in findings)
