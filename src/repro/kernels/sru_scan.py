"""Pallas TPU kernel: fused SRU element-wise time recurrence.

The SRU's MxV part is time-parallel (a plain MXU matmul, done outside); what
remains is the element-wise recurrence over T. Executed step-by-step from
HBM this re-reads the gate vectors and state every step; the kernel keeps
the state c and the per-channel vectors v_f, v_r, b_f, b_r resident in VMEM
across all T steps and streams u tiles through — one HBM pass over the data.

Outputs are (h, r, c_last): the reset gate r is emitted alongside h because
the SRU highway connection h_t = r_t*c_t + (1-r_t)*x_t needs it whenever the
layer input width equals the hidden width — the caller applies the skip
outside the kernel (x is not streamed through VMEM).

Grid layouts:
- ``sru_scan``: grid (B/bb, n/bn); each program owns a (bb, T, bn) tile of
  the three u streams and scans T in a fori_loop with the carry in
  registers/VMEM.
- ``sru_scan_pop``: grid (P, B/bb, n/bn) — the leading *population* axis
  maps one GA candidate (one quantization allocation) per grid step, so a
  whole population of quantized forwards feeds the compute units directly
  instead of vmapping over ``pallas_call``. Block shapes are
  (1, bb, T, bn) for the streams; the per-channel vectors are shared across
  the population (same underlying weights, per-candidate quantization is
  applied to the u streams upstream).
- ``bank_mxv_pop``: grid (P, M/bm, N/bn) over a *quantized-weight bank* —
  the (K, m, N) stack of the K menu-entry fake-quantizations of one weight
  matrix. The per-lane bank row index is a scalar-prefetch operand
  (``PrefetchScalarGridSpec``), so the bank BlockSpec's index_map reads
  ``idx_ref[p]`` and each grid step DMAs the *selected* row's (m, bn) tile
  straight from the bank — gather-don't-requantize: no per-lane quantize
  pass, and no (P, m, N) expanded weight array ever exists in HBM.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.quant_matmul import _unpack_block


def _rows(*vecs):
    """(n,) channel vectors as (1, n) rows: a 1-D block has no legal TPU
    layout, a (1, bn) block of a (1, n) array does."""
    return tuple(v.reshape(1, -1) for v in vecs)


def _sru_kernel(uw_ref, uf_ref, ur_ref, vf_ref, vr_ref, bf_ref, br_ref,
                h_ref, r_ref, cl_ref):
    T = uw_ref.shape[1]
    vf = vf_ref[...]
    vr = vr_ref[...]
    bf = bf_ref[...]
    br = br_ref[...]
    c0 = jnp.zeros((uw_ref.shape[0], uw_ref.shape[2]), jnp.float32)

    def body(t, c):
        uw_t = uw_ref[:, t, :]
        uf_t = uf_ref[:, t, :]
        ur_t = ur_ref[:, t, :]
        f = jax.nn.sigmoid(uf_t + vf * c + bf)
        r = jax.nn.sigmoid(ur_t + vr * c + br)
        c_new = f * c + (1.0 - f) * uw_t
        h_ref[:, t, :] = r * c_new
        r_ref[:, t, :] = r
        return c_new

    c_last = jax.lax.fori_loop(0, T, body, c0)
    cl_ref[...] = c_last


def sru_scan(uw, uf, ur, v_f, v_r, b_f, b_r,
             block: Tuple[int, int] = (8, 128), interpret: bool = False):
    """uw/uf/ur: (B, T, n) f32. v/b: (n,) f32.
    Returns (h (B,T,n), r (B,T,n), c_last (B,n)).

    B and n must divide the block sizes (ops.sru_scan pads for you)."""
    B, T, n = uw.shape
    bb, bn = block
    if B % bb or n % bn:
        raise ValueError(
            f"sru_scan shapes must divide the block: u {uw.shape}, block "
            f"(bb, bn)={block}; ops.sru_scan pads for you")
    grid = (B // bb, n // bn)
    stream = pl.BlockSpec((bb, T, bn), lambda i, j: (i, 0, j))
    vec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    return pl.pallas_call(
        _sru_kernel,
        grid=grid,
        in_specs=[stream, stream, stream, vec, vec, vec, vec],
        out_specs=[stream, stream,
                   pl.BlockSpec((bb, bn), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((B, T, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, n), jnp.float32),
                   jax.ShapeDtypeStruct((B, n), jnp.float32)],
        interpret=interpret,
    )(uw, uf, ur, *_rows(v_f, v_r, b_f, b_r))


def _sru_kernel_pop(uw_ref, uf_ref, ur_ref, vf_ref, vr_ref, bf_ref, br_ref,
                    h_ref, r_ref, cl_ref):
    # stream blocks are (1, bb, T, bn): one population lane per grid step
    T = uw_ref.shape[2]
    vf = vf_ref[...]
    vr = vr_ref[...]
    bf = bf_ref[...]
    br = br_ref[...]
    c0 = jnp.zeros((uw_ref.shape[1], uw_ref.shape[3]), jnp.float32)

    def body(t, c):
        uw_t = uw_ref[0, :, t, :]
        uf_t = uf_ref[0, :, t, :]
        ur_t = ur_ref[0, :, t, :]
        f = jax.nn.sigmoid(uf_t + vf * c + bf)
        r = jax.nn.sigmoid(ur_t + vr * c + br)
        c_new = f * c + (1.0 - f) * uw_t
        h_ref[0, :, t, :] = r * c_new
        r_ref[0, :, t, :] = r
        return c_new

    c_last = jax.lax.fori_loop(0, T, body, c0)
    cl_ref[...] = c_last[None]


def _bank_mxv_kernel(idx_ref, x_ref, bank_ref, o_ref):
    # idx_ref is the scalar-prefetch operand; the gather already happened in
    # bank_ref's index_map, so the body is a plain blocked matmul
    del idx_ref
    o_ref[0] = jnp.dot(x_ref[0], bank_ref[0],
                       preferred_element_type=jnp.float32)


def bank_mxv_pop(x, bank, idx, block: Tuple[int, int] = (8, 128),
                 interpret: bool = False):
    """Population MxV against a quantized-weight bank, gather-in-grid.

    x: (P, M, m) f32 per-lane quantized activations; bank: (K, m, N) f32 —
    row k is the weight fake-quantized to menu entry k; idx: (P,) int32 —
    each lane's menu index. Returns (P, M, N) with
    ``out[p] = x[p] @ bank[idx[p]]``.

    ``idx`` rides in as a scalar-prefetch operand so the bank BlockSpec's
    index_map can select the row per grid step: the kernel streams the
    CHOSEN bank tile from HBM instead of a per-lane requantized (or
    pre-gathered) (P, m, N) weight array. M and N must divide the block
    sizes (ops.bank_mxv_pop pads for you)."""
    P, M, m = x.shape
    K, m2, N = bank.shape
    if m != m2 or idx.shape != (P,):
        raise ValueError(
            f"bank_mxv_pop operand mismatch: x {x.shape}, bank "
            f"{bank.shape}, idx {idx.shape}")
    bm, bn = block
    if M % bm or N % bn:
        raise ValueError(
            f"bank_mxv_pop shapes must divide the block: x {x.shape}, "
            f"N={N}, block (bm, bn)={block}; ops.bank_mxv_pop pads for you")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, M // bm, N // bn),
        in_specs=[pl.BlockSpec((1, bm, m), lambda p, i, j, idx_ref:
                               (p, i, 0)),
                  pl.BlockSpec((1, m, bn), lambda p, i, j, idx_ref:
                               (idx_ref[p], 0, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda p, i, j, idx_ref:
                               (p, i, j)),
    )
    return pl.pallas_call(
        _bank_mxv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, M, N), jnp.float32),
        interpret=interpret,
    )(idx, x, bank)


def _bank_qmm_kernel(idx_ref, x_ref, q2_ref, q4_ref, q8_ref, q16_ref,
                     s_ref, o_ref, w_scr):
    # the containers and the (|menu|, bn) scale tile are menu-independent
    # blocks; only the lane's selected container is decoded (same
    # _unpack_block as quant_matmul) and scaled by its menu row
    m = x_ref.shape[2]
    sel = idx_ref[pl.program_id(0)]
    for k, (ref, bits) in enumerate(((q2_ref, 2), (q4_ref, 4),
                                     (q8_ref, 8), (q16_ref, 16))):
        @pl.when(sel == k)
        def _(ref=ref, bits=bits, k=k):
            if bits in (2, 4):
                _unpack_block(ref[...], bits, w_scr)
                codes = w_scr[pl.ds(0, m), :]
            else:
                codes = ref[...].astype(jnp.float32)
            w = codes * s_ref[k:k + 1, :]
            o_ref[0] = jnp.dot(x_ref[0], w,
                               preferred_element_type=jnp.float32)


def bank_qmm_pop(x, packed, idx, block: Tuple[int, int] = (8, 128),
                 interpret: bool = False):
    """Population MxV against a PACKED quantized-weight bank — the int-
    container twin of ``bank_mxv_pop``.

    x: (P, M, m) f32 per-lane quantized activations; ``packed``: a
    ``quantization.build_packed_weight_bank`` dict for a (m, N) weight
    ({"q2","q4","q8","q16","scale"} — sub-byte codes packed along the
    contraction axis in the ``ref.pack_weights`` layout); idx: (P,) int32
    menu indices ordered like ``SUPPORTED_BITS`` (0 -> 2-bit ... 3 -> 16-bit).
    Returns (P, M, N) f32 with ``out[p] = x[p] @ dequant(packed)[idx[p]]``.

    The whole (|menu|, bn) scale tile rides along (a one-row block breaks
    the TPU's (8, 128) tiling) and the kernel picks the lane's row and
    container by the scalar-prefetched menu index; the containers stream in at
    ~3.75 bytes/weight total — less than the f32 bank lane's 4 bytes/weight
    for the gathered row — and dequantization runs on the VPU in-kernel.
    M and N must divide the block sizes (ops.bank_qmm_pop pads for you)."""
    q2, q4, q8, q16 = packed["q2"], packed["q4"], packed["q8"], packed["q16"]
    scale = packed["scale"]
    P, M, m = x.shape
    N = q8.shape[1]
    if q8.shape[0] != m or q16.shape != q8.shape or idx.shape != (P,):
        raise ValueError(
            f"bank_qmm_pop container mismatch: x {x.shape}, q8 {q8.shape}, "
            f"q16 {q16.shape}, idx {idx.shape}")
    if any(c.shape[1] != N for c in (q2, q4, scale)):
        raise ValueError(
            f"bank_qmm_pop output-channel mismatch: N={N} but q2 {q2.shape}, "
            f"q4 {q4.shape}, scale {scale.shape}")
    bm, bn = block
    if M % bm or N % bn:
        raise ValueError(
            f"bank_qmm_pop shapes must divide the block: x {x.shape}, N={N},"
            f" block {block}; ops.bank_qmm_pop pads for you")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, M // bm, N // bn),
        in_specs=[pl.BlockSpec((1, bm, m), lambda p, i, j, idx_ref:
                               (p, i, 0)),
                  pl.BlockSpec((q2.shape[0], bn), lambda p, i, j, idx_ref:
                               (0, j)),
                  pl.BlockSpec((q4.shape[0], bn), lambda p, i, j, idx_ref:
                               (0, j)),
                  pl.BlockSpec((m, bn), lambda p, i, j, idx_ref: (0, j)),
                  pl.BlockSpec((m, bn), lambda p, i, j, idx_ref: (0, j)),
                  pl.BlockSpec((scale.shape[0], bn),
                               lambda p, i, j, idx_ref: (0, j))],
        out_specs=pl.BlockSpec((1, bm, bn), lambda p, i, j, idx_ref:
                               (p, i, j)),
        # decoded sub-byte codes: room for the longer of the two unpacks
        scratch_shapes=[pltpu.VMEM(
            (max(4 * q2.shape[0], 2 * q4.shape[0]), bn), jnp.float32)],
    )
    return pl.pallas_call(
        _bank_qmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, M, N), jnp.float32),
        interpret=interpret,
    )(idx, x, q2, q4, q8, q16, scale)


def sru_scan_pop(uw, uf, ur, v_f, v_r, b_f, b_r,
                 block: Tuple[int, int] = (8, 128),
                 interpret: bool = False):
    """Population-axis SRU scan: uw/uf/ur are (P, B, T, n) f32 — one
    quantization candidate per leading lane — and v/b: (n,) f32 shared
    across lanes. Returns (h (P,B,T,n), r (P,B,T,n), c_last (P,B,n)).

    The grid is (P, B/bb, n/bn): the population axis is a first-class grid
    dimension, so on real accelerators P candidates stream through the MXU
    pipeline back-to-back rather than being expanded by a vmap-of-kernels.
    B and n must divide the block sizes (ops.sru_scan_pop pads for you)."""
    P, B, T, n = uw.shape
    bb, bn = block
    if B % bb or n % bn:
        raise ValueError(
            f"sru_scan_pop shapes must divide the block: u {uw.shape}, "
            f"block (bb, bn)={block}; ops.sru_scan_pop pads for you")
    grid = (P, B // bb, n // bn)
    stream = pl.BlockSpec((1, bb, T, bn), lambda p, i, j: (p, i, 0, j))
    vec = pl.BlockSpec((1, bn), lambda p, i, j: (0, j))
    return pl.pallas_call(
        _sru_kernel_pop,
        grid=grid,
        in_specs=[stream, stream, stream, vec, vec, vec, vec],
        out_specs=[stream, stream,
                   pl.BlockSpec((1, bb, bn), lambda p, i, j: (p, i, j))],
        out_shape=[jax.ShapeDtypeStruct((P, B, T, n), jnp.float32),
                   jax.ShapeDtypeStruct((P, B, T, n), jnp.float32),
                   jax.ShapeDtypeStruct((P, B, n), jnp.float32)],
        interpret=interpret,
    )(uw, uf, ur, *_rows(v_f, v_r, b_f, b_r))
