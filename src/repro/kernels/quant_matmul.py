"""Pallas TPU kernel: activation x packed sub-byte weight matmul.

TPU adaptation of the paper's mixed-precision MACs (DESIGN.md): SiLago splits
a 16-bit multiplier into 4-bit Vedic sub-multipliers and Bitfusion composes
bit-bricks; the TPU MXU has no such mechanism, so low-bit weights pay off via
*memory*: int4/int2 weights are stored packed in int8 containers in HBM,
streamed tile-by-tile into VMEM, unpacked + dequantized on the VPU, and fed
to the MXU at full precision. HBM weight traffic drops 4x/8x vs bf16 — which
is exactly the dominant term of the decode roofline.

Packing: along K (contraction) axis, ``per = 8 // bits`` values per byte,
low bits first (see ref.unpack_weights). Scales are per-output-channel.

Block layout: grid (M/bm, N/bn, K/bk), K innermost for accumulation; blocks
are (8,128)-lane aligned and MXU-sized (bm, bn, bk multiples of 128 by
default). The f32 accumulator lives in the output VMEM block across K steps.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _unpack_block(packed, bits: int, out_ref):
    """Unpack a (R, bn) int8 container of ``bits``-wide codes into
    ``out_ref`` as f32 codes, in the ``ref.unpack_weights`` row order: row
    b*per + j is slot j of byte b, low bits first.

    Each slot j is decoded on whole (R, bn) int32 tiles and goes to rows
    j, j+per, ... by a row-strided store: Mosaic has no sublane interleave,
    so the row order is built in VMEM instead of by a reshape."""
    per = 8 // bits
    rows = packed.shape[0]
    u = packed.astype(jnp.int32) & 0xFF
    for j in range(per):
        v = (u >> (bits * j)) & ((1 << bits) - 1)
        v = v - ((v >> (bits - 1)) & 1) * (1 << bits)            # sign-ext
        out_ref[pl.ds(j, rows, stride=per), :] = v.astype(jnp.float32)


def unpack_codes(packed, bits: int, interpret: bool = False):
    """Decode a whole (R, N) int8 container with the kernels' own
    ``_unpack_block``, as one block: (R * 8 // bits, N) f32 codes. The
    layout checks (analysis rule K4, the round-trip tests) run this, so
    they check the decode the kernels run."""
    def kernel(p_ref, o_ref):
        _unpack_block(p_ref[...], bits, o_ref)

    rows, n = packed.shape
    out_rows = rows * (8 // bits)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((rows, n), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((out_rows, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, n), jnp.float32),
        interpret=interpret,
    )(packed)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, *scratch, bits: int):
    k = pl.program_id(2)
    if bits == 8:
        w = w_ref[...].astype(jnp.float32)
    else:
        (w_scr,) = scratch
        _unpack_block(w_ref[...], bits, w_scr)
        w = w_scr[...]
    w = w * s_ref[...]
    acc = jnp.dot(x_ref[...].astype(jnp.float32), w,
                  preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += acc


def quant_matmul(x, packed_w, scales, bits: int,
                 block: Tuple[int, int, int] = (128, 128, 256),
                 interpret: bool = False):
    """y = x @ dequant(packed_w) * scales. x: (M, K); packed_w:
    (K*bits//8, N) int8; scales: (N,) f32. Returns (M, N) f32.

    Shapes must divide the block sizes (ops.quant_matmul pads for you).
    """
    M, K = x.shape
    N = packed_w.shape[1]
    bm, bn, bk = block
    # shape validation raises (not assert: asserts vanish under python -O,
    # and a silently mis-blocked pallas_call reads out of bounds)
    if M % bm or N % bn or K % bk:
        raise ValueError(
            f"quant_matmul shapes must divide the block: x {(M, K)}, "
            f"N={N}, block (bm, bn, bk)={block} -> remainders "
            f"(M%bm={M % bm}, N%bn={N % bn}, K%bk={K % bk}); "
            f"ops.quant_matmul pads for you")
    per = 8 // bits
    if bk % per or (K * bits) % 8:
        raise ValueError(
            f"quant_matmul packing misaligned for bits={bits}: K-block "
            f"bk={bk} must be a multiple of {per} codes/byte "
            f"(bk%per={bk % per}) and K={K} must fill whole bytes "
            f"(K*bits%8={(K * bits) % 8})")
    grid = (M // bm, N // bn, K // bk)
    kernel = functools.partial(_qmm_kernel, bits=bits)
    # scales ride as a (1, N) row: a 1-D (bn,) block has no legal TPU layout
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // per, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=([] if bits == 8
                        else [pltpu.VMEM((bk, bn), jnp.float32)]),
        interpret=interpret,
    )(x, packed_w, scales.reshape(1, N))
