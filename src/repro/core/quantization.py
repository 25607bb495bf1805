"""Post-training quantization primitives (paper §4.1).

- Symmetric integer linear quantization with MMSE-selected clipping threshold
  (Sung et al. 2015), ranges [-128,127] / [-8,7] / [-2,1] for 8/4/2 bits.
- 16-bit fixed point (sign + integer bits sized to the data range + fraction)
  for recurrent vectors, biases, and 16-bit layers.
- Activation quantization against *calibrated expected ranges* (median of
  per-sequence max-abs over ~70 validation sequences, per the paper).
- Straight-through-estimator fake-quant for beacon retraining (binary-connect:
  quantized forward/backward, full-precision update).

All fake-quant: values live on the quantized grid in float — the exact
integer pipeline is exercised separately by the Pallas quant_matmul kernel.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# paper's integer ranges
INT_RANGES: Dict[int, Tuple[int, int]] = {8: (-128, 127), 4: (-8, 7), 2: (-2, 1)}
SUPPORTED_BITS = (2, 4, 8, 16)


def quantize_int(x, bits: int, clip: float):
    """Symmetric linear integer fake-quant with clipping threshold ``clip``."""
    lo, hi = INT_RANGES[bits]
    scale = clip / hi
    q = jnp.clip(jnp.round(x / scale), lo, hi)
    return q * scale


def quantize_int_real(x, bits: int, clip: float):
    """Integer codes + scale (for packed kernels)."""
    lo, hi = INT_RANGES[bits]
    scale = clip / hi
    q = jnp.clip(jnp.round(x / scale), lo, hi).astype(jnp.int8)
    return q, scale


def mmse_clip(x, bits: int, n_grid: int = 64) -> float:
    """MMSE clipping threshold: grid-search the clip value minimizing
    ||x - Q(x)||^2 (Minimum Mean Square Error method)."""
    x = np.asarray(x, np.float32)
    absmax = float(np.abs(x).max()) or 1.0
    lo, hi = INT_RANGES[bits]
    best_c, best_e = absmax, np.inf
    for frac in np.linspace(1.0 / n_grid, 1.0, n_grid):
        c = absmax * frac
        scale = c / hi
        q = np.clip(np.round(x / scale), lo, hi) * scale
        e = float(np.mean((x - q) ** 2))
        if e < best_e:
            best_e, best_c = e, c
    return best_c


def fixed_point_16(x):
    """16-bit fixed point: int bits sized to the range, rest sign+fraction."""
    absmax = jnp.max(jnp.abs(x))
    int_bits = jnp.ceil(jnp.log2(jnp.maximum(absmax, 1e-9)))
    int_bits = jnp.clip(int_bits, -14, 14)
    frac_bits = 15.0 - jnp.maximum(int_bits, 0.0)
    scale = 2.0 ** (-frac_bits)
    lim = 2.0 ** 15 - 1
    return jnp.clip(jnp.round(x / scale), -lim - 1, lim) * scale


def quantize_weight(w, bits: int, clip: Optional[float] = None):
    """Fake-quantize a weight tensor to ``bits`` (paper menu: 2/4/8 int, 16 fp)."""
    if bits == 16:
        return fixed_point_16(w)
    if clip is None:
        clip = mmse_clip(np.asarray(w, np.float32), bits)
    return quantize_int(w, bits, clip)


def ste(x, xq):
    """Straight-through estimator: value of xq, gradient of x."""
    return x + jax.lax.stop_gradient(xq - x)


def ste_quantize_weight(w, bits: int, clip: float):
    if bits == 16:
        return ste(w, fixed_point_16(w))
    return ste(w, quantize_int(w, bits, clip))


def quantize_activation(a, bits: int, expected_range: float):
    """Activation fake-quant against a calibrated expected range. 16-bit
    activations are re-quantized to fixed point with the same range logic."""
    if bits == 16:
        # re-quantization to 16-bit fixed point by a range-derived scale
        int_bits = np.ceil(np.log2(max(expected_range, 1e-9)))
        frac_bits = 15.0 - max(int_bits, 0.0)
        scale = 2.0 ** (-frac_bits)
        lim = 2.0 ** 15 - 1
        q = jnp.clip(jnp.round(a / scale), -lim - 1, lim) * scale
        return ste(a, q.astype(a.dtype))
    return ste(a, quantize_int(a, bits, expected_range).astype(a.dtype))


def quant_triple(bits: int, clip_or_range: float):
    """Express any menu precision as a dynamic (scale, lo, hi) triple so a
    single jitted forward serves every allocation (no per-candidate
    recompilation during the GA search). 16-bit -> fixed-point grid."""
    if bits == 16:
        int_bits = int(np.ceil(np.log2(max(clip_or_range, 1e-9))))
        frac_bits = 15.0 - max(int_bits, 0)
        scale = 2.0 ** (-frac_bits)
        return (scale, -32768.0, 32767.0)
    lo, hi = INT_RANGES[bits]
    return (clip_or_range / hi, float(lo), float(hi))


def fake_quant_triple(x, scale, lo, hi, use_ste: bool = True):
    q = jnp.clip(jnp.round(x / scale), lo, hi) * scale
    q = q.astype(x.dtype)
    return ste(x, q) if use_ste else q


# ---------------------------------------------------- quantized-weight banks
#
# The search menu is tiny ({2, 4, 8, 16} bits) and the per-layer quantization
# grids are frozen after calibration: for a given full-precision weight
# tensor, at most ``len(menu)`` distinct fake-quantized tensors can ever
# appear during a whole GA search. A *bank* precomputes them once — row k is
# the weight under menu entry k — so population evaluation gathers rows
# (``jnp.take`` by menu index) instead of re-fake-quantizing per lane per
# call. Memory cost: |menu| full copies of each weight tensor.
#
# Bit-parity contract: bank rows are built by ``fake_quant_triple`` with the
# triples passed as *traced arrays* (never baked-in constants), i.e. the
# exact per-element expression the on-the-fly paths execute — scalar
# ``forward(qp=)`` and the population forward's ``q_w`` vmap — so a gathered row
# is bitwise identical to requantizing on the fly (including the 16-bit
# fixed-point grid, which ``quant_triple`` expresses as a plain
# (scale, -32768, 32767) triple).
#
# Weight rows are PURE grid values (``use_ste=False``): the STE wrapper's
# float round-trip ``x + (q - x)`` can differ from ``q`` in the last ulp at
# clipped elements, and no eval lane takes gradients through weights (beacon
# retraining quantizes via the separate ``qspec``/``ste_quantize_weight``
# path). Every eval weight lane — scalar, requant, f32 bank, packed
# bank — therefore carries exactly ``clip(round(w/s), lo, hi) * s``, which
# is what makes the packed-integer reconstruction below bit-exact.

@jax.jit
def build_weight_bank(w, triples):
    """Stack fake-quantized copies of ``w``: (K, *w.shape) where row k is
    ``fake_quant_triple(w, *triples[k], use_ste=False)``. ``triples``:
    (K, 3) float32 of (scale, lo, hi) grids — one per menu entry, from
    ``menu_triples``."""
    triples = jnp.asarray(triples, jnp.float32)
    return jax.vmap(lambda t: fake_quant_triple(w, t[0], t[1], t[2],
                                                use_ste=False))(triples)


def menu_triples(bits_menu, clip_of_bits) -> np.ndarray:
    """(K, 3) float32 of ``quant_triple`` rows for a per-layer menu.
    ``clip_of_bits(bits)`` supplies the MMSE clip (int grids) or data range
    (16-bit fixed point) — frozen after calibration, which is what makes the
    bank valid for a whole search."""
    return np.asarray([quant_triple(b, clip_of_bits(b)) for b in bits_menu],
                      np.float32)


def menu_index_from_hi(w_hi, bits_menu=SUPPORTED_BITS):
    """Map a weight triple's grid-top value back to its menu slot (the bank
    row index). Each menu entry has a distinct, exactly-representable ``hi``
    (1, 7, 127 for int grids; 32767 for the 16-bit fixed-point grid), so the
    allocation's bit-width is recoverable from the (P, L, 6) qp grid stack
    alone — no side-channel index array has to be threaded to the forward."""
    tops = [32767.0 if b == 16 else float(INT_RANGES[b][1])
            for b in bits_menu]
    idx = jnp.zeros(jnp.shape(w_hi), jnp.int32)
    for t in sorted(tops)[:-1]:
        idx = idx + (w_hi > t).astype(jnp.int32)
    return idx


# ------------------------------------------------ packed-integer weight banks
#
# The f32 banks above realize the *compute* story (gather instead of
# requantize) but not the paper's *memory* story: every bank row is still a
# full-precision copy, so a |menu|=4 bank costs 16 bytes/weight. The packed
# format stores what the hardware actually ships — integer codes in their
# natural containers plus per-channel scale rows:
#
#     {"q2":  int8  (ceil(K/4), N)   4 codes/byte, kernels/ref.py layout
#      "q4":  int8  (ceil(K/2), N)   2 codes/byte,        "        "
#      "q8":  int8  (K, N)
#      "q16": int16 (K, N)           fixed-point codes
#      "scale": f32 (|menu|, C)}     per-channel scale rows; C=1 for the
#                                    per-tensor MOHAQ grids (a broadcastable
#                                    channel axis, not |menu| full rows)
#
# for a (K, N) weight: ~3.75 bytes/weight + 16 bytes vs the f32 bank's 16
# bytes/weight — >= 4x smaller at any real layer shape. The packing layout
# is shared with ``kernels/ref.py::pack_weights`` / ``unpack_weights`` (low
# bits first along the contraction axis), so the Pallas ``bank_qmm_pop``
# kernel dequantizes blocks with the same ``_unpack_block`` it already uses
# for ``quant_matmul``.
#
# Bit-parity contract: codes are ``clip(round(w/s), lo, hi)`` on the same
# (scale, lo, hi) triples the f32 banks use, and dequantization is a single
# f32 multiply by the same scale — elementwise identical to the pure-grid
# ``clip(round(x/s), lo, hi) * s`` that ``build_weight_bank`` stores (see
# the use_ste note above). Integer grids are exact by construction; the
# 16-bit fixed-point grid is exact because |codes| <= 32768 < 2^24 round-
# trips int16 -> f32 losslessly. Hence ``dequant_packed_bank`` reconstructs
# the f32 bank stack *bitwise*, and the packed lane inherits the banked
# lane's parity with scalar requantization. (Recurrent v/b vectors are NOT
# packed — they stay fake-quant f32 ``fixed_point_16`` exactly as in the
# f32 banks.)

_PACK_BITS = (2, 4)          # menu entries stored packed in int8 containers


def _code_dtype(bits: int):
    return jnp.int16 if bits == 16 else jnp.int8


@functools.partial(jax.jit, static_argnames=("bits",))
def _packed_codes(w, scale, lo, hi, bits: int):
    """Integer codes of ``w`` on the (scale, lo, hi) grid, packed into the
    container for ``bits`` (kernels/ref.py layout for sub-byte grids)."""
    codes = jnp.clip(jnp.round(w / scale), lo, hi).astype(_code_dtype(bits))
    if bits in _PACK_BITS:
        from repro.kernels import ref as kref
        codes = kref.pack_weights(codes, bits)
    return codes


def build_packed_weight_bank(w, triples, bits_menu=SUPPORTED_BITS):
    """Packed-integer bank of ``w`` (2-D, contraction axis first): integer
    codes per menu entry in their natural containers plus a (|menu|, 1)
    per-channel scale matrix (the channel axis is broadcastable: MOHAQ grids
    are per-tensor, so dequantization multiplies every channel by exactly
    the grid scale the f32 bank used). ``triples`` as in
    ``build_weight_bank``."""
    if w.ndim != 2:
        raise ValueError(f"packed banks require 2-D weights, got {w.shape}")
    triples = np.asarray(triples, np.float32)
    if len(triples) != len(bits_menu):
        raise ValueError(f"{len(triples)} triples for menu {bits_menu}")
    bank = {}
    for k, bits in enumerate(bits_menu):
        s, lo, hi = (jnp.float32(t) for t in triples[k])
        bank[f"q{bits}"] = _packed_codes(w, s, lo, hi, bits)
    bank["scale"] = jnp.asarray(triples[:, 0:1])
    return bank


def dequant_packed_bank(packed, bits_menu=SUPPORTED_BITS):
    """Reconstruct the (|menu|, K, N) f32 bank stack from a packed bank —
    bitwise identical to ``build_weight_bank`` on the same weight/triples
    (see parity note above). This is the non-kernel packed lane: one
    dequantization per layer (lane-independent), then the existing
    ``jnp.take`` row gather; HBM keeps only the packed containers."""
    from repro.kernels import ref as kref
    wide = packed[f"q{max(b for b in bits_menu if b not in _PACK_BITS)}"]
    k_dim = wide.shape[0]
    rows = []
    for k, bits in enumerate(bits_menu):
        codes = packed[f"q{bits}"]
        if bits in _PACK_BITS:
            codes = kref.unpack_weights(codes, bits, k_dim)
        rows.append(codes.astype(jnp.float32) * packed["scale"][k][None, :])
    return jnp.stack(rows)


def packed_bank_nbytes(bank) -> int:
    """Bytes a bank (packed dict or f32 stack) occupies — no host transfer."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(bank))


class ActRangeCalibrator:
    """Records per-layer activation ranges; expected range = median of
    per-sequence max-abs (paper: 70 sequences suffice)."""

    def __init__(self):
        self._ranges: Dict[str, list] = {}

    def observe(self, name: str, value) -> None:
        self._ranges.setdefault(name, []).append(
            float(jnp.max(jnp.abs(value))))

    def expected_ranges(self) -> Dict[str, float]:
        return {k: float(np.median(v)) for k, v in self._ranges.items()}


# ---------------------------------------------------- pytree quant serving

def quantize_tree(params, bits: int):
    """Quantize every >=2-D float leaf of a param tree to ``bits`` (8 or 4),
    per-tensor symmetric scales. int4 packs two codes per int8 byte along the
    last axis. Returns the quantized tree (same structure; each quantized
    leaf becomes {"q": int8, "scale": f32[]}) — for weight-quantized serving
    (MOHAQ applied to decode: HBM weight traffic / footprint drops 2x/4x)."""
    assert bits in (8, 4)

    def one(leaf):
        if leaf.ndim < 2 or leaf.dtype not in (jnp.float32, jnp.bfloat16):
            return leaf
        lf = leaf.astype(jnp.float32)
        hi = 127 if bits == 8 else 7
        scale = jnp.maximum(jnp.max(jnp.abs(lf)), 1e-9) / hi
        q = jnp.clip(jnp.round(lf / scale), -hi - 1, hi).astype(jnp.int8)
        if bits == 4:
            if q.shape[-1] % 2:
                q = jnp.concatenate(
                    [q, jnp.zeros(q.shape[:-1] + (1,), jnp.int8)], axis=-1)
            lo_n = q[..., 0::2].astype(jnp.uint8) & 0xF
            hi_n = (q[..., 1::2].astype(jnp.uint8) & 0xF) << 4
            q = (lo_n | hi_n).astype(jnp.int8)
        return {"q": q, "scale": scale}
    return jax.tree.map(one, params)


def dequantize_tree(qtree, spec_tree, bits: int):
    """Inverse of quantize_tree; ``spec_tree`` supplies original shapes/dtypes
    (e.g. from jax.eval_shape of the model init)."""
    def one(qleaf, spec):
        if not (isinstance(qleaf, dict) and "q" in qleaf):
            return qleaf
        q = qleaf["q"]
        if bits == 4:
            u = q.astype(jnp.uint8)
            lo_n = (u & 0xF).astype(jnp.int8)
            lo_n = lo_n - ((lo_n & 0x8) != 0).astype(jnp.int8) * 16
            hi_n = ((u >> 4) & 0xF).astype(jnp.int8)
            hi_n = hi_n - ((hi_n & 0x8) != 0).astype(jnp.int8) * 16
            q = jnp.stack([lo_n, hi_n], axis=-1).reshape(
                *q.shape[:-1], q.shape[-1] * 2)[..., :spec.shape[-1]]
        w = q.astype(jnp.float32) * qleaf["scale"]
        return w.astype(spec.dtype)
    return jax.tree.map(one, qtree, spec_tree,
                        is_leaf=lambda x: isinstance(x, dict) and "q" in x)


def quant_tree_axes(axes_tree, spec_tree):
    """Logical axes for the quantized tree (q inherits the leaf's axes,
    scale is replicated)."""
    def one(axes, spec):
        if len(spec.shape) < 2 or spec.dtype not in (jnp.float32, jnp.bfloat16):
            return axes
        return {"q": axes, "scale": ()}
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    return jax.tree.map(one, axes_tree, spec_tree, is_leaf=is_axes)


def compressed_bits(layer_weights: Dict[str, int], layer_bits: Dict[str, int],
                    vector_weights: int = 0) -> int:
    """Total model bits under a per-layer bit allocation; non-MxV vectors are
    16-bit (paper §4.1)."""
    total = sum(n * layer_bits[name] for name, n in layer_weights.items())
    return total + vector_weights * 16


def compression_ratio(layer_weights: Dict[str, int],
                      layer_bits: Dict[str, int],
                      vector_weights: int = 0,
                      base_bits: int = 32) -> float:
    n_all = sum(layer_weights.values()) + vector_weights
    return (n_all * base_bits) / compressed_bits(
        layer_weights, layer_bits, vector_weights)
