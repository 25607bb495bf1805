"""jit'd public wrappers around the Pallas kernels (padding + interpret
fallback on CPU). Use these from model code; call the raw kernels only in
tests.

``interpret`` defaults to *backend detection*: ``None`` resolves to True on
CPU (the Pallas interpreter is the only way to execute the kernel bodies
there) and False anywhere a real compiler exists (TPU/GPU) — previously the
wrappers hard-defaulted to True, silently running the Python interpreter
even on backends that compile the kernels. Pass ``interpret=True/False``
explicitly to override. Resolution happens at trace time and the backend is
fixed per process, so the jit cache stays consistent."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import quant_matmul as _qmm
from repro.kernels import sru_scan as _sru
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_interpret(interpret):
    """None -> interpret only where nothing can compile the kernel (CPU)."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    width = [(0, 0)] * x.ndim
    width[axis] = (0, pad)
    return jnp.pad(x, width), pad


def pack_for_kernel(w, bits: int, clip: float):
    """Quantize + pack a (K, N) weight for quant_matmul. Returns
    (packed (K*bits//8, N) int8, scales (N,) f32) with per-channel scales
    derived from the given clip (MMSE-selected upstream)."""
    from repro.core.quantization import INT_RANGES
    lo, hi = INT_RANGES[bits]
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9)
    scales = jnp.minimum(absmax, clip) / hi
    q = jnp.clip(jnp.round(w / scales[None, :]), lo, hi).astype(jnp.int8)
    return _ref.pack_weights(q, bits), scales.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def quant_matmul(x, packed_w, scales, bits: int, interpret=None):
    """Padded/jitted quant matmul; interpret=None picks the backend default
    (interpreter on CPU, compiled elsewhere), True/False forces it."""
    interpret = _resolve_interpret(interpret)
    M, K = x.shape
    N = packed_w.shape[1]
    bm = 128 if M >= 128 else _next_mult(M, 8)
    bn = 128 if N >= 128 else _next_mult(N, 128)
    bk = 256 if K >= 256 else _next_mult(K, 8 // bits * 8)
    x_p, pm = _pad_to(x, bm, 0)
    x_p, pk = _pad_to(x_p, bk, 1)
    per = 8 // bits
    w_p, _ = _pad_to(packed_w, bk // per, 0)
    w_p, pn = _pad_to(w_p, bn, 1)
    s_p, _ = _pad_to(scales, bn, 0)
    out = _qmm.quant_matmul(x_p, w_p, s_p, bits, block=(bm, bn, bk),
                            interpret=interpret)
    return out[:M, :N]


def _next_mult(x, m):
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def sru_scan(uw, uf, ur, v_f, v_r, b_f, b_r, interpret=None):
    """Padded/jitted fused SRU scan. Returns (h, r); the caller applies the
    highway skip h + (1-r)*x when the layer input width equals n."""
    interpret = _resolve_interpret(interpret)
    B, T, n = uw.shape
    bb = 8 if B >= 8 else B
    bn = 128 if n >= 128 else _next_mult(n, 8)
    def padb(t):
        t, _ = _pad_to(t, bb, 0)
        t, _ = _pad_to(t, bn, 2)
        return t
    def padv(t):
        t, _ = _pad_to(t, bn, 0)
        return t
    h, r, _c = _sru.sru_scan(padb(uw), padb(uf), padb(ur),
                             padv(v_f), padv(v_r), padv(b_f), padv(b_r),
                             block=(bb, bn), interpret=interpret)
    return h[:B, :, :n], r[:B, :, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bank_mxv_pop(x, bank, idx, interpret=None):
    """Padded/jitted population MxV against a quantized-weight bank.
    x: (P, M, m), bank: (K, m, N) — the K menu-entry fake-quantizations of
    one weight matrix — idx: (P,) int32 menu indices. Returns (P, M, N),
    ``out[p] = x[p] @ bank[idx[p]]``. The row gather happens inside the
    Pallas grid via a scalar-prefetched index (see sru_scan.bank_mxv_pop):
    no per-lane requantize pass and no (P, m, N) expanded weights."""
    interpret = _resolve_interpret(interpret)
    P, M, m = x.shape
    N = bank.shape[-1]
    bm = 8 if M >= 8 else M
    bn = 128 if N >= 128 else _next_mult(N, 8)
    x_p, _ = _pad_to(x, bm, 1)
    b_p, _ = _pad_to(bank, bn, 2)
    out = _sru.bank_mxv_pop(x_p, b_p, idx.astype(jnp.int32),
                            block=(bm, bn), interpret=interpret)
    return out[:, :M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bank_qmm_pop(x, packed, idx, interpret=None):
    """Padded/jitted population MxV against a PACKED quantized-weight bank
    (``quantization.build_packed_weight_bank`` dict for a (m, N) weight).
    x: (P, M, m), idx: (P,) int32 menu indices ordered like
    ``SUPPORTED_BITS``. Returns (P, M, N),
    ``out[p] = x[p] @ dequant(packed)[idx[p]]``. Int containers stream to
    VMEM and dequantize in-kernel (see sru_scan.bank_qmm_pop): HBM weight
    traffic drops below even the f32 bank lane's gathered row."""
    interpret = _resolve_interpret(interpret)
    P, M, m = x.shape
    N = packed["q8"].shape[1]
    bm = 8 if M >= 8 else M
    bn = 128 if N >= 128 else _next_mult(N, 8)
    x_p, _ = _pad_to(x, bm, 1)
    # the raw kernel takes (|menu|, bn) scale tiles, so it wants full
    # per-channel rows; the stored bank keeps a broadcastable (K, 1)
    # column for per-tensor grids — expand here, at trace time
    scale = packed["scale"]
    if scale.shape[1] == 1:
        scale = jnp.broadcast_to(scale, (scale.shape[0], N))
    p_p = {k: _pad_to(v, bn, 1)[0]
           for k, v in {**packed, "scale": scale}.items()}
    out = _sru.bank_qmm_pop(x_p, p_p, idx.astype(jnp.int32),
                            block=(bm, bn), interpret=interpret)
    return out[:, :M, :N]


def bank_step(x, bank, idx, interpret=None):
    """Step-shaped serving front door for the bank-gather MxV kernels.

    x: (P, T, m) — lane *i* is request *i*'s current chunk (the serving
    tier's population-axis-as-request-axis layout, already the (P, M, m)
    shape the population kernels take); ``bank`` is either a f32
    (K, m, N) menu stack (-> ``bank_mxv_pop``) or a packed-integer bank
    dict (-> ``bank_qmm_pop``, dequantizes in-kernel); idx: (P,) menu
    indices, one per request. Returns (P, T, N). Not itself jitted — it
    only dispatches to the jitted kernels, so callers can close over it
    inside their own jit without double-tracing."""
    if isinstance(bank, dict):
        return bank_qmm_pop(x, bank, idx, interpret=interpret)
    return bank_mxv_pop(x, bank, idx, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sru_scan_pop(uw, uf, ur, v_f, v_r, b_f, b_r, interpret=None):
    """Padded/jitted population-axis SRU scan. uw/uf/ur: (P, B, T, n) — one
    quantization candidate per lane, v/b shared. Returns (h, r), both
    (P, B, T, n). The population axis maps straight onto the kernel grid
    (see sru_scan.sru_scan_pop) instead of vmapping over ``pallas_call``."""
    interpret = _resolve_interpret(interpret)
    P, B, T, n = uw.shape
    bb = 8 if B >= 8 else B
    bn = 128 if n >= 128 else _next_mult(n, 8)
    def padb(t):
        t, _ = _pad_to(t, bb, 1)
        t, _ = _pad_to(t, bn, 3)
        return t
    def padv(t):
        t, _ = _pad_to(t, bn, 0)
        return t
    h, r, _c = _sru.sru_scan_pop(padb(uw), padb(uf), padb(ur),
                                 padv(v_f), padv(v_r), padv(b_f), padv(b_r),
                                 block=(bb, bn), interpret=interpret)
    return h[:, :B, :, :n], r[:, :B, :, :n]
