"""Simple Recurrent Unit (SRU) speech model — the paper's experimental model.

Architecture (paper Table 4 / Fig 6a): 4 Bi-SRU layers (n=550/direction) with
3 projection layers (p=256) between them, FC to 1904 phone-state posteriors.
Input: FBANK features m=23.

SRU cell (paper Eq. 2):
    u_t      = W   x_t                     (the only MxV — time-parallel)
    f_t      = sigma(W_f x_t + v_f . c_{t-1} + b_f)
    r_t      = sigma(W_r x_t + v_r . c_{t-1} + b_r)
    c_t      = f_t . c_{t-1} + (1 - f_t) . u_t
    h_t      = r_t . c_t + (1 - r_t) . x_t     (highway only when m == n)

Quantization boundary (paper §4.1): only the MxV weight matrices and their
input activations carry searchable precision; v_f, v_r and biases stay 16-bit
fixed point. The model exposes exactly 8 quantizable layers
(L0, Pr1, L1, Pr2, L2, Pr3, L3, FC) — a 16-variable MOHAQ genome.

Quantized-weight banks (PR 4): the precision menu is {2, 4, 8, 16} and
every grid freezes after calibration, so each layer weight has at most
|menu| distinct fake-quantized forms across a whole search.
``build_weight_banks`` precomputes them (|menu| weight copies of memory,
once per parameter set) and ``forward_population(banks=)`` gathers rows by
menu index instead of requantizing per lane per call — bitwise identical to
the on-the-fly paths by construction. ``extend_banks_u0`` additionally
freezes the input layer's quantize+MxV for a fixed validation fold.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantization as Q
from repro.distributed.sharding import shard as dist_shard

LAYER_NAMES = ("L0", "Pr1", "L1", "Pr2", "L2", "Pr3", "L3", "FC")


def layer_names_for(n_sru_layers: int):
    names = ["L0"]
    for i in range(1, n_sru_layers):
        names += [f"Pr{i}", f"L{i}"]
    return tuple(names + ["FC"])


@dataclass(frozen=True)
class SRUModelConfig:
    name: str = "sru_timit"
    input_dim: int = 23
    hidden: int = 550          # per direction
    proj: int = 256
    n_sru_layers: int = 4
    n_outputs: int = 1904
    family: str = "sru"

    @property
    def bi_out(self) -> int:
        return 2 * self.hidden

    def layer_input_dims(self) -> Dict[str, int]:
        d = {"L0": self.input_dim, "Pr1": self.bi_out, "FC": self.bi_out}
        for i in range(1, self.n_sru_layers):
            d[f"L{i}"] = self.proj
            if i >= 2:
                d[f"Pr{i}"] = self.bi_out
        return d

    def layer_names(self):
        return layer_names_for(self.n_sru_layers)

    def layer_weight_counts(self) -> Dict[str, int]:
        """MxV matrix weights per layer (== MACs per frame), paper Table 4."""
        c = {}
        for name in self.layer_names():
            m = self.layer_input_dims()[name]
            if name.startswith("L"):
                c[name] = 2 * 3 * self.hidden * m          # Bi-SRU: 2 dirs x 3 mats
            elif name.startswith("Pr"):
                c[name] = self.bi_out * self.proj
            else:
                c[name] = self.bi_out * self.n_outputs
        return c

    def vector_weight_count(self) -> int:
        """v_f, v_r + biases per direction per SRU layer (16-bit, unsearched)."""
        return self.n_sru_layers * 2 * 4 * self.hidden

    def total_weights(self) -> int:
        return sum(self.layer_weight_counts().values()) + self.vector_weight_count()

    def model_bytes(self, layer_bits: Optional[Dict[str, int]] = None,
                    base_bits: int = 32) -> float:
        if layer_bits is None:
            return self.total_weights() * base_bits / 8
        bits = Q.compressed_bits(self.layer_weight_counts(), layer_bits,
                                 self.vector_weight_count())
        return bits / 8


# ---------------------------------------------------------------- params

def init_params(key, cfg: SRUModelConfig):
    p: Dict = {}
    dims = cfg.layer_input_dims()
    names = cfg.layer_names()
    keys = jax.random.split(key, len(names))
    for k, name in zip(keys, names):
        m = dims[name]
        if name.startswith("L"):
            n = cfg.hidden
            kd = jax.random.split(k, 2)
            def one_dir(kk):
                k1, k2, k3 = jax.random.split(kk, 3)
                s = 1.0 / math.sqrt(m)
                return {
                    "W": jax.random.normal(k1, (m, 3 * n), jnp.float32) * s,
                    "v": jax.random.normal(k2, (2, n), jnp.float32) * 0.1,
                    "b": jnp.zeros((2, n), jnp.float32),
                }
            p[name] = {"fwd": one_dir(kd[0]), "bwd": one_dir(kd[1])}
        elif name.startswith("Pr"):
            s = 1.0 / math.sqrt(m)
            p[name] = {"W": jax.random.normal(k, (m, cfg.proj), jnp.float32) * s}
        else:
            s = 1.0 / math.sqrt(m)
            k1, _ = jax.random.split(k)
            p[name] = {"W": jax.random.normal(k1, (m, cfg.n_outputs)) * s,
                       "b": jnp.zeros((cfg.n_outputs,), jnp.float32)}
    return p


# ---------------------------------------------------------------- forward

def _sru_dir(dp, x, *, reverse: bool, quant16_vectors: bool,
             use_kernel: bool = False):
    """One SRU direction. x: (B, T, m) -> (B, T, n)."""
    n = dp["v"].shape[1]
    v, b = dp["v"], dp["b"]
    if quant16_vectors:
        v = Q.fixed_point_16(v)
        b = Q.fixed_point_16(b)
    u = jnp.einsum("btm,mh->bth", x, dp["W"])                 # (B,T,3n)
    uw, uf, ur = u[..., :n], u[..., n:2 * n], u[..., 2 * n:]
    if reverse:
        uw, uf, ur = uw[:, ::-1], uf[:, ::-1], ur[:, ::-1]

    if use_kernel:
        from repro.kernels import ops as kops
        h, r = kops.sru_scan(uw, uf, ur, v[0], v[1], b[0], b[1])
        if x.shape[-1] == n:                                  # highway skip
            xx = x[:, ::-1] if reverse else x
            h = h + (1.0 - r) * xx
    else:
        def step(c, ufr):
            uw_t, uf_t, ur_t = ufr
            f = jax.nn.sigmoid(uf_t + v[0] * c + b[0])
            r = jax.nn.sigmoid(ur_t + v[1] * c + b[1])
            c_new = f * c + (1.0 - f) * uw_t
            h_t = r * c_new                                  # highway added below
            return c_new, (h_t, r)
        c0 = jnp.zeros((x.shape[0], n), jnp.float32)
        _, (h, r) = jax.lax.scan(
            step, c0, (uw.transpose(1, 0, 2), uf.transpose(1, 0, 2),
                       ur.transpose(1, 0, 2)))
        h = h.transpose(1, 0, 2)
        r = r.transpose(1, 0, 2)
        if x.shape[-1] == n:                                  # highway skip
            xx = x[:, ::-1] if reverse else x
            h = h + (1.0 - r) * xx
    if reverse:
        h = h[:, ::-1]
    return h


def quant_triples_for(alloc, wclips: Dict[Tuple[str, int], float],
                      act_ranges: Dict[str, float],
                      wranges: Dict[str, float]):
    """Build the dynamic quantization-parameter pytree for ``forward(qp=)``:
    {name: 6 floats} — scale/lo/hi for the weight grid and activation grid.
    Computed in numpy per candidate; the jitted forward never recompiles."""
    qp = {}
    for name, (wb, ab) in alloc.items():
        wtrip = Q.quant_triple(
            wb, wclips[(name, wb)] if wb != 16 else wranges[name])
        atrip = Q.quant_triple(ab, act_ranges[name])
        qp[name] = tuple(np.float32(v) for v in (wtrip + atrip))
    return qp


def build_weight_banks(params, cfg: SRUModelConfig,
                       wclips: Dict[Tuple[str, int], float],
                       wranges: Dict[str, float],
                       menu: Tuple[int, ...] = Q.SUPPORTED_BITS,
                       packed: bool = False):
    """Precompute the quantized-weight banks for a parameter set.

    Returns a pytree mirroring ``params``: each MxV weight becomes a stacked
    bank ``(len(menu), m, h)`` whose row k is the weight fake-quantized to
    ``menu[k]`` bits against the frozen post-calibration grids — the same
    ``quant_triple`` grids the on-the-fly paths use (MMSE clips for 2/4/8,
    the data range for the 16-bit fixed-point row), so bank rows are bitwise
    identical to per-call requantization. The 16-bit recurrent vectors and
    biases (menu-independent) are quantized once alongside.

    Cost: ``len(menu)`` full copies of every MxV weight — for the paper
    model ~4x the weight footprint, paid once per parameter set (base model
    or retrained beacon) and reused for every candidate of every generation.
    ``forward_population(banks=...)`` then gathers rows by menu index
    instead of requantizing per lane per call.

    ``packed=True`` stores each MxV bank as PACKED integer containers +
    scales (``Q.build_packed_weight_bank``) instead of the f32 stack —
    >= 4x smaller, and ``dequant_packed_bank`` reconstructs the f32 rows
    bitwise, so every parity contract carries over. The 16-bit recurrent
    vectors/biases stay fake-quant f32 (``fixed_point_16``) in both
    formats; ``forward_population`` detects the format per bank node."""
    build = (lambda w, t: Q.build_packed_weight_bank(w, t, menu)) if packed \
        else Q.build_weight_bank
    fixed16 = jax.jit(Q.fixed_point_16)
    banks: Dict = {}
    for name in cfg.layer_names():
        trips = Q.menu_triples(
            menu, lambda b: wranges[name] if b == 16 else wclips[(name, b)])
        if name.startswith("L"):
            banks[name] = {
                d: {"W": build(params[name][d]["W"], trips),
                    "v": fixed16(params[name][d]["v"]),
                    "b": fixed16(params[name][d]["b"])}
                for d in ("fwd", "bwd")}
        else:
            banks[name] = {"W": build(params[name]["W"], trips)}
    return banks


def weight_ranges(params, cfg: SRUModelConfig) -> Dict[str, float]:
    out = {}
    for name in cfg.layer_names():
        if name.startswith("L"):
            w = max(float(jnp.max(jnp.abs(params[name]["fwd"]["W"]))),
                    float(jnp.max(jnp.abs(params[name]["bwd"]["W"]))))
        else:
            w = float(jnp.max(jnp.abs(params[name]["W"])))
        out[name] = w
    return out


def forward(params, cfg: SRUModelConfig, feats,
            qspec: Optional[Dict[str, Tuple[int, int]]] = None,
            wclips: Optional[Dict[str, float]] = None,
            act_ranges: Optional[Dict[str, float]] = None,
            calibrator: Optional[Q.ActRangeCalibrator] = None,
            qp: Optional[Dict[str, tuple]] = None,
            use_kernel: bool = False):
    """feats: (B, T, input_dim) -> logits (B, T, n_outputs).

    Two quantization entry points:
    - qspec[name] = (w_bits, a_bits): the paper's mixed-precision path with
      static bits (MMSE clips computed on the fly if missing);
    - qp[name] = (w_scale, w_lo, w_hi, a_scale, a_lo, a_hi): dynamic grids
      (one compilation serves every allocation — used by the GA search).
    MxV inputs fake-quantized against calibrated ranges, MxV weights against
    MMSE clips, recurrent vectors/biases at 16-bit fixed point. The qspec
    path keeps STE everywhere so it retrains beacons (binary-connect); the
    eval-only qp path stores weights as pure grid values (bit-identical to
    the f32 and packed bank rows).
    """
    quantized = qspec is not None or qp is not None

    # Weight and activation quantization are split so each layer's input is
    # observed/quantized exactly ONCE even when several weight matrices share
    # it (Bi-SRU fwd + bwd): observing per-weight would record every
    # activation twice and skew the median-of-max calibration statistics.
    def prep_w(name, w):
        if qp is not None and name in qp:
            # pure grid values (no STE): the qp lane is eval-only — beacon
            # retraining goes through the qspec/ste_quantize_weight branch —
            # and pure ``q`` is what the banks (f32 AND packed) store
            ws, wl, wh, _as, _al, _ah = qp[name]
            return Q.fake_quant_triple(w, ws, wl, wh, use_ste=False)
        if qspec is not None and name in qspec:
            wb, _ab = qspec[name]
            clip = (wclips or {}).get(name)
            if clip is None and wb != 16:
                clip = Q.mmse_clip(np.asarray(w), wb)
            return Q.ste_quantize_weight(w, wb, clip)
        return w

    def prep_x(name, x):
        if calibrator is not None:
            calibrator.observe(name, x)
        if qp is not None and name in qp:
            _ws, _wl, _wh, as_, al, ah = qp[name]
            return Q.fake_quant_triple(x, as_, al, ah)
        if qspec is not None and name in qspec:
            _wb, ab = qspec[name]
            rng = (act_ranges or {}).get(name)
            if rng is None:
                rng = float(jnp.max(jnp.abs(x)))
            return Q.quantize_activation(x, ab, rng)
        return x

    x = feats
    for i in range(cfg.n_sru_layers):
        name = f"L{i}"
        lp = params[name]
        xq_f = prep_x(name, x)
        wf = prep_w(name, lp["fwd"]["W"])
        wb_ = prep_w(name, lp["bwd"]["W"])
        fw = _sru_dir({**lp["fwd"], "W": wf}, xq_f, reverse=False,
                      quant16_vectors=quantized, use_kernel=use_kernel)
        bw = _sru_dir({**lp["bwd"], "W": wb_}, xq_f, reverse=True,
                      quant16_vectors=quantized, use_kernel=use_kernel)
        x = jnp.concatenate([fw, bw], axis=-1)                # (B,T,2n)
        if i < cfg.n_sru_layers - 1:
            pname = f"Pr{i + 1}"
            xq = prep_x(pname, x)
            x = jnp.einsum("btm,mp->btp", xq, prep_w(pname, params[pname]["W"]))
    xq = prep_x("FC", x)
    logits = jnp.einsum("btm,mo->bto", xq, prep_w("FC", params["FC"]["W"])) \
        + params["FC"]["b"]
    return logits


# lax.scan unroll of the population recurrence. Unrolling is exact (it
# never changes per-element arithmetic), so the value only moves speed: 8
# was the best measured on a 2-core CPU at the compact eval shape when the
# banked lane was tuned, and it is the unroll the sru_timit chip cell's
# program is compiled with — change it only against a chip measurement
_SCAN_UNROLL = 8


def extend_banks_u0(banks, cfg: SRUModelConfig, feats, a_trips):
    """Add the input-layer u-bank to a quantized-weight bank pytree.

    The first Bi-SRU layer's MxV input is ``fake_quant(feats, a_grid)`` and
    both operands are menu-indexed: ``feats`` is the same every call (the
    evaluator's frozen validation fold) and the activation grid and weight
    are one of |menu| entries each. So the whole L0 product
    ``u[p] = fq(feats, a_menu[a]) @ W_menu[w]`` takes at most
    |menu|^2 distinct values per direction — precompute them ALL
    ((Ka*Kw, B, T, 3n) per direction, row ``a*Kw + w``) and the per-
    generation dispatch gathers L0's u streams instead of running P
    activation-quant passes and P batched matmuls.

    ``a_trips``: (Ka, 3) float32 — L0's activation ``quant_triple`` rows in
    menu order. The stored rows are bound to ``feats``; the evaluator only
    ever calls the forward with that same fold. Only valid when the L0
    highway skip is statically inactive (``input_dim != hidden`` — the skip
    would need the quantized input activations); callers gate on that."""
    assert cfg.input_dim != cfg.hidden, "u0 bank invalid under highway skip"
    a_trips = jnp.asarray(a_trips, jnp.float32)

    @jax.jit
    def u0(bank_w, feats, a_trips):
        def one_a(t):
            xq = Q.fake_quant_triple(feats, t[0], t[1], t[2])
            xf = xq.reshape(-1, xq.shape[-1])                # (B*T, m)
            return jax.vmap(lambda w: jnp.matmul(xf, w))(bank_w)
        u = jax.vmap(one_a)(a_trips)                  # (Ka, Kw, B*T, 3n)
        ka, kw = u.shape[:2]
        return u.reshape((ka * kw,) + feats.shape[:2] + (u.shape[-1],))

    out = dict(banks)
    out["L0"] = {key: dict(banks["L0"][key]) for key in ("fwd", "bwd")}
    for key in ("fwd", "bwd"):
        out["L0"][key]["U"] = u0(banks["L0"][key]["W"], feats, a_trips)
    return out


def forward_population(params, cfg: SRUModelConfig, feats, qp_stack,
                       use_kernel: bool = False, banks=None):
    """Population-parameterized forward: score P quantization candidates in
    ONE jitted call, each lane bitwise equal to the scalar ``forward(qp=)``
    (the GA's Pareto fronts are exact).

    ``qp_stack``: (P, L, 6) float32 — for each candidate (population lane)
    and each layer in ``cfg.layer_names()`` order, the dynamic
    (w_scale, w_lo, w_hi, a_scale, a_lo, a_hi) grids produced by
    ``quant_triples_for``. Params are closed over (broadcast, not vmapped).
    ``feats`` (B, T, m) is one shared input scored under every lane's
    grids, or (P, B, T, m) with one input per lane. Returns logits
    (P, B, T, n_outputs).

    ``banks`` (optional): precomputed quantized-weight banks from
    ``build_weight_banks`` for the SAME ``params``. When given, each lane's
    quantized weight is *gathered* — row ``menu_index_from_hi(w_hi)`` of
    the (|menu|, m, h) bank — instead of fake-quantized per lane per call;
    only activations (data-dependent) are still quantized on the fly. Bank
    rows store the identical pure-grid values the requant lane computes,
    so both are bitwise equal. Banks built with ``packed=True`` are
    detected per node: the XLA lane dequantizes the int containers once per
    layer (bitwise equal to the f32 rows) and the kernel lane streams them
    into ``kernels.ops.bank_qmm_pop``, which dequantizes in-kernel. With
    the L0 u-bank of ``extend_banks_u0``, the input layer's whole
    quantize+MxV is one row gather per direction.

    One lowering: the P axis is explicit end to end. The MxV einsums are
    P-batched matmuls and each Bi-SRU direction runs its own recurrence,
    the backward one scanned ``reverse=True`` (outputs come back aligned,
    no time flips); the reset-gate output is elided when the highway skip
    is statically inactive. ``use_kernel=True`` swaps the ``lax.scan`` for
    the Pallas population-axis kernel (``kernels.ops.sru_scan_pop``, grid
    (P, B/bb, n/bn), the backward stream time-flipped in and out), and with
    ``banks`` the MxV for ``kernels.ops.bank_step``, which reads the
    selected bank row in place via a scalar-prefetched index. Named scopes
    (``sru.bank_gather``, ``sru.u0_bank``, ``sru.projection`` for every
    MxV, ``sru.scan``) carry into the compiled program's op metadata.
    """
    names = list(cfg.layer_names())
    li = {n: i for i, n in enumerate(names)}
    P = qp_stack.shape[0]
    n = cfg.hidden
    # per-lane bank row index, recovered from the weight grid tops — the
    # qp grid stack stays the only per-candidate input of the dispatch
    w_idx = (Q.menu_index_from_hi(qp_stack[:, :, 2])
             if banks is not None else None)

    def q_act(name, x):                       # per-lane activation grids
        row = qp_stack[:, li[name]]
        return jax.vmap(Q.fake_quant_triple)(x, row[:, 3], row[:, 4],
                                             row[:, 5])

    def q_w(name, w):                         # per-lane weight grids
        # pure grid values (use_ste=False): matches the scalar qp lane and
        # the bank rows exactly — see quantization.build_weight_bank
        row = qp_stack[:, li[name]]
        return jax.vmap(lambda s, lo, hi: Q.fake_quant_triple(
            w, s, lo, hi, use_ste=False))(row[:, 0], row[:, 1], row[:, 2])

    def raw_bank(name, sub=None):
        node = banks[name] if sub is None else banks[name][sub]
        return node["W"]

    def bank_of(name, sub=None):
        w = raw_bank(name, sub)
        if isinstance(w, dict):
            # packed-integer bank: reconstruct the f32 menu stack ONCE per
            # layer (lane-independent, bitwise equal to the f32 bank rows —
            # quantization.dequant_packed_bank) and gather from it; HBM
            # keeps only the packed containers
            return Q.dequant_packed_bank(w)
        return w

    def lane_w(name, sub=None):
        """(P, m, h) per-lane quantized weight: bank gather or requant."""
        if banks is not None:
            with jax.named_scope("sru.bank_gather"):
                return jnp.take(bank_of(name, sub), w_idx[:, li[name]],
                                axis=0)
        w = params[name]["W"] if sub is None else params[name][sub]["W"]
        return q_w(name, w)

    def mxv(xq, wq):                          # (P,B,T,m) @ (P,m,h)
        with jax.named_scope("sru.projection"):
            out = jnp.matmul(xq.reshape(P, -1, xq.shape[-1]), wq)
            return out.reshape(xq.shape[:3] + (wq.shape[-1],))

    def mxv_layer(xq, name, sub=None):
        """Per-lane quantized MxV. With banks + kernel the gather happens
        INSIDE the Pallas grid (scalar-prefetched row index), so the bank is
        read in place instead of being expanded to P lane copies first —
        packed banks additionally dequantize in-kernel (bank_qmm_pop)."""
        if banks is not None and use_kernel:
            from repro.kernels import ops as kops
            x2 = xq.reshape(P, -1, xq.shape[-1])
            with jax.named_scope("sru.projection"):
                u = kops.bank_step(x2, raw_bank(name, sub),
                                   w_idx[:, li[name]])
            return u.reshape(xq.shape[:3] + (u.shape[-1],))
        return mxv(xq, lane_w(name, sub))

    def recurrence(uw, uf, ur, v, b, reverse, highway):
        """One direction over (P, B, T, n) streams -> (h, r), both aligned
        with the input's time order; the scan elides ``r`` (None) when
        ``highway`` is off."""
        if use_kernel:
            from repro.kernels import ops as kops
            flip = (lambda a: a[:, :, ::-1]) if reverse else (lambda a: a)
            with jax.named_scope("sru.scan"):
                h, r = kops.sru_scan_pop(flip(uw), flip(uf), flip(ur),
                                         v[0], v[1], b[0], b[1])
            return flip(h), flip(r)

        def step(c, t3):
            uw_t, uf_t, ur_t = t3                            # (P,B,n)
            f = jax.nn.sigmoid(uf_t + v[0] * c + b[0])
            r = jax.nn.sigmoid(ur_t + v[1] * c + b[1])
            c_new = f * c + (1.0 - f) * uw_t
            return c_new, ((r * c_new, r) if highway else (r * c_new,))

        tr = lambda a: a.transpose(2, 0, 1, 3)               # (T,P,B,n)
        with jax.named_scope("sru.scan"):
            _, out = jax.lax.scan(
                step, jnp.zeros(uw.shape[:2] + (n,), jnp.float32),
                (tr(uw), tr(uf), tr(ur)),
                unroll=_SCAN_UNROLL, reverse=reverse)
        h = out[0].transpose(1, 2, 0, 3)                     # (P,B,T,n)
        return h, (out[1].transpose(1, 2, 0, 3) if highway else None)

    # feats (B, T, m): one shared input scored under P candidate grids
    # (the search substrate). feats (P, B, T, m): one input PER LANE —
    # the serving tier's population-axis-as-request-axis contract, where
    # lane i carries request i's frames under request i's allocation.
    # Every downstream op is already per-lane, so only this entry differs.
    if feats.ndim == 4:
        if feats.shape[0] != P:
            raise ValueError(f"per-lane feats lead axis {feats.shape[0]} "
                             f"!= population size {P}")
        x = feats                                            # (P,B,T,m)
    else:
        x = jnp.broadcast_to(feats, (P,) + feats.shape)      # (P,B,T,m)
    # anchor the population lane on the mesh's "pop" axis (no-op outside an
    # axis_rules context) so the GSPMD lowering of the sharded evaluator
    # partitions candidates instead of replicating them
    x = dist_shard(x, "pop")
    for i in range(cfg.n_sru_layers):
        name = f"L{i}"
        # input-layer u-bank (see extend_banks_u0): L0's whole quantize+MxV
        # collapses to one row gather per direction; statically skipped when
        # the highway would need the quantized input, and for per-lane feats
        # (the u-bank rows are bound to the shared eval fold)
        use_u0 = (i == 0 and banks is not None and feats.ndim == 3
                  and "U" in banks["L0"]["fwd"] and feats.shape[-1] != n)
        if use_u0:
            a_idx0 = Q.menu_index_from_hi(qp_stack[:, li[name], 5])
            n_w = banks[name]["fwd"]["W"].shape[0]
            combo = a_idx0 * n_w + w_idx[:, li[name]]
            xq = None
        else:
            xq = q_act(name, x)
        highway = x.shape[-1] == n
        hs = []
        for key in ("fwd", "bwd"):
            if use_u0:
                # re-anchor the lane axis here: with L0 gathered from the
                # u-bank the broadcast input (the usual anchor) is dead
                # code, so GSPMD must pick the partitioning up from the
                # gathered stream
                with jax.named_scope("sru.u0_bank"):
                    u = jnp.take(banks[name][key]["U"], combo, axis=0)
                u = dist_shard(u, "pop")
            else:
                u = mxv_layer(xq, name, key)                 # (P,B,T,3n)
            uw, uf, ur = u[..., :n], u[..., n:2 * n], u[..., 2 * n:]
            if banks is not None:             # 16-bit vectors pre-quantized
                v, b = banks[name][key]["v"], banks[name][key]["b"]
            else:
                v = Q.fixed_point_16(params[name][key]["v"])
                b = Q.fixed_point_16(params[name][key]["b"])
            h, r = recurrence(uw, uf, ur, v, b, key == "bwd", highway)
            if highway:                                      # aligned: no flip
                h = h + (1.0 - r) * xq
            hs.append(h)
        x = jnp.concatenate(hs, axis=-1)
        if i < cfg.n_sru_layers - 1:
            pname = f"Pr{i + 1}"
            x = mxv_layer(q_act(pname, x), pname)
    xq = q_act("FC", x)
    logits = mxv_layer(xq, "FC") + params["FC"]["b"]
    return dist_shard(logits, "pop")


def forward_decode_step(params, cfg: SRUModelConfig, feats, qp_stack,
                        banks=None, use_kernel: bool = False):
    """One serving decode step: P request lanes, one chunk each.

    ``feats``: (P, T, m) — lane *i* holds request *i*'s current chunk of T
    frames; ``qp_stack``: (P, L, 6) — lane *i*'s row is request *i*'s
    allocation (its quantization grids, from which the banked dispatch
    recovers the menu index). This is the serving tier's hot path: the
    whole mixed-allocation batch is ONE banked population dispatch — the
    population axis reused as the request axis — so adding a request with
    a different allocation changes a gather index, not the dispatch count.

    Bi-SRU is bidirectional, so a "step" is chunk-synchronous: each lane's
    chunk runs the full forward with fresh recurrent state (c0 = 0 per
    chunk), exactly like the scalar ``forward(qp=)`` on that chunk — the
    per-chunk logits are bitwise equal to the scalar path, which is the
    serving parity contract. Returns logits (P, T, n_outputs).
    """
    if feats.ndim != 3:
        raise ValueError(f"decode-step feats must be (P, T, m), got "
                         f"shape {feats.shape}")
    logits = forward_population(params, cfg, feats[:, None], qp_stack,
                                use_kernel=use_kernel, banks=banks)
    return logits[:, 0]


def calibrate(params, cfg: SRUModelConfig, feats_batches) -> Dict[str, float]:
    """Expected activation ranges = median of per-sequence max-abs."""
    cal = Q.ActRangeCalibrator()
    for feats in feats_batches:
        forward(params, cfg, feats, calibrator=cal)
    return cal.expected_ranges()


def weight_clips(params, cfg: SRUModelConfig,
                 bits_by_layer: Dict[str, int]) -> Dict[str, float]:
    """MMSE clip per layer at a given bit-width (weights of both directions
    pooled for Bi-SRU layers)."""
    clips = {}
    for name, bits in bits_by_layer.items():
        if bits == 16:
            continue
        if name.startswith("L"):
            w = np.concatenate([np.asarray(params[name]["fwd"]["W"]).ravel(),
                                np.asarray(params[name]["bwd"]["W"]).ravel()])
        else:
            w = np.asarray(params[name]["W"]).ravel()
        clips[name] = Q.mmse_clip(w, bits)
    return clips


def frame_error_rate(params, cfg: SRUModelConfig, feats, labels, **fw_kwargs):
    logits = forward(params, cfg, feats, **fw_kwargs)
    pred = jnp.argmax(logits, axis=-1)
    return float(jnp.mean((pred != labels).astype(jnp.float32)) * 100.0)
