"""Bi-SRU speech model (the MOHAQ paper's model, Table 4): weights, fold,
plain reference, calibration and work counts for the chip benchmark.

Nothing here imports the program except ``build_target``, which hands the
weights, the fold and the grids made here to the program's own
``TrainedSRU``. The reference is a straightforward float32 forward of one
allocation at a time, each matmul's operands rounded to the precision the
config states and the products summed in float32 at ``Precision.HIGHEST``:

    u_t = W x_t;  f_t = sigmoid(u^f_t + v_f c_{t-1} + b_f)
    r_t = sigmoid(u^r_t + v_r c_{t-1} + b_r)
    c_t = f_t c_{t-1} + (1 - f_t) u^w_t;  h_t = r_t c_t (+ (1 - r_t) x_t
    when the input width equals the hidden width)

with each MxV weight and MxV input on the allocation's grid
``clip(round(x / scale), lo, hi) * scale`` and the recurrent vectors and
biases at 16-bit fixed point (paper section 4.1).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from families import common as C


# ------------------------------------------------------------ geometry

def layer_names(cfg):
    names = ["L0"]
    for i in range(1, cfg["n_sru_layers"]):
        names += [f"Pr{i}", f"L{i}"]
    return names + ["FC"]


def input_dims(cfg):
    bi = 2 * cfg["hidden"]
    dims = {"L0": cfg["input_dim"], "FC": bi}
    for i in range(1, cfg["n_sru_layers"]):
        dims[f"Pr{i}"] = bi
        dims[f"L{i}"] = cfg["proj"]
    return dims


def weight_counts(cfg):
    """MxV weights per layer (== MACs per frame)."""
    dims, h = input_dims(cfg), cfg["hidden"]
    out = {}
    for name in layer_names(cfg):
        m = dims[name]
        if name.startswith("L"):
            out[name] = 2 * 3 * h * m
        elif name.startswith("Pr"):
            out[name] = 2 * h * cfg["proj"]
        else:
            out[name] = 2 * h * cfg["n_outputs"]
    return out


def work(cfg, mix):
    """The algorithm's FLOPs and bytes of one dispatched population lane:
    2 x MxV weights x frames; the lane's quantized weights read once at
    the bank's storage width. The fold (features and labels) is read once
    per dispatch."""
    rows, frames = C.fold_shape(mix)
    n_items = rows * frames
    weights = sum(weight_counts(cfg).values())
    return {"flops_per_lane": 2.0 * weights * n_items,
            "bytes_per_lane": float(weights * C.BYTES[cfg["bank_dtype"]]),
            "bytes_per_dispatch": float(n_items * (cfg["input_dim"] + 1)
                                        * 4)}


# ------------------------------------------------------------ weights, fold

def init_weights(cfg, seed):
    """The program's parameter pytree, drawn on the device in one call:
    N(0, 1/m) MxV weights, N(0, 0.01) recurrent vectors, zero biases."""
    names, dims, h = layer_names(cfg), input_dims(cfg), cfg["hidden"]

    @jax.jit
    def make(key):
        p = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            m = dims[name]
            s = 1.0 / math.sqrt(m)
            if name.startswith("L"):
                def one(kk):
                    k1, k2 = jax.random.split(kk)
                    return {"W": jax.random.normal(k1, (m, 3 * h)) * s,
                            "v": jax.random.normal(k2, (2, h)) * 0.1,
                            "b": jnp.zeros((2, h), jnp.float32)}
                kf, kb = jax.random.split(k)
                p[name] = {"fwd": one(kf), "bwd": one(kb)}
            elif name.startswith("Pr"):
                p[name] = {"W": jax.random.normal(k, (m, cfg["proj"])) * s}
            else:
                p[name] = {"W": jax.random.normal(
                               k, (m, cfg["n_outputs"])) * s,
                           "b": jnp.zeros((cfg["n_outputs"],), jnp.float32)}
        return p

    return make(C.key(seed, "weights"))


def make_features(cfg, mix, seed):
    """Speech-like feature tracks: unit normals smoothed over 5 frames, one
    row per utterance, every row ``length`` frames long."""
    rows, frames = C.fold_shape(mix)
    m = cfg["input_dim"]

    @jax.jit
    def make(key):
        raw = jax.random.normal(key, (rows, frames + 4, m))
        return sum(raw[:, i:i + frames] for i in range(5)) / np.sqrt(5.0)

    return make(C.key(seed, "fold"))


# ------------------------------------------------------------ reference

def fixed16(x):
    """16-bit fixed point with integer bits sized to the data range."""
    absmax = jnp.max(jnp.abs(x))
    int_bits = jnp.clip(jnp.ceil(jnp.log2(jnp.maximum(absmax, 1e-9))),
                        -14, 14)
    scale = 2.0 ** (-(15.0 - jnp.maximum(int_bits, 0.0)))
    return jnp.clip(jnp.round(x / scale), -32768.0, 32767.0) * scale


def forward(params, cfg, feats, qp=None, prec=C.EXACT, taps=None):
    """Plain float32 forward: feats (B, T, m) -> logits (B, T, n_outputs).
    ``qp`` (L, 6): per layer (w_scale, w_lo, w_hi, a_scale, a_lo, a_hi);
    None runs unquantized. ``prec`` (``common.Precision``) rounds every
    matmul operand (the config's stated precision: bfloat16 operands, as
    the TPU's default precision computes a float32 matmul in one pass;
    the control's: float8). ``taps`` (a dict) collects each MxV input's
    per-row max-abs."""
    names = layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}
    h = cfg["hidden"]
    rnd, dot = prec.mm(), prec.dot_precision()

    def mm(a, w):
        return jnp.einsum("btm,mh->bth", rnd(a), rnd(w), precision=dot)

    def act(name, x):
        if taps is not None:
            taps[name] = jnp.max(jnp.abs(x), axis=(1, 2))
        if qp is None:
            return x
        r = qp[li[name]]
        return C.fake_quant(x, r[3], r[4], r[5])

    def wgt(name, w):
        if qp is None:
            return w
        r = qp[li[name]]
        return C.fake_quant(w, r[0], r[1], r[2])

    x = feats
    for i in range(cfg["n_sru_layers"]):
        name = f"L{i}"
        xq = act(name, x)
        hs = []
        for d in ("fwd", "bwd"):
            p = params[name][d]
            v, b = p["v"], p["b"]
            if qp is not None:
                v, b = fixed16(v), fixed16(b)
            u = mm(xq, wgt(name, p["W"]))

            def step(c, t, v=v, b=b):
                uw, uf, ur = t
                f = jax.nn.sigmoid(uf + v[0] * c + b[0])
                r = jax.nn.sigmoid(ur + v[1] * c + b[1])
                c = f * c + (1.0 - f) * uw
                return c, (r * c, r)

            seq = tuple(u[..., k * h:(k + 1) * h].transpose(1, 0, 2)
                        for k in range(3))
            _, (hh, rr) = jax.lax.scan(
                step, jnp.zeros((x.shape[0], h), jnp.float32), seq,
                reverse=(d == "bwd"))
            hh, rr = hh.transpose(1, 0, 2), rr.transpose(1, 0, 2)
            if xq.shape[-1] == h:                        # highway
                hh = hh + (1.0 - rr) * xq
            hs.append(hh)
        x = jnp.concatenate(hs, axis=-1)
        if i < cfg["n_sru_layers"] - 1:
            pname = f"Pr{i + 1}"
            x = mm(act(pname, x), wgt(pname, params[pname]["W"]))
    return mm(act("FC", x), wgt("FC", params["FC"]["W"])) \
        + params["FC"]["b"]


def pooled_weights(params, cfg):
    """{layer: its MxV weights flattened, both directions pooled}."""
    out = {}
    for name in layer_names(cfg):
        if name.startswith("L"):
            out[name] = jnp.concatenate(
                [params[name][d]["W"].ravel() for d in ("fwd", "bwd")])
        else:
            out[name] = params[name]["W"].ravel()
    return out


# ------------------------------------------------------------ the program

def build_target(cfg, params, subsets, grids, score_baseline=True):
    """The program's ``TrainedSRU`` over the weights, fold and grids made
    here; its baseline error is the program's own unquantized score."""
    from repro.core.sru_experiment import TrainedSRU
    from repro.models.sru import SRUModelConfig

    pcfg = SRUModelConfig(
        name=cfg["name"], input_dim=cfg["input_dim"], hidden=cfg["hidden"],
        proj=cfg["proj"], n_sru_layers=cfg["n_sru_layers"],
        n_outputs=cfg["n_outputs"])
    target = TrainedSRU(pcfg, params, None, subsets, [], grids.act_ranges,
                        grids.wclips, grids.wranges, 0.0, 0.0)
    if score_baseline:
        target.baseline_val_error = target.val_error()
    return target


FAMILY = C.Family(
    layer_names=layer_names, weight_counts=weight_counts, work=work,
    init_weights=init_weights, make_inputs=make_features, forward=forward,
    pooled_weights=pooled_weights, build_target=build_target)
