"""xLSTM language model (arXiv:2405.04517), as the program builds it:
alternating (mLSTM, sLSTM) block pairs with pre-norm residuals, searched
per block. Weights, fold, plain reference, calibration and work counts for
the chip benchmark.

Nothing here imports the program except ``build_target``, which hands the
weights, the fold and the grids made here to the program's own
``XLSTMTarget``. The reference is a plain float32 forward of one
allocation at a time, each matmul's operands and the activation stream
rounded to the precision the config states, products summed in float32 at
``Precision.HIGHEST``; stabilized exponential gating:

  mLSTM  C_t = f_t C_{t-1} + i_t k_t v_t^T, n_t = f_t n_{t-1} + i_t k_t,
         y_t = (q_t/sqrt(d)) C_t / max(|(q_t/sqrt(d)) . n_t|, e^{-m_t}),
         computed in its parallel form (``forward``'s ``mlstm``)
         out = (y * silu(x W_z)) W_o
  sLSTM  per head, gates (i, f, z, o) from x W_x + b + h_{t-1} R
         c_t = f' c + i' tanh(z); n_t = max(f' n + i', 1e-6); h_t = o c_t / n_t
         out = h W_o

The searchable layers are the blocks' matmul weight sets (m{g}: wq, wk,
wv, wz, wo; s{g}: wx, r, wo) and the LM head; each block input and the
head input take the allocation's activation grid.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from families import common as C

QUANT_LEAVES = {"m": ("wq", "wk", "wv", "wz", "wo"), "s": ("wx", "r", "wo")}


# ------------------------------------------------------------ geometry

def dims(cfg):
    d, h = cfg["d_model"], cfg["n_heads"]
    di = cfg["ssm_expand"] * d
    return d, h, di, di // h, -(-cfg["vocab_size"] // 256) * 256


def layer_names(cfg):
    names = []
    for g in range(cfg["n_layers"] // 2):
        names += [f"m{g}", f"s{g}"]
    return names + ["head"]


def leaf_shapes(cfg):
    """{layer: {leaf: (shape, dtype)}} of the searchable weights."""
    d, h, di, dh, vp = dims(cfg)
    m = {k: ((d, di), "bfloat16") for k in ("wq", "wk", "wv", "wz")}
    m["wo"] = ((di, d), "bfloat16")
    s = {"wx": ((d, 4 * di), "bfloat16"), "r": ((h, dh, 4 * dh), "float32"),
         "wo": ((di, d), "bfloat16")}
    out = {}
    for name in layer_names(cfg):
        if name == "head":
            out[name] = {"lm_head": ((d, vp), "bfloat16")}
        else:
            out[name] = m if name[0] == "m" else s
    return out


def weight_counts(cfg):
    return {name: sum(int(np.prod(s)) for s, _ in leaves.values())
            for name, leaves in leaf_shapes(cfg).items()}


def work(cfg, mix):
    """The algorithm's FLOPs and bytes of one dispatched population lane:
    2 x every weight matrix's size per token (searchable weights, the
    sLSTM recurrence and the gate projections), plus the mLSTM's matrix
    memory update and readout (4 x heads x head_dim^2 per token); the
    lane's quantized weights read once at their storage width. The fold
    (tokens and labels) is read once per dispatch."""
    d, h, di, dh, _ = dims(cfg)
    rows, length = C.fold_shape(mix)
    tokens = rows * length
    n_m = cfg["n_layers"] // 2
    gate_weights = n_m * 2 * d * h
    per_token = (2 * (sum(weight_counts(cfg).values()) + gate_weights)
                 + n_m * 4 * h * dh * dh)
    lane_bytes = sum(int(np.prod(s)) * C.BYTES[t]
                     for leaves in leaf_shapes(cfg).values()
                     for s, t in leaves.values())
    return {"flops_per_lane": float(per_token * tokens),
            "bytes_per_lane": float(lane_bytes),
            "bytes_per_dispatch": float(tokens * 2 * 4)}


# ------------------------------------------------------------ weights, fold

def init_weights(cfg, seed):
    """The program's parameter pytree in the types it is served in (bf16
    matrices, f32 gates, recurrence and norms), drawn on the device in one
    call: N(0, 1/fan_in) matrices, forget-gate bias 3, unit norms."""
    d, h, di, dh, vp = dims(cfg)
    g = cfg["n_layers"] // 2
    bf, f32 = jnp.bfloat16, jnp.float32

    def nrm(k, shape, fan_in, dtype=bf):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    @jax.jit
    def make(key):
        ke, kp, kh = jax.random.split(key, 3)
        km = jax.random.split(kp, 13)
        mlstm = {"wq": nrm(km[0], (g, d, di), d),
                 "wk": nrm(km[1], (g, d, di), d),
                 "wv": nrm(km[2], (g, d, di), d),
                 "wi": nrm(km[3], (g, d, h), d, f32),
                 "wf": nrm(km[4], (g, d, h), d, f32),
                 "fbias": jnp.full((g, h), 3.0, f32),
                 "wz": nrm(km[5], (g, d, di), d),
                 "wo": nrm(km[6], (g, di, d), di)}
        slstm = {"wx": nrm(km[7], (g, d, 4 * di), d),
                 "r": nrm(km[8], (g, h, dh, 4 * dh), dh, f32),
                 "bias": jnp.zeros((g, 4 * di), f32),
                 "wo": nrm(km[9], (g, di, d), di)}
        return {"embed": nrm(ke, (vp, d), d),
                "pairs": {"norm_m": jnp.ones((g, d), f32), "mlstm": mlstm,
                          "norm_s": jnp.ones((g, d), f32), "slstm": slstm},
                "final_norm": jnp.ones((d,), f32),
                "lm_head": nrm(kh, (d, vp), d)}

    return make(C.key(seed, "weights"))


def make_tokens(cfg, mix, seed):
    """Bigram-structured token rows: next = (5 prev + noise) mod vocab,
    noise uniform over ``mix["fold"]["n_noise"]`` values."""
    rows, length = C.fold_shape(mix)
    vocab, n_noise = cfg["vocab_size"], mix["fold"]["n_noise"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        first = jax.random.randint(k1, (rows,), 0, vocab)
        noise = jax.random.randint(k2, (length - 1, rows), 0, n_noise)

        def step(prev, n):
            nxt = (prev * 5 + n) % vocab
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, noise)
        return jnp.concatenate([first[:, None], rest.T], axis=1)

    return make(C.key(seed, "fold")).astype(jnp.int32)


# ------------------------------------------------------------ reference

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def forward(params, cfg, tokens, qp=None, prec=C.EXACT, taps=None):
    """Plain float32 forward: tokens (B, T) -> logits (B, T, padded vocab).
    ``qp`` and ``taps`` as in ``families.sru.forward``. ``prec``
    (``common.Precision``) rounds every matmul operand, and the activation
    stream at each point where the program keeps it narrow: the residual
    stream, the norms' and the activation grids' outputs, the q/k/v/z
    projections, the gated mLSTM output, the sLSTM output before its
    projection, and each block's output. The config states bfloat16 for
    both, as the TPU computes a float32 matmul at its default precision."""
    d, h, di, dh, _ = dims(cfg)
    names = layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}
    eps = cfg["norm_eps"]
    ro, ra, dot = prec.mm(), prec.act(), prec.dot_precision()

    def mm(spec, a, w):
        return jnp.einsum(spec, ro(a), ro(w), precision=dot)

    def act(name, x):
        if taps is not None:
            taps[name] = jnp.max(jnp.abs(x), axis=(1, 2))
        if qp is None:
            return x
        r = qp[li[name]]
        return ra(C.fake_quant(x, r[3], r[4], r[5]))

    def wgt(name, w):
        """A searchable matrix on the allocation's grid, stored in the
        matrix's own type (bfloat16; the sLSTM recurrence float32)."""
        if qp is None:
            return w.astype(jnp.float32)
        r = qp[li[name]]
        q = C.fake_quant(w.astype(jnp.float32), r[0], r[1], r[2])
        return q.astype(w.dtype).astype(jnp.float32)

    def mlstm(p, name, x):
        """Parallel form over the whole sequence (paper appendix): with
        G_t the cumulative log forget gate and m_t = max(G_t, max_{s<=t}
        log i_s + G_t - G_s), y_t = sum_s a_ts v_s / max(|sum_s a_ts|,
        e^{-m_t}) where a_ts = (q_t . k_s / sqrt(d)) e^{log i_s + G_t -
        G_s - m_t} for s <= t. Equal to the step recurrence
        C_t = f_t C_{t-1} + i_t k_t v_t^T from a zero state."""
        b, t, _ = x.shape
        proj = lambda k: ra(mm("btd,de->bte", x, wgt(name, p[k]))).reshape(
            b, t, h, dh)
        q, k, v = proj("wq"), proj("wk"), proj("wv")
        logi = mm("btd,dh->bth", x, p["wi"])
        logf = jax.nn.log_sigmoid(mm("btd,dh->bth", x, p["wf"])
                                  + p["fbias"])
        g = jnp.cumsum(logf, axis=1)                        # (b, t, h)
        causal = (jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
                  )[None, :, :, None]
        gate = jnp.where(causal, logi[:, None] + g[:, :, None] - g[:, None],
                         -1e30)                             # (b, tq, tk, h)
        m = jnp.maximum(g, gate.max(axis=2))
        score = mm("bthd,bshd->btsh", q, k) / math.sqrt(dh)
        a = jnp.exp(gate - m[:, :, None]) * score * causal
        y = mm("btsh,bshd->bthd", a, v)
        den = jnp.maximum(jnp.abs(a.sum(axis=2)), jnp.exp(-m))[..., None]
        y = (y / den).reshape(b, t, di)
        z = jax.nn.silu(ra(mm("btd,de->bte", x, wgt(name, p["wz"]))))
        return ra(mm("btd,de->bte", ra(y * z), wgt(name, p["wo"])))

    def slstm(p, name, x):
        b, t, _ = x.shape
        pre = (mm("btd,de->bte", x, wgt(name, p["wx"])) + p["bias"]
               ).reshape(b, t, h, dh, 4)
        r = wgt(name, p["r"])

        def step(state, pre_t):
            c, n, hh, m = state
            rec = mm("bhd,hde->bhe", hh, r).reshape(b, h, 4, dh)
            g = pre_t + rec.transpose(0, 1, 3, 2)
            logi, logf = g[..., 0], jax.nn.log_sigmoid(g[..., 1])
            z, o = jnp.tanh(g[..., 2]), jax.nn.sigmoid(g[..., 3])
            m_new = jnp.maximum(logf + m, logi)
            i_, f_ = jnp.exp(logi - m_new), jnp.exp(logf + m - m_new)
            c = f_ * c + i_ * z
            n = jnp.maximum(f_ * n + i_, 1e-6)
            hh = o * (c / n)
            return (c, n, hh, m_new), hh

        zero = jnp.zeros((b, h, dh))
        _, hs = jax.lax.scan(step, (zero, zero, zero, zero),
                             jnp.moveaxis(pre, 1, 0))
        hs = ra(jnp.moveaxis(hs, 0, 1).reshape(b, t, di))
        return ra(mm("btd,de->bte", hs, wgt(name, p["wo"])))

    x = ra(jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32))
    pairs = params["pairs"]
    for gi in range(cfg["n_layers"] // 2):
        bp = jax.tree.map(lambda a, gi=gi: a[gi], pairs)
        m, s = f"m{gi}", f"s{gi}"
        x = ra(x + mlstm(bp["mlstm"], m,
                         act(m, ra(_rms_norm(x, bp["norm_m"], eps)))))
        x = ra(x + slstm(bp["slstm"], s,
                         act(s, ra(_rms_norm(x, bp["norm_s"], eps)))))
    x = act("head", ra(_rms_norm(x, params["final_norm"], eps)))
    return mm("btd,dv->btv", x, wgt("head", params["lm_head"]))


def pooled_weights(params, cfg):
    out = {}
    for name in layer_names(cfg):
        if name == "head":
            out[name] = params["lm_head"].ravel()
            continue
        g, kind = int(name[1:]), ("mlstm" if name[0] == "m" else "slstm")
        out[name] = jnp.concatenate(
            [params["pairs"][kind][k][g].astype(jnp.float32).ravel()
             for k in QUANT_LEAVES[name[0]]])
    return out


# ------------------------------------------------------------ the program

def program_config(cfg):
    import dataclasses
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(cfg["program_config"]), n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_heads"], vocab_size=cfg["vocab_size"],
        ssm_expand=cfg["ssm_expand"], norm_eps=cfg["norm_eps"])


def build_target(cfg, params, subsets, grids, score_baseline=True):
    """The program's ``XLSTMTarget`` over the weights, fold and grids made
    here; its baseline error is the program's own unquantized score."""
    from repro.core.xlstm_target import XLSTMTarget

    target = XLSTMTarget(program_config(cfg), params, subsets, [],
                         grids.act_ranges, grids.wclips, grids.wranges)
    if score_baseline:
        target.baseline_val_error = target.val_error()
    return target


FAMILY = C.Family(
    layer_names=layer_names, weight_counts=weight_counts, work=work,
    init_weights=init_weights, make_inputs=make_tokens, forward=forward,
    pooled_weights=pooled_weights, build_target=build_target)
