"""xLSTM (sLSTM + mLSTM) language model.

Blocks alternate mLSTM (matrix memory, chunkwise-parallel linear attention
with per-head scalar exponential gating) and sLSTM (scalar memory, per-head
block-diagonal recurrence, sequential time scan) per arXiv:2405.04517.
Stabilized gating (m-state) in f32 throughout.

Layer stacking: scan over G = L/2 groups of (mLSTM, sLSTM) pairs.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models import common as cm
from repro.models import transformer as tf


# ------------------------------------------------------------------ mLSTM

def init_mlstm(key, cfg):
    D, H = cfg.d_model, cfg.n_heads
    di = cfg.ssm_d_inner
    ks = jax.random.split(key, 7)
    s = 1.0 / math.sqrt(D)
    return {
        "wq": cm.normal_init(ks[0], (D, di), s),
        "wk": cm.normal_init(ks[1], (D, di), s),
        "wv": cm.normal_init(ks[2], (D, di), s),
        "wi": cm.normal_init(ks[3], (D, H), s, jnp.float32),
        "wf": cm.normal_init(ks[4], (D, H), s, jnp.float32),
        "fbias": jnp.full((H,), 3.0, jnp.float32),   # open forget gates at init
        "wz": cm.normal_init(ks[5], (D, di), s),
        "wo": cm.normal_init(ks[6], (di, D), 1.0 / math.sqrt(di)),
    }


MLSTM_AXES = {"wq": ("embed", "ssm_inner"), "wk": ("embed", "ssm_inner"),
              "wv": ("embed", "ssm_inner"), "wi": ("embed", "heads"),
              "wf": ("embed", "heads"), "fbias": ("heads",),
              "wz": ("embed", "ssm_inner"), "wo": ("ssm_inner", "embed")}


def _mlstm_qkvg(p, cfg, x):
    B, T, D = x.shape
    H = cfg.n_heads
    dh = cfg.ssm_d_inner // H
    def proj(w):
        y = cm.dense(x, w)
        return y.reshape(B, T, H, dh)
    q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    logi = jnp.dot(x.astype(jnp.float32), p["wi"])            # (B,T,H)
    logf = jax.nn.log_sigmoid(jnp.dot(x.astype(jnp.float32), p["wf"])
                              + p["fbias"])
    return q, k, v, logi, logf


def mlstm_fwd(p, cfg, x, chunk: int = 128, return_state: bool = False):
    """Chunkwise-parallel mLSTM. x: (B,T,D) -> (B,T,D)."""
    B, T, D = x.shape
    H = cfg.n_heads
    dh = cfg.ssm_d_inner // H
    q, k, v, logi, logf = _mlstm_qkvg(p, cfg, x)
    chunk = min(chunk, T)
    nch = -(-T // chunk)
    pad = nch * chunk - T
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        logi = jnp.pad(logi, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        logf = jnp.pad(logf, ((0, 0), (0, pad), (0, 0)))

    def to_c(t):
        return t.reshape(B, nch, chunk, *t.shape[2:]).transpose(
            1, 0, 2, *range(3, t.ndim + 1))
    qc, kc, vc, ic, fc = map(to_c, (q, k, v, logi, logf))
    scale = 1.0 / math.sqrt(dh)

    def chunk_step(carry, xs):
        S, n, m = carry              # (B,H,dh,dh), (B,H,dh), (B,H)
        qk, kk, vk, ik, fk = xs
        g = jnp.cumsum(fk, axis=1)                            # (B,c,H)
        g_last = g[:, -1]                                     # (B,H)
        # stabilizers
        a = g + m[:, None]                                    # inter decay logits
        intra = ik[:, None, :, :] + (g[:, :, None, :] - g[:, None, :, :])
        # intra[b, t_q, t_k, h]; mask t_k <= t_q
        tq = jnp.arange(qk.shape[1])
        mask = tq[None, :, None, None] >= tq[None, None, :, None]
        intra = jnp.where(mask, intra, -1e30)
        m_intra = intra.max(axis=2)                           # (B,c,H)
        m_new_t = jnp.maximum(a, m_intra)                     # running stabilizer/time
        s_intra = jnp.einsum("bthd,bshd->btsh", qk.astype(jnp.float32),
                             kk.astype(jnp.float32)) * scale
        w_intra = jnp.exp(intra - m_new_t[:, :, None, :]) * s_intra * \
            (tq[None, :, None, None] >= tq[None, None, :, None])
        y_intra = jnp.einsum("btsh,bshd->bthd", w_intra, vk.astype(jnp.float32))
        # normalizer = sum of attention scores (matches the step recurrence
        # |q^T n| with n = sum exp * k): intra part is the plain row sum
        sum_intra = w_intra.sum(axis=2)                       # (B,c,H)
        w_inter = jnp.exp(a - m_new_t)                        # (B,c,H)
        y_inter = jnp.einsum("bthd,bhde,bth->bthe",
                             qk.astype(jnp.float32) * scale, S, w_inter)
        n_inter = jnp.einsum("bthd,bhd,bth->bth",
                             qk.astype(jnp.float32) * scale, n, w_inter)
        denom = jnp.maximum(jnp.abs(sum_intra + n_inter),
                            jnp.exp(-m_new_t))[..., None]
        y = (y_intra + y_inter) / denom                       # (B,c,H,dh)
        # state update
        m_next = jnp.maximum(g_last + m, (ik + (g_last[:, None] - g)).max(1))
        up_w = jnp.exp(ik + (g_last[:, None] - g) - m_next[:, None])
        S_new = S * jnp.exp(g_last + m - m_next)[..., None, None] + \
            jnp.einsum("bthd,bthe,bth->bhde", kk.astype(jnp.float32),
                       vk.astype(jnp.float32), up_w)
        n_new = n * jnp.exp(g_last + m - m_next)[..., None] + \
            jnp.einsum("bthd,bth->bhd", kk.astype(jnp.float32), up_w)
        return (S_new, n_new, m_next), y

    S0 = jnp.zeros((B, H, dh, dh), jnp.float32)
    n0 = jnp.zeros((B, H, dh), jnp.float32)
    m0 = jnp.zeros((B, H), jnp.float32)
    (Sf, nf, mf), yc = jax.lax.scan(chunk_step, (S0, n0, m0),
                                    (qc, kc, vc, ic, fc))
    y = yc.transpose(1, 0, 2, 3, 4).reshape(B, nch * chunk, H * dh)[:, :T]
    z = jax.nn.silu(cm.dense(x, p["wz"]).astype(jnp.float32))
    y = (y * z).astype(x.dtype)
    out = cm.dense(y, p["wo"])
    if return_state:
        return out, {"S": Sf, "n": nf, "m": mf}
    return out


def mlstm_step(p, cfg, x, state):
    """x: (B,1,D); state {'S','n','m'}."""
    B = x.shape[0]
    H = cfg.n_heads
    dh = cfg.ssm_d_inner // H
    q, k, v, logi, logf = _mlstm_qkvg(p, cfg, x)
    q, k, v = (t[:, 0].astype(jnp.float32) for t in (q, k, v))
    logi, logf = logi[:, 0], logf[:, 0]
    S, n, m = state["S"], state["n"], state["m"]
    m_new = jnp.maximum(logf + m, logi)
    fw = jnp.exp(logf + m - m_new)[..., None, None]
    iw = jnp.exp(logi - m_new)[..., None, None]
    S_new = S * fw + iw * jnp.einsum("bhd,bhe->bhde", k, v)
    n_new = n * fw[..., 0] + iw[..., 0] * k
    scale = 1.0 / math.sqrt(dh)
    y = jnp.einsum("bhd,bhde->bhe", q * scale, S_new)
    denom = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q * scale, n_new)),
                        jnp.exp(-m_new))[..., None]
    y = (y / denom).reshape(B, 1, H * dh)
    z = jax.nn.silu(cm.dense(x, p["wz"]).astype(jnp.float32))
    y = (y * z).astype(x.dtype)
    return cm.dense(y, p["wo"]), {"S": S_new, "n": n_new, "m": m_new}


# ------------------------------------------------------------------ sLSTM

def init_slstm(key, cfg):
    D, H = cfg.d_model, cfg.n_heads
    di = cfg.ssm_d_inner
    dh = di // H
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(D)
    return {
        "wx": cm.normal_init(ks[0], (D, 4 * di), s),          # i,f,z,o pre-acts
        "r": cm.normal_init(ks[1], (H, dh, 4 * dh), 1.0 / math.sqrt(dh),
                            jnp.float32),
        "bias": jnp.zeros((4 * di,), jnp.float32),
        "wo": cm.normal_init(ks[3], (di, D), 1.0 / math.sqrt(di)),
    }


SLSTM_AXES = {"wx": ("embed", "ssm_inner"), "r": ("heads", None, None),
              "bias": ("ssm_inner",), "wo": ("ssm_inner", "embed")}


def _slstm_cell(p, cfg, pre, state, rec_fn=None):
    """pre: (B,H,dh,4) gate pre-activations (x-part); state dict.
    ``rec_fn(h)`` -> (B,H,4*dh) replaces the recurrent product with
    ``p["r"]`` (the search's banked population forward passes one)."""
    c, n, h, m = state["c"], state["n"], state["h"], state["m"]
    if rec_fn is None:
        rec = jnp.einsum("bhd,hde->bhe", h, p["r"])           # (B,H,4*dh)
    else:
        rec = rec_fn(h)
    B, H = h.shape[0], h.shape[1]
    dh = h.shape[2]
    rec = rec.reshape(B, H, 4, dh).transpose(0, 1, 3, 2)
    g = pre + rec
    logi = g[..., 0]
    logf = jax.nn.log_sigmoid(g[..., 1])
    z = jnp.tanh(g[..., 2])
    o = jax.nn.sigmoid(g[..., 3])
    m_new = jnp.maximum(logf + m, logi)
    i_ = jnp.exp(logi - m_new)
    f_ = jnp.exp(logf + m - m_new)
    c_new = f_ * c + i_ * z
    n_new = jnp.maximum(f_ * n + i_, 1e-6)
    h_new = o * (c_new / n_new)
    return {"c": c_new, "n": n_new, "h": h_new, "m": m_new}


def slstm_init_state(cfg, B):
    H = cfg.n_heads
    dh = cfg.ssm_d_inner // H
    zero = lambda: jnp.zeros((B, H, dh), jnp.float32)
    return {"c": zero(), "n": zero(), "h": zero(),
            "m": jnp.zeros((B, H, dh), jnp.float32)}


def slstm_fwd(p, cfg, x, return_state: bool = False, rec_fn=None):
    """x: (B,T,D) -> (B,T,D); ``rec_fn`` as in ``_slstm_cell``."""
    B, T, D = x.shape
    H = cfg.n_heads
    di = cfg.ssm_d_inner
    dh = di // H
    pre = (jnp.dot(x, p["wx"], preferred_element_type=jnp.float32)
           + p["bias"]).reshape(B, T, H, dh, 4)

    def step(state, pre_t):
        new = _slstm_cell(p, cfg, pre_t, state, rec_fn)
        return new, new["h"]

    state0 = slstm_init_state(cfg, B)
    statef, hs = jax.lax.scan(step, state0, pre.transpose(1, 0, 2, 3, 4))
    y = hs.transpose(1, 0, 2, 3).reshape(B, T, di).astype(x.dtype)
    out = cm.dense(y, p["wo"])
    if return_state:
        return out, statef
    return out


def slstm_step(p, cfg, x, state):
    B = x.shape[0]
    H = cfg.n_heads
    di = cfg.ssm_d_inner
    dh = di // H
    pre = (jnp.dot(x[:, 0], p["wx"], preferred_element_type=jnp.float32)
           + p["bias"]).reshape(B, H, dh, 4)
    new = _slstm_cell(p, cfg, pre, state)
    y = new["h"].reshape(B, 1, di).astype(x.dtype)
    return cm.dense(y, p["wo"]), new


# ------------------------------------------------------------------ LM

def init_block_pair(key, cfg):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"norm_m": jnp.ones((cfg.d_model,), jnp.float32),
            "mlstm": init_mlstm(k1, cfg),
            "norm_s": jnp.ones((cfg.d_model,), jnp.float32),
            "slstm": init_slstm(k2, cfg)}


PAIR_AXES = {"norm_m": ("embed",), "mlstm": MLSTM_AXES,
             "norm_s": ("embed",), "slstm": SLSTM_AXES}


def init_lm(key, cfg):
    ke, kl, kh = jax.random.split(key, 3)
    D, V = cfg.d_model, cfg.padded_vocab
    G = cfg.n_layers // 2
    return {
        "embed": cm.normal_init(ke, (V, D), 1.0 / math.sqrt(D)),
        "pairs": jax.vmap(partial(init_block_pair, cfg=cfg))(
            jax.random.split(kl, G)),
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": cm.normal_init(kh, (D, V), 1.0 / math.sqrt(D)),
    }


def lm_axes(cfg):
    return {"embed": ("vocab", "embed"),
            "pairs": tf._stacked(PAIR_AXES, 1),
            "final_norm": ("embed",),
            "lm_head": ("embed", "vocab")}


def forward(params, cfg, tokens, extra_embeds=None, remat: bool = True):
    x = tf.embed_tokens(params, cfg, tokens, extra_embeds)

    def pair_body(h, bp):
        h = h + mlstm_fwd(bp["mlstm"], cfg,
                          cm.rms_norm(h, bp["norm_m"], cfg.norm_eps))
        h = h + slstm_fwd(bp["slstm"], cfg,
                          cm.rms_norm(h, bp["norm_s"], cfg.norm_eps))
        return h, None
    body = jax.checkpoint(pair_body) if remat else pair_body
    x, _ = jax.lax.scan(body, x, params["pairs"])
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return tf.logits_head(params, cfg, x)


def init_state(cfg, batch: int, max_len: int = 0):
    G = cfg.n_layers // 2
    H = cfg.n_heads
    dh = cfg.ssm_d_inner // H
    z = lambda *s: jnp.zeros((G, batch) + s, jnp.float32)
    return {
        "mlstm": {"S": z(H, dh, dh), "n": z(H, dh), "m": z(H)},
        "slstm": {"c": z(H, dh), "n": z(H, dh), "h": z(H, dh), "m": z(H, dh)},
        "cur": jnp.zeros((), jnp.int32),
    }


def state_axes(cfg):
    return {"mlstm": {"S": ("stack", "cache_batch", "heads", None, None),
                      "n": ("stack", "cache_batch", "heads", None),
                      "m": ("stack", "cache_batch", "heads")},
            "slstm": {k: ("stack", "cache_batch", "heads", None)
                      for k in ("c", "n", "h", "m")},
            "cur": ()}


def decode_step(params, cfg, cache, token):
    x = tf.embed_tokens(params, cfg, token)

    def pair_body(h, xs):
        bp, mst, sst = xs
        y, mst2 = mlstm_step(bp["mlstm"], cfg,
                             cm.rms_norm(h, bp["norm_m"], cfg.norm_eps), mst)
        h = h + y
        y, sst2 = slstm_step(bp["slstm"], cfg,
                             cm.rms_norm(h, bp["norm_s"], cfg.norm_eps), sst)
        return h + y, (mst2, sst2)

    x, (mst, sst) = jax.lax.scan(
        pair_body, x, (params["pairs"], cache["mlstm"], cache["slstm"]))
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return tf.logits_head(params, cfg, x), \
        {"mlstm": mst, "slstm": sst, "cur": cache["cur"] + 1}


def prefill(params, cfg, tokens):
    """Run the prompt, return (last_logits, state cache) for decode."""
    x = tf.embed_tokens(params, cfg, tokens)

    def pair_body(h, bp):
        y, mst = mlstm_fwd(bp["mlstm"], cfg,
                           cm.rms_norm(h, bp["norm_m"], cfg.norm_eps),
                           return_state=True)
        h = h + y
        y, sst = slstm_fwd(bp["slstm"], cfg,
                           cm.rms_norm(h, bp["norm_s"], cfg.norm_eps),
                           return_state=True)
        return h + y, (mst, sst)

    x, (mst, sst) = jax.lax.scan(pair_body, x, params["pairs"])
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = tf.logits_head(params, cfg, x[:, -1:])
    return logits, {"mlstm": mst, "slstm": sst,
                    "cur": jnp.asarray(tokens.shape[1], jnp.int32)}
