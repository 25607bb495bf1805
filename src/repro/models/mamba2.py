"""Mamba-2 mixer (SSD, arXiv:2405.21060) as granite-4.0-h builds it (the HF
``granitemoehybrid`` layout): heads with one scalar decay each, B and C
shared by the heads of each group.

    [z | xBC | dt] = x W_in
    xBC = silu(causal depthwise conv(xBC) + bias);  xBC -> x, B, C
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (one per head)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               (P x N a head)
    y_t = S_t C_t + D x_t
    out = rmsnorm(y * silu(z)) W_out

The program runs the state-space dual (SSD) in chunks (``ssd_chunked``): a
quadratic form inside each chunk and the state passed from chunk to chunk
in float32. ``ssd_step`` is the single-step form and ``ssd_sequential``
the recurrence it makes; tests hold the chunked form to it. Matrices and
the activation stream are bfloat16; dt, A, the state, the gated norm and
every sum are float32. The named scopes ``granite.ssd_chunk`` (the
quadratic form) and ``granite.ssd_state`` (the state read-out and update)
carry into the compiled program's op metadata.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import common as cm

F32 = jnp.float32


def dims(cfg):
    """(d_inner, groups x d_state, heads, head width) of a config."""
    return (cfg.ssm_n_heads * cfg.ssm_head_dim,
            cfg.ssm_n_groups * cfg.ssm_d_state, cfg.ssm_n_heads,
            cfg.ssm_head_dim)


def init_mamba2(key, cfg, dtype=jnp.bfloat16):
    """One mixer's parameters: N(0, 1/fan_in) matrices and conv, zero conv
    bias, unit gated norm, D = 1, A_log = log(1..H) (HF's initialisation)
    and dt_bias the inverse softplus of dt spread log-uniformly over
    [1e-3, 1e-1] (the reference Mamba-2's)."""
    D = cfg.d_model
    di, gn, H, _ = dims(cfg)
    conv = di + 2 * gn
    ks = jax.random.split(key, 3)
    dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), H))
    return {
        "in_proj": cm.normal_init(ks[0], (D, 2 * di + 2 * gn + H),
                                  1.0 / math.sqrt(D), dtype),
        "conv_w": cm.normal_init(ks[1], (cfg.ssm_d_conv, conv),
                                 1.0 / math.sqrt(cfg.ssm_d_conv), F32),
        "conv_b": jnp.zeros((conv,), F32),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(F32),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=F32)),
        "D": jnp.ones((H,), F32),
        "norm": jnp.ones((di,), F32),
        "out_proj": cm.normal_init(ks[2], (di, D), 1.0 / math.sqrt(di),
                                   dtype),
    }


def causal_conv(x, w, b):
    """Depthwise causal conv over time in float32: x (B, T, C), w (K, C),
    b (C,); y_t = b + sum_k w[k] x_{t-K+1+k} (zero before the start)."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(F32), ((0, 0), (K - 1, 0), (0, 0)))
    y = b
    for k in range(K):
        y = y + xp[:, k:k + T] * w[k]
    return y


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """The SSD over a whole sequence in chunks of ``chunk`` steps, from a
    zero state. x (b, T, H, P), dt (b, T, H), A (H,), B and C (b, T, G, N),
    all float32; returns y (b, T, H, P) without the D skip. Inside a chunk,
    with cs the running sum of dt A from the chunk's start,

        y_l = sum_{s<=l} exp(cs_l - cs_s) (C_l . B_s) dt_s x_s
              + exp(cs_l) S_in C_l
        S_out = exp(cs_L) S_in + sum_s exp(cs_L - cs_s) dt_s x_s B_s^T

    T need not be a multiple of ``chunk``: padded steps have dt = 0, so
    they neither decay the state nor add to it."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    L = min(chunk, T)
    nc = -(-T // L)
    pad = nc * L - T
    if pad:
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad))
                               + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, B, C))

    def chunks(t):                        # (b, nc*L, ...) -> (nc, b, L, ...)
        return jnp.moveaxis(t.reshape((b, nc, L) + t.shape[2:]), 1, 0)

    xs = chunks(x.reshape(b, nc * L, G, H // G, P))   # (nc, b, L, G, Hg, P)
    dts = chunks(dt.reshape(b, nc * L, G, H // G))    # (nc, b, L, G, Hg)
    Ag = A.reshape(G, H // G)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]

    def step(S, inp):                     # S (b, G, Hg, P, N) float32
        xc, dtc, Bc, Cc = inp
        cs = jnp.cumsum(dtc * Ag, axis=1)                   # (b, L, G, Hg)
        with jax.named_scope("granite.ssd_chunk"):
            seg = cs[:, :, None] - cs[:, None]              # (b, l, s, G, Hg)
            decay = jnp.exp(jnp.where(causal[None, :, :, None, None], seg,
                                      -jnp.inf))
            cb = jnp.einsum("blgn,bsgn->blsg", Cc, Bc)
            m = cb[..., None] * decay * dtc[:, None]
            y = jnp.einsum("blsgh,bsghp->blghp", m, xc)
        with jax.named_scope("granite.ssd_state"):
            y = y + jnp.einsum("blgn,bghpn->blghp", Cc, S) * jnp.exp(
                cs)[..., None]
            w = jnp.exp(cs[:, -1:] - cs) * dtc              # (b, L, G, Hg)
            S = (jnp.exp(cs[:, -1])[..., None, None] * S
                 + jnp.einsum("blgh,blghp,blgn->bghpn", w, xc, Bc))
        return S, y

    S0 = jnp.zeros((b, G, H // G, P, N), F32)
    _, ys = jax.lax.scan(step, S0, (xs, dts, chunks(B), chunks(C)))
    return jnp.moveaxis(ys, 0, 1).reshape(b, nc * L, H, P)[:, :T]


def ssd_step(S, x_t, dt_t, A, B_t, C_t):
    """One step of the recurrence: S (b, H, P, N), x_t (b, H, P), dt_t
    (b, H), B_t and C_t (b, G, N). Returns (S_t, y_t) without the D skip."""
    rep = S.shape[1] // B_t.shape[1]
    Bh, Ch = (jnp.repeat(t, rep, axis=1) for t in (B_t, C_t))   # (b, H, N)
    S = (jnp.exp(dt_t * A)[..., None, None] * S
         + (dt_t[..., None] * x_t)[..., None] * Bh[:, :, None, :])
    return S, jnp.einsum("bhpn,bhn->bhp", S, Ch)


def ssd_sequential(x, dt, A, B, C):
    """``ssd_step`` over the sequence from a zero state (shapes as
    ``ssd_chunked``)."""
    b, _, H, P = x.shape
    S0 = jnp.zeros((b, H, P, B.shape[-1]), F32)
    _, ys = jax.lax.scan(
        lambda S, inp: ssd_step(S, *inp[:2], A, *inp[2:]), S0,
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, B, C)))
    return jnp.moveaxis(ys, 0, 1)


def mamba2_mixer(p, cfg, x):
    """The mixer on a normed input x (B, T, D) bfloat16 -> (B, T, D)."""
    Bsz, T, _ = x.shape
    di, gn, H, P = dims(cfg)
    G = cfg.ssm_n_groups
    zxd = jnp.dot(x, p["in_proj"], preferred_element_type=F32)
    z = zxd[..., :di].astype(x.dtype)
    xbc = zxd[..., di:2 * di + 2 * gn].astype(x.dtype)
    dt = jax.nn.softplus(zxd[..., 2 * di + 2 * gn:] + p["dt_bias"])
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"])
                      ).astype(x.dtype).astype(F32)
    xs = xbc[..., :di].reshape(Bsz, T, H, P)
    Bm = xbc[..., di:di + gn].reshape(Bsz, T, G, -1)
    Cm = xbc[..., di + gn:].reshape(Bsz, T, G, -1)
    A = -jnp.exp(p["A_log"])
    y = ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
    y = (y + p["D"][:, None] * xs).reshape(Bsz, T, di)
    g = y * jax.nn.silu(z.astype(F32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg.norm_eps) * p["norm"]
    return cm.dense(g.astype(x.dtype), p["out_proj"])
