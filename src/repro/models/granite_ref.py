"""Plain float32 reference of the granite-4.0-h hybrid's forward under one
quantization allocation: ``jax.numpy`` only, every matmul at
``jax.default_matmul_precision("highest")``, the Mamba-2 state updated step
by step (a sequential recurrence, no chunks), softmax attention written
out, no banks, no vmap, no kernels. Tests hold the search's banked
population forward (``core/granite_target.py``) to it.

It reads the program's parameter pytree (``granite_target.init_params``)
and grid rows in ``granite_target.quant_layer_names`` order.

Departures from the published description (HF ``granitemoehybrid``,
https://huggingface.co/ibm-granite/granite-4.0-h-micro):
- the allocation's fake quantization, which the published model does not
  have: each searchable matrix on its weight grid (the result kept in the
  leaf's own type, bfloat16, as the program stores it) and each searchable
  layer's normed input on its activation grid; the tied table is
  quantized in the lookup and in the head alike;
- float32 activations throughout, where the released checkpoint runs in
  bfloat16;
- the vocabulary is whatever the configuration holds (a slice of the
  published one in the benchmark's cut);
- no cache and no decoding: one forward over whole sequences.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _fq(x, r):
    return jnp.clip(jnp.round(x / r[0]), r[1], r[2]) * r[0]


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def forward(params, cfg, tokens, qp=None):
    """tokens (B, T) -> logits (B, T, vocab) float32. ``qp``: (L, 6) grid
    rows (w_scale, w_lo, w_hi, a_scale, a_lo, a_hi) of the searchable
    layers, or None for the unquantized model."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, tokens, qp)


def _forward(params, cfg, tokens, qp):
    n = cfg.n_layers
    names = [f"{kind}{i}" for i in range(n) for kind in ("mix", "mlp")]
    row = {name: i for i, name in enumerate(names + ["head"])}
    eps = cfg.norm_eps

    def w(name, leaf):
        if qp is None:
            return leaf.astype(F32)
        q = _fq(leaf.astype(F32), qp[row[name]][:3])
        return q.astype(leaf.dtype).astype(F32)

    def a(name, x):
        return x if qp is None else _fq(x, qp[row[name]][3:])

    def mamba(p, name, x):
        Bsz, T, _ = x.shape
        H, P, G = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups
        di, gn = H * P, G * cfg.ssm_d_state
        zxd = x @ w(name, p["in_proj"])
        z, xbc, dt = zxd[..., :di], zxd[..., di:2 * di + 2 * gn], \
            zxd[..., 2 * di + 2 * gn:]
        K = p["conv_w"].shape[0]
        pad = jnp.concatenate([jnp.zeros((Bsz, K - 1, xbc.shape[-1])), xbc],
                              axis=1)
        conv = p["conv_b"] + sum(pad[:, k:k + T] * p["conv_w"][k]
                                 for k in range(K))
        xbc = _silu(conv)
        xs = xbc[..., :di].reshape(Bsz, T, H, P)
        Bm = jnp.repeat(xbc[..., di:di + gn].reshape(Bsz, T, G, -1),
                        H // G, axis=2)                   # (B, T, H, N)
        Cm = jnp.repeat(xbc[..., di + gn:].reshape(Bsz, T, G, -1),
                        H // G, axis=2)
        dt = _softplus(dt + p["dt_bias"])                 # (B, T, H)
        A = -jnp.exp(p["A_log"])

        def step(S, t):                                   # S (B, H, P, N)
            S = (jnp.exp(dt[:, t] * A)[..., None, None] * S
                 + dt[:, t][..., None, None] * xs[:, t][..., None]
                 * Bm[:, t][:, :, None, :])
            return S, jnp.einsum("bhpn,bhn->bhp", S, Cm[:, t])

        S0 = jnp.zeros((Bsz, H, P, cfg.ssm_d_state))
        _, ys = jax.lax.scan(step, S0, jnp.arange(T))
        y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * xs
        g = y.reshape(Bsz, T, di) * _silu(z)
        return _rms(g, p["norm"], eps) @ w(name, p["out_proj"])

    def attention(p, name, x):
        Bsz, T, _ = x.shape
        H, KV = cfg.n_heads, cfg.n_kv_heads
        q = jnp.einsum("btd,dhk->bthk", x, w(name, p["wq"]))
        k = jnp.einsum("btd,dhk->bthk", x, w(name, p["wk"]))
        v = jnp.einsum("btd,dhk->bthk", x, w(name, p["wv"]))
        k, v = (jnp.repeat(t, H // KV, axis=2) for t in (k, v))
        scale = cfg.attention_multiplier or 1.0 / math.sqrt(q.shape[-1])
        s = jnp.einsum("bthk,bshk->bhts", q, k) * scale
        causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = jnp.einsum("bhts,bshk->bthk", e / e.sum(-1, keepdims=True), v)
        return jnp.einsum("bthk,hkd->btd", o, w(name, p["wo"]))

    def mlp(p, name, x):
        h = _silu(x @ w(name, p["w_gate"])) * (x @ w(name, p["w_up"]))
        return h @ w(name, p["w_down"])

    table = w("head", params["embed"])
    h = table[tokens] * cfg.embedding_multiplier
    for i, layer in enumerate(params["layers"]):
        x = a(f"mix{i}", _rms(h, layer["norm_mix"], eps))
        mixer = attention if i in cfg.attn_layers else mamba
        h = h + cfg.residual_multiplier * mixer(layer["mixer"], f"mix{i}", x)
        x = a(f"mlp{i}", _rms(h, layer["norm_mlp"], eps))
        h = h + cfg.residual_multiplier * mlp(layer["mlp"], f"mlp{i}", x)
    x = a("head", _rms(h, params["final_norm"], eps))
    return x @ table.T / cfg.logits_scaling
