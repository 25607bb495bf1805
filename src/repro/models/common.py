"""Shared model primitives: RMSNorm, RoPE, GQA flash-style attention, MLP, MoE.

Pure-functional: params are plain dict pytrees; a parallel *logical-axes* tree
(same structure, tuples of logical axis names) drives sharding. All matmuls
accumulate in f32 (``preferred_element_type``) and keep activations in bf16.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard

# ------------------------------------------------------- perf levers
# Set by repro.launch.dryrun flags / trainer config; see EXPERIMENTS.md §Perf.
# BF16_PARTIALS: emit matmul partial sums in bf16 so GSPMD's cross-shard
# reductions (TP activation all-reduces) move half the bytes. The MXU still
# accumulates f32 internally per shard; only the cross-device sum is bf16.
BF16_PARTIALS = False
# MoE dispatch: token-group size (bigger = fewer expert-weight re-streams)
# and algorithm ("einsum" = GShard one-hot matmuls; "gather" = top-C token
# selection per expert via gather/scatter — removes the S*E*C*D dispatch
# FLOPs that dominate small-expert MoEs).
MOE_GROUP_SIZE = 1024
MOE_DISPATCH = "einsum"
MOE_CAPACITY_FACTOR = 1.25


def acc_dtype():
    return jnp.bfloat16 if BF16_PARTIALS else jnp.float32


# ---------------------------------------------------------------- utilities

def dense(x, w):
    """x @ w with f32 (or bf16 under BF16_PARTIALS) accumulation."""
    return jnp.dot(x, w, preferred_element_type=acc_dtype()).astype(x.dtype)


def rms_norm(x, w, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def normal_init(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------- RoPE

def rope(x, positions, theta: float = 10000.0):
    """x: (..., T, n, d). positions: (..., T) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention

def _attn_block(q, k, v, qpos, kpos, kv_valid, scale=None):
    """Full (non-chunked) GQA attention for one block. q:(B,Tq,KV,G,d),
    k/v:(B,Tk,KV,d). Returns (B,Tq,KV,G,d) in f32."""
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k,
                        preferred_element_type=jnp.float32)
    scores *= 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    mask = kpos[None, :] <= qpos[:, None]                    # (Tq,Tk) causal
    if kv_valid is not None:
        mask = mask & (kpos[None, :] < kv_valid)
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


DENSE_ATTN_MAX = 8192   # up to here, materialize scores (differentiable path)


def gqa_attention(q, k, v, *, q_offset=0, kv_valid=None,
                  chunk_q: int = 512, chunk_k: int = 1024,
                  causal: bool = True, dense_max: Optional[int] = None,
                  scale: Optional[float] = None):
    """Memory-safe GQA attention.

    q: (B, Tq, H, d); k, v: (B, Tk, KV, d). Grouped so each of KV kv-heads
    serves G = H // KV query heads. Scores are scaled by ``scale``, by
    default 1/sqrt(d).

    Two regimes:
    - T <= DENSE_ATTN_MAX: materialized scores. Used for training — the
      flash-style scan's backward saves per-(q,k)-block f32 accumulators
      as stacked scan outputs (measured +20 GiB/device at 4k), while the
      dense path under per-layer remat peaks at one layer's score matrix.
    - longer: flash-style two-level scan (q-chunks outer, kv-chunks inner,
      online softmax) — forward-only serving path (32k prefill), where
      nothing is saved for a backward pass.
    """
    B, Tq, H, d = q.shape
    _, Tk, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Tq, KV, G, d)
    qpos_base = q_offset

    dense_max = DENSE_ATTN_MAX if dense_max is None else dense_max
    if (Tq <= chunk_q and Tk <= chunk_k) or max(Tq, Tk) <= dense_max:
        qpos = qpos_base + jnp.arange(Tq)
        kpos = jnp.arange(Tk)
        if not causal:
            qpos = jnp.full((Tq,), Tk)      # everything visible
        o = _attn_block(qg, k, v, qpos, kpos, kv_valid, scale)
        return o.reshape(B, Tq, H, d).astype(q.dtype)

    # pad Tq/Tk to chunk multiples
    nq = -(-Tq // chunk_q)
    nk = -(-Tk // chunk_k)
    pq, pk = nq * chunk_q - Tq, nk * chunk_k - Tk
    if pq:
        qg = jnp.pad(qg, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        valid = jnp.asarray(Tk if kv_valid is None else kv_valid)
    else:
        valid = None if kv_valid is None else jnp.asarray(kv_valid)

    qc = qg.reshape(B, nq, chunk_q, KV, G, d).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, chunk_k, KV, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, chunk_k, KV, d).transpose(1, 0, 2, 3, 4)
    scale = 1.0 / math.sqrt(d) if scale is None else scale

    def q_step(_, qi_qchunk):
        qi, qchunk = qi_qchunk
        qpos = qpos_base + qi * chunk_q + jnp.arange(chunk_q)

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kch, vch = ki_kv
            kpos = ki * chunk_k + jnp.arange(chunk_k)
            s = jnp.einsum("btkgd,bskd->bkgts", qchunk, kch,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((chunk_q, chunk_k), bool)
            if causal:
                mask = kpos[None, :] <= qpos[:, None]
            if valid is not None:
                mask = mask & (kpos[None, :] < valid)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgts,bskd->bkgtd", p.astype(vch.dtype), vch,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, chunk_q), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KV, G, chunk_q), jnp.float32)
        a0 = jnp.zeros((B, KV, G, chunk_q, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), kc, vc))
        o = acc / jnp.maximum(l, 1e-30)[..., None]           # (B,KV,G,Cq,d)
        return None, o.transpose(0, 3, 1, 2, 4)              # (B,Cq,KV,G,d)

    _, oc = jax.lax.scan(q_step, None, (jnp.arange(nq), qc))
    o = oc.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * chunk_q, H, d)
    return o[:, :Tq].astype(q.dtype)


def init_attn(key, d_model, n_heads, n_kv_heads, head_dim, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d_model)
    return {
        "wq": normal_init(ks[0], (d_model, n_heads, head_dim), s, dtype),
        "wk": normal_init(ks[1], (d_model, n_kv_heads, head_dim), s, dtype),
        "wv": normal_init(ks[2], (d_model, n_kv_heads, head_dim), s, dtype),
        "wo": normal_init(ks[3], (n_heads, head_dim, d_model),
                          1.0 / math.sqrt(n_heads * head_dim), dtype),
    }


ATTN_AXES = {
    "wq": ("embed", "heads", "qkv_dim"),
    "wk": ("embed", "kv_heads", "qkv_dim"),
    "wv": ("embed", "kv_heads", "qkv_dim"),
    "wo": ("heads", "qkv_dim", "embed"),
}


def attn_qkv(p, x, positions, theta, nope: bool = False):
    """q, k, v projections; RoPE on q and k unless ``nope``."""
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"],
                   preferred_element_type=acc_dtype()).astype(x.dtype)
    k = jnp.einsum("btd,dhk->bthk", x, p["wk"],
                   preferred_element_type=acc_dtype()).astype(x.dtype)
    v = jnp.einsum("btd,dhk->bthk", x, p["wv"],
                   preferred_element_type=acc_dtype()).astype(x.dtype)
    if not nope:
        q, k = rope(q, positions, theta), rope(k, positions, theta)
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    return q, k, v


def attn_out(p, o):
    y = jnp.einsum("bthk,hkd->btd", o, p["wo"],
                   preferred_element_type=acc_dtype()).astype(o.dtype)
    return shard(y, "batch", "seq", "embed")


# ---------------------------------------------------------------- MLP / MoE

def init_mlp(key, d_model, d_ff, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    return {
        "w_gate": normal_init(ks[0], (d_model, d_ff), s_in, dtype),
        "w_up": normal_init(ks[1], (d_model, d_ff), s_in, dtype),
        "w_down": normal_init(ks[2], (d_ff, d_model), s_out, dtype),
    }


MLP_AXES = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
            "w_down": ("mlp", "embed")}


def mlp(p, x):
    h = jax.nn.silu(dense(x, p["w_gate"])) * dense(x, p["w_up"])
    h = shard(h, "batch", "seq", "mlp")
    return shard(dense(h, p["w_down"]), "batch", "seq", "embed")


def init_moe(key, d_model, d_ff, n_experts, n_shared, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    p = {
        "router": normal_init(ks[0], (d_model, n_experts), s_in, jnp.float32),
        "w_gate": normal_init(ks[1], (n_experts, d_model, d_ff), s_in, dtype),
        "w_up": normal_init(ks[2], (n_experts, d_model, d_ff), s_in, dtype),
        "w_down": normal_init(ks[3], (n_experts, d_ff, d_model), s_out, dtype),
    }
    if n_shared:
        p["shared"] = init_mlp(ks[4], d_model, d_ff * n_shared, dtype)
    return p


def moe_axes(n_shared):
    p = {"router": ("embed", None),
         "w_gate": ("experts", "embed", "expert_mlp"),
         "w_up": ("experts", "embed", "expert_mlp"),
         "w_down": ("experts", "expert_mlp", "embed")}
    if n_shared:
        p["shared"] = dict(MLP_AXES)
    return p


def _dispatch_mask(gates, top_k: int, capacity: int):
    """GShard-style top-k dispatch. gates: (S, E) probs.
    Returns dispatch (S, E, C) bool-ish, combine (S, E, C) f32."""
    S, E = gates.shape
    topw, topi = jax.lax.top_k(gates, top_k)                 # (S, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros((S, E, capacity), jnp.bool_)
    combine = jnp.zeros((S, E, capacity), jnp.float32)
    for slot in range(top_k):
        oh = jax.nn.one_hot(topi[:, slot], E, dtype=jnp.int32)      # (S,E)
        pos = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]          # (S,E)
        counts = counts + oh.sum(0)
        keep = (pos < capacity) & (oh > 0)
        pos_oh = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)   # (S,E,C)
        d = pos_oh * keep[..., None]
        dispatch = dispatch | (d > 0)
        combine = combine + d * topw[:, slot][:, None, None]
    return dispatch, combine


def moe_ffn(p, x, *, top_k: int, group_size: int = 0,
            capacity_factor: float = 0.0):
    """Mixture-of-experts FFN with grouped GShard dispatch.

    Tokens are processed in groups of ``group_size`` (scanned) so the one-hot
    dispatch tensors stay (S, E, C) small. Overflowing tokens are dropped
    (residual passthrough), the standard capacity-based baseline.
    """
    B, T, D = x.shape
    E = p["w_gate"].shape[0]
    N = B * T
    flat = x.reshape(N, D)
    S = min(group_size or MOE_GROUP_SIZE, N)
    capacity_factor = capacity_factor or MOE_CAPACITY_FACTOR
    n_groups = -(-N // S)
    pad = n_groups * S - N
    if pad:
        flat = jnp.pad(flat, ((0, pad), (0, 0)))
    groups = flat.reshape(n_groups, S, D)
    capacity = max(1, int(math.ceil(S * top_k * capacity_factor / E)))

    def expert_ffn(xe, g_dtype):
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"],
                                   preferred_element_type=acc_dtype())) \
            * jnp.einsum("ecd,edf->ecf", xe, p["w_up"],
                         preferred_element_type=acc_dtype())
        h = h.astype(g_dtype)
        ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                        preferred_element_type=acc_dtype()).astype(g_dtype)
        return shard(ye, "experts", None, "embed")

    def per_group(_, g):
        g = shard(g, "batch", "embed")
        logits = jnp.dot(g.astype(jnp.float32), p["router"])
        gates = jax.nn.softmax(logits, axis=-1)
        if MOE_DISPATCH == "gather":
            S_ = g.shape[0]
            topw, topi = jax.lax.top_k(gates, top_k)
            topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
            w_se = jnp.zeros((S_, E), jnp.float32).at[
                jnp.arange(S_)[:, None], topi].set(topw)
            # per-expert: take the top-`capacity` tokens by gate weight
            cap = min(capacity, S_)
            sel_w, sel_idx = jax.lax.top_k(w_se.T, cap)           # (E, C)
            capacity_ = cap
            xe = jnp.take(g, sel_idx.reshape(-1), axis=0) \
                .reshape(E, capacity_, D)
            xe = shard(xe, "experts", None, "embed")
            ye = expert_ffn(xe, g.dtype)
            contrib = (ye.astype(jnp.float32)
                       * sel_w[..., None]).reshape(E * capacity_, D)
            y = jnp.zeros((S_, D), jnp.float32).at[
                sel_idx.reshape(-1)].add(contrib)
            return None, y.astype(g.dtype)
        dispatch, combine = _dispatch_mask(gates, top_k, capacity)
        xe = jnp.einsum("sec,sd->ecd", dispatch.astype(g.dtype), g,
                        preferred_element_type=acc_dtype()).astype(g.dtype)
        xe = shard(xe, "experts", None, "embed")
        ye = expert_ffn(xe, g.dtype)
        y = jnp.einsum("sec,ecd->sd", combine.astype(g.dtype), ye,
                       preferred_element_type=acc_dtype()).astype(g.dtype)
        return None, y

    if n_groups == 1:
        _, y = per_group(None, groups[0])
        y = y[None]
    else:
        _, y = jax.lax.scan(per_group, None, groups)
    y = y.reshape(n_groups * S, D)[:N].reshape(B, T, D)
    if "shared" in p:
        y = y + mlp(p["shared"], x)
    return shard(y, "batch", "seq", "embed")
