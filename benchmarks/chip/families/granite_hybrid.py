"""granite-4.0-h hybrid language model (HF ``granitemoehybrid``), as the
program builds it: Mamba-2 mixers and NoPE GQA attention layers, a SwiGLU
MLP in every layer, a tied embedding table, searched per layer. Weights,
fold, plain reference and work counts for the chip benchmark.

Nothing here imports the program except ``build_target``, which hands the
weights, the fold and the grids made here to the program's own
``GraniteTarget``. The reference is a plain float32 forward of one
allocation at a time, each matmul's operands and the activation stream
rounded to the precision the config states, products summed in float32 at
``Precision.HIGHEST``; for each layer i

  x = rmsnorm(h);  h += r * mixer_i(x);  x = rmsnorm(h);  h += r * mlp(x)

from h = e * table[tokens], logits = rmsnorm(h) table^T / s, where

  Mamba-2  [z | xBC | dt] = x W_in; xBC = silu(conv(xBC) + b) -> x, B, C;
           dt = softplus(dt + dt_bias); A = -exp(A_log); per head
           S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t,
           stepped one token at a time; out = rmsnorm(y * silu(z)) W_out
  GQA      softmax(q k^T * attention_multiplier, causal) v W_o, no
           positional embedding
  MLP      (silu(x W_gate) * x W_up) W_down

The searchable layers are each layer's mixer (mix{i}: in_proj, out_proj or
wq, wk, wv, wo), its MLP (mlp{i}: w_gate, w_up, w_down) and the tied table
(head), quantized in the lookup and in the head; each takes the
allocation's activation grid at its normed input.
"""
from __future__ import annotations

import importlib.util
import math

import jax
import jax.numpy as jnp
import numpy as np

from families import common as C
from families.xlstm import make_tokens

# a program without the target cannot run the cell: say so at once, before
# any work on the device
if importlib.util.find_spec("repro.core.granite_target") is None:
    raise ImportError("this program has no granite-4.0-h search target "
                      "(repro.core.granite_target)")

LEAVES = {"mamba": ("in_proj", "out_proj"),
          "attention": ("wq", "wk", "wv", "wo"),
          "mlp": ("w_gate", "w_up", "w_down")}


# ------------------------------------------------------------ geometry

def kinds(cfg):
    """The mixer of each of the cut's layers, from the published pattern."""
    return cfg["layer_types"][:cfg["n_layers"]]


def dims(cfg):
    d, hs, ps = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"d": d, "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"], "di": hs * ps, "gn": gn,
            "hs": hs, "ps": ps, "conv": hs * ps + 2 * gn,
            "ff": cfg["shared_intermediate_size"], "vocab": cfg["vocab_size"]}


def layer_names(cfg):
    names = []
    for i in range(cfg["n_layers"]):
        names += [f"mix{i}", f"mlp{i}"]
    return names + ["head"]


def leaf_shapes(cfg):
    """{layer: {leaf: (shape, dtype)}} of the searchable weights."""
    g = dims(cfg)
    d, bf = g["d"], cfg["dtype"]
    mixers = {
        "mamba": {"in_proj": ((d, 2 * g["di"] + 2 * g["gn"] + g["hs"]), bf),
                  "out_proj": ((g["di"], d), bf)},
        "attention": {"wq": ((d, g["heads"], g["hd"]), bf),
                      "wk": ((d, g["kv"], g["hd"]), bf),
                      "wv": ((d, g["kv"], g["hd"]), bf),
                      "wo": ((g["heads"], g["hd"], d), bf)}}
    mlp = {"w_gate": ((d, g["ff"]), bf), "w_up": ((d, g["ff"]), bf),
           "w_down": ((g["ff"], d), bf)}
    out = {}
    for i, kind in enumerate(kinds(cfg)):
        out[f"mix{i}"], out[f"mlp{i}"] = mixers[kind], mlp
    out["head"] = {"embed": ((g["vocab"], d), bf)}
    return out


def weight_counts(cfg):
    return {name: sum(int(np.prod(s)) for s, _ in leaves.values())
            for name, leaves in leaf_shapes(cfg).items()}


def work(cfg, mix):
    """The algorithm's FLOPs and bytes of one dispatched population lane:
    2 x every searchable matrix's size per token (the tied table once, as
    the head; the lookup reads rows), the SSD of each Mamba-2 layer in its
    chunked form (causal half of each chunk's C B^T and of its product
    with x, the state's read-out and update, 2 x heads x P x N a token
    each) and each attention layer's causal q k^T and p v; the lane's
    quantized weights read once at their storage width. The fold (tokens
    and labels) is read once per dispatch."""
    g = dims(cfg)
    rows, length = C.fold_shape(mix)
    tokens = rows * length
    L = min(cfg["mamba_chunk_size"], length)
    n_chunks = -(-length // L)
    pairs = n_chunks * L * (L + 1) // 2          # causal (l, s) per row
    ssd = rows * (pairs * 2 * (g["gn"] + g["hs"] * g["ps"])
                  + tokens // rows * 2 * 2 * g["hs"] * g["ps"]
                  * cfg["mamba_d_state"])
    attn = rows * length * (length + 1) // 2 * 2 * 2 * g["heads"] * g["hd"]
    n_mamba = sum(k == "mamba" for k in kinds(cfg))
    flops = (2 * sum(weight_counts(cfg).values()) * tokens + n_mamba * ssd
             + (cfg["n_layers"] - n_mamba) * attn)
    lane_bytes = sum(int(np.prod(s)) * C.BYTES[t]
                     for leaves in leaf_shapes(cfg).values()
                     for s, t in leaves.values())
    return {"flops_per_lane": float(flops),
            "bytes_per_lane": float(lane_bytes),
            "bytes_per_dispatch": float(tokens * 2 * 4)}


# ------------------------------------------------------------ weights

def init_weights(cfg, seed):
    """The program's parameter pytree in the types it is served in
    (matrices and table in the config's ``dtype``, bfloat16; f32 conv, SSM
    vectors and norms), drawn on the
    device in one call: N(0, 1/fan_in) matrices and conv, the tied table
    N(0, 0.09/d_model), zero conv bias, A_log = log(1..heads), dt_bias the
    inverse softplus of dt spread log-uniformly over [1e-3, 1e-1], D = 1,
    unit norms. The table's scale is the config's ``assumed``: at
    N(0, 1/d_model), 12 x a token's own row outweighs what ten layers of
    random weights add to the stream, so every argmax is the input token
    and almost no allocation moves one; at N(0, 0.01/d_model) nearly every
    allocation below 8 bits moves nearly all of them."""
    g = dims(cfg)
    d, bf, f32 = g["d"], jnp.dtype(cfg["dtype"]), jnp.float32
    shapes = leaf_shapes(cfg)

    def nrm(k, shape, fan_in, dtype=bf):
        return (jax.random.normal(k, shape) / math.sqrt(fan_in)).astype(dtype)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 8 * cfg["n_layers"] + 1))
        dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), g["hs"]))
        layers = []
        for i, kind in enumerate(kinds(cfg)):
            mixer = {k: nrm(next(keys), s, s[0] if k != "wo" else
                            s[0] * s[1])
                     for k, (s, _) in shapes[f"mix{i}"].items()}
            if kind == "mamba":
                mixer.update(
                    conv_w=nrm(next(keys), (cfg["mamba_d_conv"], g["conv"]),
                               cfg["mamba_d_conv"], f32),
                    conv_b=jnp.zeros((g["conv"],), f32),
                    dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(f32),
                    A_log=jnp.log(jnp.arange(1, g["hs"] + 1, dtype=f32)),
                    D=jnp.ones((g["hs"],), f32),
                    norm=jnp.ones((g["di"],), f32))
            mlp = {k: nrm(next(keys), s, s[0])
                   for k, (s, _) in shapes[f"mlp{i}"].items()}
            layers.append({"norm_mix": jnp.ones((d,), f32), "mixer": mixer,
                           "norm_mlp": jnp.ones((d,), f32), "mlp": mlp})
        return {"embed": nrm(next(keys), (g["vocab"], d), d / 0.3 ** 2),
                "layers": layers, "final_norm": jnp.ones((d,), f32)}

    return make(C.key(seed, "weights"))


# ------------------------------------------------------------ reference

def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def forward(params, cfg, tokens, qp=None, prec=C.EXACT, taps=None):
    """Plain float32 forward: tokens (B, T) -> logits (B, T, vocab).
    ``qp`` and ``taps`` as in ``families.sru.forward``. ``prec``
    (``common.Precision``) rounds every matmul operand, and the activation
    stream at each point where the program keeps it narrow: the residual
    stream, the norms' and the activation grids' outputs, the mixer's z
    and conv input and output, the gated norm's output, the q/k/v outputs,
    the attention probabilities and output, the MLP's gate, up, sigmoid,
    silu and product, and each branch's output. The config states
    bfloat16 for both, as the TPU computes a float32 matmul at its default
    precision."""
    g = dims(cfg)
    names = layer_names(cfg)
    li = {n: i for i, n in enumerate(names)}
    eps = cfg["rms_norm_eps"]
    ro, ra, dot = prec.mm(), prec.act(), prec.dot_precision()
    f32 = jnp.float32

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
        matrix's own type (the config's ``dtype``)."""
        if qp is None:
            return w.astype(f32)
        r = qp[li[name]]
        q = C.fake_quant(w.astype(f32), r[0], r[1], r[2])
        return q.astype(w.dtype).astype(f32)

    def mamba(p, name, x):
        b, t, _ = x.shape
        di, gn, hs, ps = g["di"], g["gn"], g["hs"], g["ps"]
        G = cfg["mamba_n_groups"]
        zxd = mm("btd,de->bte", x, wgt(name, p["in_proj"]))
        z, xbc = ra(zxd[..., :di]), ra(zxd[..., di:2 * di + 2 * gn])
        dt = jax.nn.softplus(zxd[..., 2 * di + 2 * gn:] + p["dt_bias"])
        K = p["conv_w"].shape[0]
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = p["conv_b"] + sum(pad[:, k:k + t] * p["conv_w"][k]
                                 for k in range(K))
        xbc = ra(jax.nn.silu(conv))
        xs = xbc[..., :di].reshape(b, t, hs, ps)
        Bm = jnp.repeat(xbc[..., di:di + gn].reshape(b, t, G, -1),
                        hs // G, axis=2)
        Cm = jnp.repeat(xbc[..., di + gn:].reshape(b, t, G, -1),
                        hs // G, axis=2)
        A = -jnp.exp(p["A_log"])

        def step(S, inp):                          # S (b, heads, P, N)
            x_t, dt_t, B_t, C_t = inp
            S = (jnp.exp(dt_t * A)[..., None, None] * S
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
            return S, mm("bhpn,bhn->bhp", S, C_t)

        S0 = jnp.zeros((b, hs, ps, cfg["mamba_d_state"]), f32)
        _, ys = jax.lax.scan(step, S0, tuple(
            jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm)))
        y = jnp.moveaxis(ys, 0, 1) + p["D"][:, None] * xs
        gated = y.reshape(b, t, di) * jax.nn.silu(z)
        gated = ra(_rms_norm(gated, p["norm"], eps))
        return ra(mm("bte,ed->btd", gated, wgt(name, p["out_proj"])))

    def attention(p, name, x):
        b, t, _ = x.shape
        rep = g["heads"] // g["kv"]
        q = ra(mm("btd,dhk->bthk", x, wgt(name, p["wq"])))
        k = jnp.repeat(ra(mm("btd,dhk->bthk", x, wgt(name, p["wk"]))), rep,
                       axis=2)
        v = jnp.repeat(ra(mm("btd,dhk->bthk", x, wgt(name, p["wv"]))), rep,
                       axis=2)
        s = mm("bthk,bshk->bhts", q, k) * cfg["attention_multiplier"]
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = ra(mm("bhts,bshk->bthk", ra(e / e.sum(-1, keepdims=True)), v))
        return ra(mm("bthk,hkd->btd", o, wgt(name, p["wo"])))

    def mlp(p, name, x):
        gate = ra(mm("btd,df->btf", x, wgt(name, p["w_gate"])))
        up = ra(mm("btd,df->btf", x, wgt(name, p["w_up"])))
        h = ra(ra(gate * ra(jax.nn.sigmoid(gate))) * up)
        return ra(mm("btf,fd->btd", h, wgt(name, p["w_down"])))

    r = cfg["residual_multiplier"]
    table = wgt("head", params["embed"])
    h = ra(jnp.take(table, tokens, axis=0) * cfg["embedding_multiplier"])
    for i, kind in enumerate(kinds(cfg)):
        lay = params["layers"][i]
        mixer = mamba if kind == "mamba" else attention
        x = act(f"mix{i}", ra(_rms_norm(h, lay["norm_mix"], eps)))
        h = ra(h + r * mixer(lay["mixer"], f"mix{i}", x))
        x = act(f"mlp{i}", ra(_rms_norm(h, lay["norm_mlp"], eps)))
        h = ra(h + r * mlp(lay["mlp"], f"mlp{i}", x))
    x = act("head", ra(_rms_norm(h, params["final_norm"], eps)))
    return mm("btd,vd->btv", x, table) / cfg["logits_scaling"]


def pooled_weights(params, cfg):
    out = {}
    for name in layer_names(cfg):
        if name == "head":
            out[name] = params["embed"].ravel()
            continue
        i = int(name[3:])
        lay = params["layers"][i]
        if name.startswith("mix"):
            leaves = [lay["mixer"][k] for k in LEAVES[kinds(cfg)[i]]]
        else:
            leaves = [lay["mlp"][k] for k in LEAVES["mlp"]]
        out[name] = jnp.concatenate(
            [w.astype(jnp.float32).ravel() for w in leaves])
    return out


# ------------------------------------------------------------ the program

def program_config(cfg):
    import dataclasses
    from repro.configs import get_config

    out = dataclasses.replace(
        get_config(cfg["program_config"]), n_layers=cfg["n_layers"],
        attn_layers=tuple(i for i, k in enumerate(kinds(cfg))
                          if k == "attention"),
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        ssm_n_heads=cfg["mamba_n_heads"], ssm_n_groups=cfg["mamba_n_groups"],
        ssm_d_state=cfg["mamba_d_state"], ssm_d_conv=cfg["mamba_d_conv"],
        ssm_expand=cfg["mamba_expand"], ssm_chunk=cfg["mamba_chunk_size"],
        nope=cfg["position_embedding_type"] == "nope",
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=float(cfg["logits_scaling"]),
        attention_multiplier=cfg["attention_multiplier"],
        norm_eps=cfg["rms_norm_eps"])
    # the program derives the head width from expand x hidden over heads
    assert out.ssm_head_dim == cfg["mamba_d_head"], (out.ssm_head_dim,
                                                     cfg["mamba_d_head"])
    return out


def build_target(cfg, params, subsets, grids, score_baseline=True):
    """The program's ``GraniteTarget`` over the weights, fold and grids
    made here; its baseline error is the program's own unquantized
    score."""
    from repro.core.granite_target import GraniteTarget

    target = GraniteTarget(program_config(cfg), params, subsets, [],
                           grids.act_ranges, grids.wclips, grids.wranges)
    if score_baseline:
        target.baseline_val_error = target.val_error()
    return target


FAMILY = C.Family(
    layer_names=layer_names, weight_counts=weight_counts, work=work,
    init_weights=init_weights, make_inputs=make_tokens, forward=forward,
    pooled_weights=pooled_weights, build_target=build_target)
