"""Serve a small LM with MOHAQ-quantized weights through the Pallas
quant_matmul kernel path — prefill + batched decode — and serve a whole
*population* of quantization allocations in one dispatch from a PACKED
deployment artifact.

Demonstrates the TPU adaptation of the paper (DESIGN.md): int4/int2 weights
packed in int8 containers, dequantized in-kernel. On this CPU container the
kernel runs in interpret mode; on TPU the same call compiles to MXU ops
(``kernels.ops`` resolves the mode from the backend).
The population-serving half goes through ``tools/convert_checkpoint.py``
and the ``repro.serving`` tier: a trained search model + its chosen
allocations are frozen into a packed artifact (int codes + scales +
manifest, >= 4x smaller than the f32 banks), an SLO router picks each
request's operating point off the stored front, and the continuous
batcher serves the whole mixed-allocation batch in ONE
``forward_decode_step`` dispatch per step — no f32 weight tensor shipped
at all, and no per-allocation dispatch fan-out.

Run: PYTHONPATH=src python examples/serve_quantized.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.quantization import mmse_clip
from repro.kernels import ops as kops
from repro.launch.compile_cache import use_repo_compile_cache
from repro.models import transformer as tfm


def decode_loop(params, cfg, tokens, gen, head_fn=None):
    """Greedy prefill + decode; the output head is ``head_fn`` (dense when
    None). Returns the generated (B, gen) tokens."""
    logits, cache = tfm.prefill(params, cfg, tokens,
                                max_len=tokens.shape[1] + gen,
                                head_fn=head_fn)
    out = []
    for _ in range(gen):
        nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(nxt)
        logits, cache = tfm.decode_step(params, cfg, cache, nxt,
                                        head_fn=head_fn)
    return jnp.concatenate(out, axis=1)


def main():
    use_repo_compile_cache()
    cfg = get_config("stablelm-1.6b").reduced()
    from repro.models.registry import get_model
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # --- quantize the LM head: int4 for memory, int8 for lossless serving ---
    w = params["lm_head"].astype(jnp.float32)          # (D, V)
    clip4 = mmse_clip(jax.device_get(w), 4)
    packed4, scales4 = kops.pack_for_kernel(w, 4, clip4)
    orig_bytes = w.size * 2                            # bf16 deployment
    q_bytes = packed4.size + scales4.size * 4
    print(f"lm_head: {w.shape} bf16 {orig_bytes/1e3:.0f}kB -> int4 "
          f"{q_bytes/1e3:.0f}kB ({orig_bytes/q_bytes:.1f}x smaller)")
    # int8 is argmax-lossless on this head (int4 flips near-tie logits on a
    # 256-way random-init vocab — exactly the error/hardware trade the MOHAQ
    # search navigates); serve through int8, report int4 noise below
    packed8, scales8 = kops.pack_for_kernel(
        w, 8, float(jnp.max(jnp.abs(w))))

    def quant_head(hidden):                            # (B, 1, D) -> logits
        h2 = hidden.reshape(-1, cfg.d_model).astype(jnp.float32)
        y = kops.quant_matmul(h2, packed8, scales8, 8)
        return y.reshape(hidden.shape[:-1] + (w.shape[1],))

    # --- serve: prefill a prompt, decode 8 tokens greedily, both heads ---
    B, prompt_len, gen = 2, 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, prompt_len),
                                0, cfg.vocab_size)
    t0 = time.time()
    dense_tokens = decode_loop(params, cfg, tokens, gen)
    t_dense = time.time() - t0
    t0 = time.time()
    quant_tokens = decode_loop(params, cfg, tokens, gen, head_fn=quant_head)
    t_quant = time.time() - t0
    match = bool(jnp.all(dense_tokens == quant_tokens))
    print(f"dense head  {t_dense:.1f}s tokens {jax.device_get(dense_tokens).tolist()}")
    print(f"int8 head   {t_quant:.1f}s tokens {jax.device_get(quant_tokens).tolist()}")
    print(f"generated tokens match dense head: {match}")
    assert match, "quantized decode head diverged from the dense head"

    # --- validate the kernel path against the dense head on real hiddens ---
    x = jax.random.normal(jax.random.PRNGKey(2), (B, cfg.d_model), jnp.float32)
    dense_logits = x @ w
    kern_logits = kops.quant_matmul(x, packed4, scales4, 4)
    err = float(jnp.max(jnp.abs(dense_logits - kern_logits)))
    rel = err / float(jnp.max(jnp.abs(dense_logits)))
    print(f"int4 kernel vs dense head: max abs err {err:.3f} (rel {rel:.3f}) "
          f"- int4 quantization noise, as expected")

    # --- Pareto-front-as-a-service from the packed artifact ---------------
    # The search-loop substrate (forward_population's explicit population
    # axis) doubles as a serving substrate: ``repro.serving`` loads the
    # PACKED artifact written by tools/convert_checkpoint.py once, a Router
    # maps each request's SLO class onto the stored front, and the
    # ContinuousBatcher runs every decode step as ONE mixed-allocation
    # dispatch — lane i's scalar-prefetched menu index IS request i's
    # allocation, so adding an operating point never adds a dispatch.
    import os
    import sys
    import tempfile

    import numpy as np
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))           # repo root for `tools.*`
    from repro import serving as S
    from repro.core import sru_experiment as X
    from repro.models import sru
    from tools import convert_checkpoint as CC

    trained = X.train_small_sru(steps=8)
    names = list(trained.layer_names)
    presets = [{n: (b, 8) for n in names} for b in (2, 4, 8, 16)]
    objectives = [{"error": 12.0}, {"error": 7.0}, {"error": 3.0},
                  {"error": 1.0}]              # front-row stand-ins
    with tempfile.TemporaryDirectory() as d:
        manifest = CC.pack_deployment(trained, presets, d,
                                      objectives=objectives)
        art = S.DeploymentArtifact.load(d)
    by = manifest["bytes"]
    print(f"packed artifact: {art.n_allocs} allocations, weight banks "
          f"{by['packed_weight_banks']/1e3:.0f}kB "
          f"({by['ratio']:.2f}x smaller than f32 banks)")
    router = S.Router(art)
    for c in router.classes:
        dec = router.route(c.name)
        row = art.objectives[dec.alloc]
        print(f"  SLO {c.name:>8s} -> allocation {dec.alloc} "
              f"(error {row['error']:.0f}%, {row['cost_bits']:.1f} mean "
              f"weight bits)")
    bat = S.ContinuousBatcher(S.ServingEngine(art), router, max_lanes=4,
                              chunk=16, collect=True)
    rng = np.random.default_rng(0)
    dim = art.cfg.input_dim
    reqs = [S.Request(rid=i, slo=("premium", "standard", "economy")[i % 3],
                      feats=rng.normal(size=(32, dim)).astype(np.float32))
            for i in range(6)]
    for r in reqs:
        bat.submit(r)
    log = bat.run_until_idle()
    for r in reqs:                             # served == scalar, bitwise
        qp = trained.qp_for(presets[log.requests[r.rid].alloc])
        ref = jnp.concatenate([
            sru.forward(trained.params, trained.cfg, r.feats[s:s + 16][None],
                        qp=qp)[0] for s in range(0, 32, 16)])
        assert np.array_equal(bat.results[r.rid], np.asarray(ref)), r.rid
    s = log.summary()
    print(f"served {s['n_completed']} requests over 3 SLO classes in "
          f"{s['n_dispatches']} dispatches ({s['n_steps']} steps, "
          f"{s['tokens_per_s']:.0f} frames/s) — logits bitwise == the "
          f"scalar forward(qp=) path")


if __name__ == "__main__":
    main()
