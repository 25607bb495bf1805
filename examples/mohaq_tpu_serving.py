"""MOHAQ at pod scale: per-layer weight-precision search for deepseek-67b
decode on the TPU v5e mesh, with hardware feedback from the *compiled
roofline* instead of a lookup table (DESIGN.md §TPU adaptation).

Objectives (both minimized by NSGA-II):
  - sensitivity: ZeroQ-style proxy = sum_l MACs_l * E[quant MSE at b_l bits]
    (relative quantization noise of a normal weight distribution);
  - decode step lower-bound: the dry-run baseline's roofline terms with the
    weight-stream bytes rescaled by the candidate's bit allocation.
Constraint: quantized params + KV cache fit 16 GiB/chip HBM.

This is the paper's Fig. 4 flow with {SiLago, Bitfusion} swapped for a
compiled-TPU hardware model. Runs in seconds — candidate evaluation is
pure arithmetic on the dry-run artifact.

Run: PYTHONPATH=src python examples/mohaq_tpu_serving.py

``--sharded-demo`` instead runs the *sharded population evaluator* end to
end on the SRU search model: a 1-D "pop" device mesh partitions every GA
generation's candidates across all visible devices (shard_map over
``forward_population``'s P axis; see ``repro.distributed.pop_sharding``),
and the demo asserts the sharded search's Pareto front is bit-identical to
the single-device one. On a TPU slice each candidate shard lands on its
own chip; on CPU, force a mesh with the XLA host-device flag below.

``--serve-demo`` closes the loop search-side to serving-side: a
checkpointed SRU search's Pareto front is packed into the deployment
artifact (``convert_checkpoint.front_from_store``) and served through
``repro.serving`` — SLO-routed, continuously batched, parity-gated
against the scalar ``forward(qp=)`` path.

Testing
-------
The mesh-parity lane covers this path:

- fast (in-process, 1-device mesh):
    PYTHONPATH=src python -m pytest -q tests/test_sharded_eval.py -m "not slow"
- end-to-end (8-way host-device mesh, spawned in a subprocess):
    PYTHONPATH=src python -m pytest -q tests/test_sharded_eval.py -m slow
- this demo on an 8-way host mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python examples/mohaq_tpu_serving.py --sharded-demo

``tools/check.sh`` chains the fast lane, the slow mesh lane, and the
``benchmarks/run.py --quick`` lane (which records the ``search_sharded``
throughput rows).
"""
import argparse
import json
import os

import numpy as np

from repro.configs import get_config
from repro.core.hardware import TPU_HBM_BW
from repro.core.nsga2 import NSGA2
from repro.launch.compile_cache import use_repo_compile_cache

# relative MSE of b-bit symmetric quantization of a unit normal (numeric)
QNOISE = {2: 0.119, 4: 0.0104, 8: 5.0e-5, 16: 1e-9}
BITS = [2, 4, 8, 16]
HBM_GIB = 16.0


def sharded_demo():
    """SRU MOHAQ search with every generation's population partitioned
    across the device mesh — and proof the front is bit-identical to the
    single-device run."""
    import time

    import jax

    from repro.core import sru_experiment as X
    from repro.core.api import SearchSession, get_platform
    from repro.launch.mesh import make_population_mesh

    trained = X.train_small_sru(steps=40)
    mesh = make_population_mesh()
    n_dev = len(jax.devices())
    print(f"population mesh: 1-D 'pop' axis over {n_dev} device(s)")

    kw = dict(generations=3, pop=8, initial=16, seed=0)
    bitfusion = get_platform("bitfusion")
    sess_m = SearchSession(trained, bitfusion, ("error", "speedup"),
                           mesh=mesh, share_memo=False)
    sess_s = SearchSession(trained, bitfusion, ("error", "speedup"),
                           share_memo=False)
    t0 = time.time()
    res_m = sess_m.run(**kw)
    t_mesh = time.time() - t0
    t0 = time.time()
    res_s = sess_s.run(**kw)
    t_single = time.time() - t0

    assert res_m.front_key() == res_s.front_key(), "sharded front diverged!"
    print(f"sharded search: {t_mesh:.1f}s over {n_dev} shard(s); "
          f"single-device: {t_single:.1f}s; fronts BIT-IDENTICAL "
          f"({len(res_m.pareto)} solutions, {res_m.n_evals} unique evals)")
    print(res_m.format(with_test=False))


def serve_demo():
    """Search -> checkpoint -> pack the front -> serve it, end to end.

    The deployment half of the demo: a checkpointed SRU search leaves a
    ``SearchStore`` behind, ``convert_checkpoint.front_from_store`` pulls
    the finished Pareto front (allocations + objective rows) out of it,
    and the ``repro.serving`` tier serves live traffic across that front —
    SLO classes route onto the stored objective rows and every decode step
    is ONE mixed-allocation ``forward_decode_step`` dispatch over the
    packed banks (no f32 weights rebuilt, no per-allocation fan-out).
    Serving is parity-gated in-demo: each request's logits must be bitwise
    equal to the scalar ``forward(qp=)`` path under its allocation.
    """
    import sys
    import tempfile

    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))           # repo root for `tools.*`
    from repro import serving as S
    from repro.core import sru_experiment as X
    from repro.core.api import SearchSession
    from repro.models import sru
    from tools import convert_checkpoint as CC

    trained = X.train_small_sru(steps=40)
    with tempfile.TemporaryDirectory() as root:
        ckpt = os.path.join(root, "ckpt")
        SearchSession(trained, "bitfusion", ("error", "speedup"),
                      share_memo=False).run(generations=2, pop=6, initial=8,
                                            seed=0, checkpoint_dir=ckpt)
        allocs, rows = CC.front_from_store(ckpt, trained)
        out = os.path.join(root, "artifact")
        manifest = CC.pack_deployment(trained, allocs, out, objectives=rows)
        art = S.DeploymentArtifact.load(out)
    by = manifest["bytes"]
    print(f"packed front: {art.n_allocs} allocations from the checkpointed "
          f"search ({by['packed_weight_banks']/1e3:.0f}kB banks, "
          f"{by['ratio']:.2f}x smaller than f32)")
    router = S.Router(art)
    for c in router.classes:
        dec = router.route(c.name)
        row = art.objectives[dec.alloc]
        print(f"  SLO {c.name:>8s} -> allocation {dec.alloc}: error "
              f"{row['error']:.2f}%, speedup {row.get('speedup', 0.0):.2f}x,"
              f" {row['cost_bits']:.1f} mean weight bits")
    bat = S.ContinuousBatcher(S.ServingEngine(art), router, max_lanes=4,
                              chunk=16, collect=True)
    rng = np.random.default_rng(0)
    dim = art.cfg.input_dim
    reqs = [S.Request(rid=i, slo=("premium", "standard", "economy")[i % 3],
                      feats=rng.normal(size=(32, dim)).astype(np.float32))
            for i in range(9)]
    for r in reqs:
        bat.submit(r)
    log = bat.run_until_idle()
    for r in reqs:
        qp = trained.qp_for(art.allocs[log.requests[r.rid].alloc])
        ref = np.concatenate([
            np.asarray(sru.forward(trained.params, trained.cfg,
                                   r.feats[s:s + 16][None], qp=qp))[0]
            for s in range(0, 32, 16)])
        assert np.array_equal(bat.results[r.rid], ref), \
            f"request {r.rid} diverged from the scalar path"
    s = log.summary()
    print(f"served {s['n_completed']} requests across "
          f"{len(router.classes)} SLO classes in {s['n_dispatches']} "
          f"dispatches ({s['tokens_per_s']:.0f} frames/s) — logits bitwise "
          f"== scalar forward(qp=)")


def main():
    cfg = get_config("deepseek-67b")
    art = "experiments/dryrun/deepseek-67b_decode_32k_single_kv8.json"
    if not os.path.exists(art):
        raise SystemExit(f"run the dry-run first: {art} missing")
    d = json.load(open(art))
    r = d["roofline"]
    n_dev = r["n_devices"]

    # per-layer-group weight byte shares (bf16 baseline, per device)
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    groups = {
        "attn_qo": L * (D * H * hd + H * hd * D),
        "attn_kv": L * 2 * D * KV * hd,
        "mlp_gate_up": L * 2 * D * F,
        "mlp_down": L * F * D,
        "embed_head": 2 * cfg.padded_vocab * D,
    }
    names = list(groups)
    total_params = sum(groups.values())
    bf16_weight_bytes_dev = 2 * total_params / n_dev / 16 * 16  # per device
    base_mem_s = r["memory_s"]
    # weight-stream share of the baseline memory term
    w_share_s = (2 * total_params / n_dev) / TPU_HBM_BW
    other_mem_s = max(base_mem_s - w_share_s, 0.0)
    cache_gib = d["memory_analysis"]["argument_bytes"] / 2**30 - \
        (2 * total_params / n_dev) / 2**30

    def evaluate(genome):
        alloc = {n: BITS[int(g) - 1] for n, g in zip(names, genome)}
        sens = sum(groups[n] * QNOISE[alloc[n]] for n in names) / total_params
        wbytes_dev = sum(groups[n] * alloc[n] / 8 for n in names) / n_dev
        mem_s = other_mem_s + wbytes_dev / TPU_HBM_BW
        step_bound = max(mem_s, r["collective_s"], r["compute_s"])
        fit_gib = wbytes_dev / 2**30 + max(cache_gib, 0.0)
        viol = max(0.0, fit_gib - HBM_GIB)
        return [sens, step_bound], viol

    # population-axis evaluation: one vectorized sweep scores a whole GA
    # generation (the same many-allocations-per-dispatch substrate the SRU
    # search uses through forward_population / NSGA2's evaluate_batch hook)
    sizes = np.asarray([groups[n] for n in names], float)
    bits_arr = np.asarray(BITS, float)
    qnoise_arr = np.asarray([QNOISE[b] for b in BITS], float)
    coll_comp = max(r["collective_s"], r["compute_s"])

    def evaluate_batch(genomes):
        G = np.stack(genomes).astype(int) - 1            # (P, n_var)
        sens = (sizes[None, :] * qnoise_arr[G]).sum(1) / total_params
        wbytes_dev = (sizes[None, :] * bits_arr[G] / 8).sum(1) / n_dev
        mem_s = other_mem_s + wbytes_dev / TPU_HBM_BW
        step_bound = np.maximum(mem_s, coll_comp)
        fit_gib = wbytes_dev / 2**30 + max(cache_gib, 0.0)
        viol = np.maximum(0.0, fit_gib - HBM_GIB)
        return [([float(s), float(sb)], float(v))
                for s, sb, v in zip(sens, step_bound, viol)]

    ga = NSGA2(n_var=len(names), var_lo=1, var_hi=4, evaluate=evaluate,
               evaluate_batch=evaluate_batch,
               pop_size=12, initial_pop_size=40, n_generations=40, seed=0)
    front = ga.run()
    print(f"search: {len(ga.history)} evals, {ga.n_cache_hits} cache hits "
          f"(population-axis batched evaluation)")
    print(f"deepseek-67b decode_32k on 256 chips (int8 KV cache baseline: "
          f"memory {base_mem_s*1e3:.0f} ms, collective "
          f"{r['collective_s']*1e3:.1f} ms)")
    print(f"{'bits ' + '/'.join(names):>58s}   sensitivity  step_bound")
    for ind in sorted(front, key=lambda s: s.objectives[0]):
        alloc = [BITS[int(g) - 1] for g in ind.genome]
        print(f"{str(alloc):>58s}   {ind.objectives[0]:.5f}      "
              f"{ind.objectives[1]*1e3:7.2f} ms")
    best = min(front, key=lambda s: s.objectives[1])
    print(f"\nfastest point quantizes to {[BITS[int(g)-1] for g in best.genome]}"
          f" -> step bound {best.objectives[1]*1e3:.2f} ms"
          f" (the designer picks the accuracy/speed trade-off, per the paper)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded-demo", action="store_true",
                    help="run the mesh-sharded SRU population search demo "
                         "instead of the deepseek-67b roofline search")
    ap.add_argument("--serve-demo", action="store_true",
                    help="run the checkpointed-search -> packed-artifact "
                         "-> SLO-routed serving demo (repro.serving)")
    args = ap.parse_args()
    use_repo_compile_cache()
    if args.serve_demo:
        serve_demo()
    elif args.sharded_demo:
        sharded_demo()
    else:
        main()
