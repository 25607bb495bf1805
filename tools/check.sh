#!/usr/bin/env bash
# Repo check pipeline (the order mirrors how a CI provider would stage it):
#
#   0a. analyze    — repro-analyze static-analysis gate (tools/analysis):
#                    AST invariant lint (R1 SeedSequence, R2 deprecated
#                    entrypoints, R3 host effects in jit, R4 retrace
#                    hazards incl. static_argnums, R5 parity-frozen
#                    dtypes), the jaxpr contract checks (C1 gather-don't-
#                    requantize, C2 no f64, C3 donation, C4 one dispatch/
#                    generation, C5 population-lane independence via the
#                    dataflow prover) traced per registered SearchTarget,
#                    and the Pallas kernel verifier (K0 coverage, K1 grid/
#                    BlockSpec divisibility, K2 index_map bounds, K3 VMEM
#                    working set, K4 packed-layout agreement). Each layer
#                    is timed and the whole gate must finish inside the
#                    --max-seconds budget below — a slow gate stops being
#                    run. New findings fail; the committed
#                    tools/analysis/baseline.json grandfathers documented
#                    exceptions (justification required). See ROADMAP
#                    "Static-analysis gate".
#
#   1. fast lane   — unit/parity tests, slow-marked suites skipped
#   2. slow lane   — end-to-end suites under an 8-way host-device mesh
#                    (the mesh-parity tests spawn their own subprocess with
#                    the XLA flag; exporting it here also runs the
#                    in-process suites against 8 virtual devices)
#   3. benchmarks  — the paper's Tables 1-8 recomputed from
#                    benchmarks/paper_tables.py (untimed CSV rows; the
#                    search's speed is measured on the chip by
#                    benchmarks/chip/, never here)
#
#   0. api smoke   — import + public-name check of the repro.core.api
#                    SearchTarget/SearchSession surface and the platform
#                    registry (runs before the fast lane)
#   0b. resilience — crash-safety smoke chained after the api stage: a
#                    tiny checkpointed search, discard the newest
#                    checkpoints, resume, and assert the resumed Pareto
#                    front is bit-identical (==) to the uninterrupted
#                    run (the full kill/torn-write matrix is the slow
#                    lane's test_kill_resume.py)
#   0c. packed     — packed-integer bank lane parity smoke: error counts
#                    under bank_format="packed" must equal the f32-banked
#                    and scalar paths exactly, and the packed weight banks
#                    must be >= 4x smaller in bytes (the full matrix is
#                    tests/test_packed_banks.py)
#   0d. serve      — serving-tier smoke chained after packed: pack a tiny
#                    3-allocation artifact, route 8 requests across the 3
#                    default SLO classes, run them through the continuous
#                    batcher, and assert every served logit is bitwise ==
#                    the scalar forward(qp=) path on the same frames (the
#                    full matrix is tests/test_serving.py)
#
# Usage: tools/check.sh [analyze|api|resilience|packed|serve|fast|slow|bench]
#        (no argument = all)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Persistent XLA compilation cache: the search pipelines recompile the same
# per-bucket evaluators every run; caching them cuts the compile time of
# every stage after the first. Override JAX_COMPILATION_CACHE_DIR to
# relocate, or set it to the empty string to disable (the `-` expansion
# keeps an explicitly empty value, unlike `:-`).
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR-$PWD/.jax_cache}"
if [ -n "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-0.2}"
  mkdir -p "$JAX_COMPILATION_CACHE_DIR"
fi

stage="${1:-all}"

run_analyze() {
  echo "== analyze: python -m tools.analysis (lint + contracts + kernels) =="
  python -m tools.analysis src/ examples/ benchmarks/ --max-seconds 30
}

run_api_smoke() {
  echo "== api surface smoke: repro.core.api public names =="
  python - <<'PY'
import repro.core.api as api

required = ["SearchTarget", "SearchSession", "SearchResult",
            "build_problem_from_target", "result_table", "format_rows",
            "get_platform", "list_platforms"]
missing = [n for n in required if not hasattr(api, n)]
assert not missing, f"api surface regressed, missing: {missing}"
assert sorted(api.__all__) == sorted(required), \
    f"__all__ drifted: {sorted(api.__all__)}"
from repro.core.hardware import get_platform, list_platforms
for name in ("silago", "bitfusion", "tpuv5e", "mem-only"):
    assert name in list_platforms(), name
    get_platform(name)
from repro.core.batched_eval import BatchedSRUEvaluator, PopulationEvaluator
assert issubclass(BatchedSRUEvaluator, PopulationEvaluator)
print("api surface OK:", ", ".join(sorted(api.__all__)))
PY
}

run_resilience() {
  echo "== resilience smoke: checkpoint -> discard tail -> resume -> front == =="
  python - <<'PY'
import tempfile

from repro.core import checkpointing as ckpt
from repro.core import sru_experiment as X
from repro.core.api import SearchSession
from repro.core.hardware import get_platform

trained = X.train_small_sru(steps=40)
kw = dict(generations=3, pop=6, initial=8, seed=0)

def session():
    return SearchSession(trained, "mem-only", ("error", "memory"),
                         share_memo=False)

with tempfile.TemporaryDirectory() as d:
    ref = session().run(**kw)
    full = session().run(checkpoint_dir=d, **kw)
    assert full.front_key() == ref.front_key(), "checkpointing changed the front"
    key = ckpt.search_key(trained, get_platform("mem-only"), 0)
    settings = {"generations": 3, "pop": 6, "initial": 8,
                "objectives": ["error", "memory"], "beacons": False,
                "retrain_steps": 0, "distance_threshold": 0.0}
    store = ckpt.SearchStore(d)
    gens = store.generations(key, settings)
    assert gens == [0, 1, 2, 3], gens
    store.discard_after(key, settings, 1)
    res = session().run(checkpoint_dir=d, resume=True, **kw)
    assert res.front_key() == ref.front_key(), "resume diverged"
    assert res.n_evals == ref.n_evals
print("resilience OK: resumed front bit-identical to the uninterrupted run")
PY
}

run_packed() {
  echo "== packed lane smoke: packed == f32 == scalar, banks >= 4x smaller =="
  python - <<'PY'
import numpy as np

from repro.core import quantization as Q
from repro.core import sru_experiment as X

trained = X.train_small_sru(steps=40)
names = list(trained.layer_names)
allocs = [{n: (b, 8) for n in names} for b in (2, 4, 8, 16)]
scalar = [trained.val_error(a) for a in allocs]
assert trained.val_error_batch(allocs, bank_format="packed") == scalar, \
    "packed-bank error counts diverged from the scalar path"
assert trained.val_error_batch(allocs, use_banks=True) == scalar, \
    "f32-bank error counts diverged from the scalar path"

def w_bytes(banks, packed):
    total = 0
    for name in names:
        nodes = ([banks[name][d] for d in ("fwd", "bwd")]
                 if name.startswith("L") else [banks[name]])
        for node in nodes:
            w = node["W"]
            total += (Q.packed_bank_nbytes(w) if packed
                      else w.size * w.dtype.itemsize)
    return total

pb = w_bytes(trained.make_packed_banks(trained.params), True)
fb = w_bytes(trained.make_banks(trained.params), False)
assert fb / pb >= 4.0, f"packed banks only {fb / pb:.2f}x smaller"
print(f"packed lane OK: errors bit-identical, banks {fb / pb:.2f}x smaller")
PY
}

run_serve() {
  echo "== serving smoke: pack front -> SLO-route 8 requests -> bitwise parity =="
  python - <<'PY'
import tempfile

import numpy as np

from repro import serving as S
from repro.core import sru_experiment as X
from repro.models import sru
from tools import convert_checkpoint as CC

trained = X.train_small_sru(steps=40)
names = list(trained.layer_names)
allocs = [{n: (b, 8) for n in names} for b in (2, 4, 8)]
objectives = [{"error": 9.0}, {"error": 5.0}, {"error": 2.0}]

with tempfile.TemporaryDirectory() as d:
    CC.pack_deployment(trained, allocs, d, objectives=objectives)
    art = S.DeploymentArtifact.load(d)
    router = S.Router(art)
    bat = S.ContinuousBatcher(S.ServingEngine(art), router,
                              max_lanes=4, chunk=8, collect=True)
    rng = np.random.default_rng(0)
    m = art.cfg.input_dim
    reqs = [S.Request(rid=i, slo=("premium", "standard", "economy")[i % 3],
                      feats=rng.normal(size=(n, m)).astype(np.float32))
            for i, n in enumerate([8, 16, 11, 8, 24, 16, 11, 8])]
    for r in reqs:
        assert not bat.submit(r).shed
    log = bat.run_until_idle()
    assert len(log.completed()) == len(reqs)
    for r in reqs:
        alloc = allocs[log.requests[r.rid].alloc]
        qp = trained.qp_for(alloc)
        ref = np.concatenate([
            np.asarray(sru.forward(trained.params, trained.cfg,
                                   r.feats[s:s + 8][None], qp=qp))[0]
            for s in range(0, r.feats.shape[0], 8)])
        assert np.array_equal(bat.results[r.rid], ref), \
            f"request {r.rid}: served logits != scalar forward(qp=)"
    by_alloc = sorted({log.requests[r.rid].alloc for r in reqs})
    assert by_alloc == [0, 1, 2], by_alloc
    s = log.summary()
print(f"serving OK: {s['n_completed']} requests over 3 allocations, "
      f"{s['n_dispatches']} dispatches in {s['n_steps']} steps, "
      f"served logits bitwise == scalar path")
PY
}

run_fast() {
  echo "== fast lane: pytest -m 'not slow' =="
  python -m pytest -x -q -m "not slow"
}

run_slow() {
  echo "== slow lane: pytest -m slow (8-device host mesh) =="
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -x -q -m slow
}

run_bench() {
  echo "== benchmarks: python -m benchmarks.run (paper Tables 1-8) =="
  python -m benchmarks.run
}

case "$stage" in
  analyze) run_analyze ;;
  api)   run_api_smoke; run_resilience ;;
  resilience) run_resilience ;;
  packed) run_packed ;;
  serve) run_serve ;;
  fast)  run_api_smoke; run_resilience; run_packed; run_serve; run_fast ;;
  slow)  run_slow ;;
  bench) run_bench ;;
  all)   run_analyze; run_api_smoke; run_resilience; run_packed; run_serve
         run_fast; run_slow; run_bench ;;
  *)     echo "unknown stage: $stage (want analyze|api|resilience|packed|serve|fast|slow|bench)" >&2
         exit 2 ;;
esac
echo "== check.sh: all requested stages passed =="
