"""Mesh-parity lane for the sharded population evaluator.

Contract (PRs 1-3): error counts are integers and every evaluator lowering
— scalar, batched, population-axis, and now mesh-sharded — must agree
EXACTLY, so Pareto fronts compare with ``==``, never with tolerances.

Fast tests exercise the sharding machinery in-process on a 1-device "pop"
mesh (padding, shard_map/gspmd wrapping, gather, search wiring). The slow
tests run the real thing: an 8-way host-device mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) in a subprocess,
checking bit-identical errors and full NSGA-II Pareto fronts for divisible
(P=32) and non-divisible (P=5, P=13) populations, plus beacon-grouped
routing — one subprocess, many assertions, so the mesh is paid for once.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import sru_experiment as X
from repro.distributed import pop_sharding
from repro.launch.mesh import make_population_mesh


# --------------------------------------------------------------- unit


class TestPaddingMath:
    def test_padded_pop(self):
        assert pop_sharding.padded_pop(1, 8) == 8
        assert pop_sharding.padded_pop(4, 8) == 8
        assert pop_sharding.padded_pop(8, 8) == 8
        assert pop_sharding.padded_pop(16, 8) == 16
        assert pop_sharding.padded_pop(16, 3) == 18
        assert pop_sharding.padded_pop(5, 1) == 5

    def test_pop_axis_size(self):
        assert pop_sharding.pop_axis_size(None) == 1
        mesh = make_population_mesh()
        assert pop_sharding.pop_axis_size(mesh) >= 1
        with pytest.raises(ValueError):
            pop_sharding.pop_axis_size(mesh, axis="nonexistent")

    def test_bad_partition_mode(self):
        mesh = make_population_mesh()
        with pytest.raises(ValueError):
            pop_sharding.shard_population(lambda x: x, mesh, n_replicated=0,
                                          mode="magic")


# ------------------------------------------------- in-process (1-dev mesh)


@pytest.fixture(scope="module")
def trained():
    return X.train_small_sru(steps=40)


@pytest.fixture(scope="module")
def mesh1():
    return make_population_mesh()     # 1 device in the plain test process


def _random_allocs(problem, n, seed=0):
    rng = np.random.default_rng(seed)
    return [problem.decode(problem._snap(rng.integers(1, 5, problem.n_var)))
            for _ in range(n)]


class TestSingleDeviceMeshParity:
    """The mesh code path (padding to shard multiples, shard_map/gspmd
    wrapping, host gather) must be a bit-exact no-op on a 1-device mesh."""

    @pytest.mark.parametrize("partition", ["shard_map", "gspmd"])
    def test_errors_parity_odd_population(self, trained, mesh1, partition):
        prob = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        allocs = _random_allocs(prob, 5, seed=2)
        scalar = [trained.val_error(a) for a in allocs]
        sharded = trained.val_error_batch(allocs, mesh=mesh1,
                                          partition=partition)
        assert sharded == scalar

    def test_evaluate_population_through_mesh(self, trained, mesh1):
        """build_problem(mesh=...) routes evaluate_population through the
        sharded evaluator with identical objectives + violations."""
        prob_m = X.build_problem(trained, X.BITFUSION, ("error", "speedup"),
                                 mesh=mesh1)
        prob_m.error_memo = {}
        prob_s = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_s.error_memo = {}
        rng = np.random.default_rng(4)
        genomes = [rng.integers(1, 5, prob_m.n_var) for _ in range(13)]
        batched = prob_m.evaluate_population(genomes)
        scalar = [prob_s.evaluate(g) for g in genomes]
        for (so, sv), (bo, bv) in zip(scalar, batched):
            assert list(so) == list(bo) and sv == bv

    def test_search_front_identical(self, trained, mesh1):
        """Full NSGA-II: sharded (1-dev mesh) vs plain batched — identical
        Pareto fronts and eval counts."""
        kw = dict(n_generations=3, pop_size=5, initial_pop_size=9, seed=3)
        prob_m = X.build_problem(trained, X.BITFUSION, ("error", "speedup"),
                                 mesh=mesh1)
        prob_p = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        prob_m.error_memo = {}
        prob_p.error_memo = {}
        rm = X.run_search(prob_m, **kw)
        rp = X.run_search(prob_p, **kw)
        key = lambda res: sorted((tuple(i.genome.tolist()),
                                  tuple(i.objectives.tolist()),
                                  float(i.violation)) for i in res.pareto)
        assert key(rm) == key(rp)
        assert rm.n_evals == rp.n_evals


# ----------------------------------------------- 8-device host mesh (slow)

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.core import sru_experiment as X
    from repro.launch.mesh import make_population_mesh

    out = {"n_devices": len(jax.devices())}
    trained = X.train_small_sru(steps=30)
    mesh = make_population_mesh()
    out["mesh_pop"] = int(mesh.shape["pop"])
    prob = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
    rng = np.random.default_rng(0)

    # ---- error parity: divisible and non-divisible populations ----
    for p in (5, 13, 32):
        allocs = [prob.decode(prob._snap(rng.integers(1, 5, prob.n_var)))
                  for _ in range(p)]
        scalar = [trained.val_error(a) for a in allocs]
        for part in ("shard_map", "gspmd"):
            got = trained.val_error_batch(allocs, mesh=mesh, partition=part)
            out[f"errors_p{p}_{part}"] = bool(got == scalar)

    # ---- full NSGA-II front parity, pop 32 and non-divisible 5/13 ----
    key = lambda res: sorted((tuple(i.genome.tolist()),
                              tuple(i.objectives.tolist()),
                              float(i.violation)) for i in res.pareto)
    for pop, gens, init in ((5, 3, 9), (13, 2, 13), (32, 2, 32)):
        kw = dict(n_generations=gens, pop_size=pop, initial_pop_size=init,
                  seed=3)
        pm = X.build_problem(trained, X.BITFUSION, ("error", "speedup"),
                             mesh=mesh)
        ps = X.build_problem(trained, X.BITFUSION, ("error", "speedup"))
        pm.error_memo = {}
        ps.error_memo = {}
        rm = X.run_search(pm, **kw)
        rs = X.run_search(ps, **kw)
        out[f"front_p{pop}"] = bool(key(rm) == key(rs))
        out[f"evals_p{pop}"] = bool(rm.n_evals == rs.n_evals)

    # ---- beacon-grouped routing shards independently ----
    kw = dict(generations=2, pop=6, initial=8, seed=0, retrain_steps=3)
    r_m, bs_m = X.experiment3_bitfusion(trained, beacon=True, mesh=mesh, **kw)
    r_s, bs_s = X.experiment3_bitfusion(trained, beacon=True, **kw)
    out["beacon_front"] = bool(key(r_m) == key(r_s))
    out["beacon_retrains"] = bool(bs_m.n_retrains == bs_s.n_retrains)
    out["beacon_nbeacons"] = bool(len(bs_m.beacons) == len(bs_s.beacons))

    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh8_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
class TestEightDeviceMesh:
    def test_mesh_really_eight_wide(self, mesh8_results):
        assert mesh8_results["n_devices"] == 8
        assert mesh8_results["mesh_pop"] == 8

    @pytest.mark.parametrize("p", [5, 13, 32])
    @pytest.mark.parametrize("partition", ["shard_map", "gspmd"])
    def test_errors_bit_identical(self, mesh8_results, p, partition):
        assert mesh8_results[f"errors_p{p}_{partition}"]

    @pytest.mark.parametrize("p", [5, 13, 32])
    def test_search_fronts_bit_identical(self, mesh8_results, p):
        assert mesh8_results[f"front_p{p}"]
        assert mesh8_results[f"evals_p{p}"]

    def test_beacon_grouped_routing(self, mesh8_results):
        assert mesh8_results["beacon_front"]
        assert mesh8_results["beacon_retrains"]
        assert mesh8_results["beacon_nbeacons"]
