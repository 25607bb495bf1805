"""The language-model targets' ``gathered_bytes`` stat on a population mesh.

Under ``shard_map`` the evaluator hands a target the lane count of one
shard; the stat must still count the per-lane weight copies of every lane
the program computes, as it does without a mesh. A padded bucket (3
allocations in 4 lanes) puts a padding lane on one shard: the granite
forward skips it, the xLSTM's computes it, and both score every real
lane as on one device. Runs the granite and xLSTM contract harnesses'
targets on a 2-device host mesh in a subprocess
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``), so the mesh is
real and this process keeps its one device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax
    from repro.core import lm_target as LM
    from repro.core import target_registry as tr
    from repro.launch.mesh import make_population_mesh

    mesh = make_population_mesh()
    out = {"pop_shards": int(mesh.shape["pop"])}
    for name in ("granite_hybrid", "xlstm"):
        t = tr.get_contract_harness(name).target
        allocs = [{n: (b, 8) for n in t.layer_names} for b in (2, 4, 8, 16)]
        got = {}
        for label, m in (("one_device", None), ("shard_map", mesh)):
            ev = t.batched_evaluator(mesh=m, use_banks=True)
            seen, real = [], ev._dispatch_stats
            ev._dispatch_stats = lambda traced, computed, banks: (
                seen.append((traced, computed,
                             real(traced, computed, banks))) or seen[-1][2])
            errs = ev.errors(allocs, t.params)
            padded = ev.errors(allocs[:3], t.params)
            got[label] = {"lanes": [l for l, _, _ in seen],
                          "computed": [c for _, c, _ in seen],
                          "gathered": [s["gathered_bytes"]
                                       for _, _, s in seen],
                          "errors": [float(e) for e in errs],
                          "padded_errors": [float(e) for e in padded]}
        banks = t.make_banks(t.params)
        got["whole_bucket"] = LM.gathered_bytes(banks, 4)
        got["three_lanes"] = LM.gathered_bytes(banks, 3)
        out[name] = got
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT ")]
    assert run.returncode == 0 and line, run.stderr[-3000:]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["pop_shards"] == 2
    return out


@pytest.mark.parametrize("name", ["granite_hybrid", "xlstm"])
def test_gathered_bytes_count_the_whole_bucket_on_a_mesh(mesh_run, name):
    """4 allocations, one bucket of 4 lanes: 2 traced lanes a shard, and
    the copies of all 4 lanes counted on the mesh as on one device."""
    got = mesh_run[name]
    assert got["one_device"]["lanes"][0] == 4
    assert got["shard_map"]["lanes"][0] == 2
    for label in ("one_device", "shard_map"):
        assert got[label]["computed"][0] == 4, label
        assert got[label]["gathered"][0] == got["whole_bucket"], label
    assert got["shard_map"]["errors"] == got["one_device"]["errors"]


@pytest.mark.parametrize("name", ["granite_hybrid", "xlstm"])
def test_padded_bucket_on_a_mesh_scores_as_one_device(mesh_run, name):
    """3 allocations in a bucket of 4, so one shard holds a padding lane:
    the granite forward skips it (3 lanes computed and gathered), the
    xLSTM's computes it (4), and on both the mesh's errors equal the one
    device's and those of the same allocations in the unpadded bucket."""
    got = mesh_run[name]
    padded = 3 if name == "granite_hybrid" else 4
    copies = got["three_lanes" if padded == 3 else "whole_bucket"]
    assert got["one_device"]["lanes"][1] == 4
    assert got["shard_map"]["lanes"][1] == 2
    for label in ("one_device", "shard_map"):
        assert got[label]["computed"][1] == padded, label
        assert got[label]["gathered"][1] == copies, label
    assert got["shard_map"]["padded_errors"] \
        == got["one_device"]["padded_errors"] \
        == got["one_device"]["errors"][:3]
