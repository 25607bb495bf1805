"""The language-model targets' ``gathered_bytes`` stat on a population mesh.

Under ``shard_map`` the evaluator hands a target the lane count of one
shard; the stat must still count the per-lane weight copies of the whole
bucket, as it does without a mesh. Runs the granite and xLSTM contract
harnesses' targets on a 2-device host mesh in a subprocess
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``), so the mesh is
real and this process keeps its one device."""
import json
import os
import subprocess
import sys
import textwrap

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
            ev._dispatch_stats = lambda lanes, banks: (
                seen.append((lanes, real(lanes, banks))) or seen[-1][1])
            errs = ev.errors(allocs, t.params)
            got[label] = {"lanes": [l for l, _ in seen],
                          "gathered": [s["gathered_bytes"] for _, s in seen],
                          "errors": [float(e) for e in errs]}
        got["whole_bucket"] = LM.gathered_bytes(t.make_banks(t.params), 4)
        out[name] = got
    print("RESULT " + json.dumps(out))
""")


def test_gathered_bytes_count_the_whole_bucket_on_a_mesh():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    line = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT ")]
    assert run.returncode == 0 and line, run.stderr[-3000:]
    out = json.loads(line[-1][len("RESULT "):])
    assert out["pop_shards"] == 2
    for name in ("granite_hybrid", "xlstm"):
        got = out[name]
        # 4 allocations, one bucket of 4 lanes: 2 traced lanes a shard
        assert got["one_device"]["lanes"] == [4], name
        assert got["shard_map"]["lanes"] == [2], name
        assert got["one_device"]["gathered"] == [got["whole_bucket"]], name
        assert got["shard_map"]["gathered"] == [got["whole_bucket"]], name
        assert got["shard_map"]["errors"] == got["one_device"]["errors"]
