"""Miniature end-to-end dry-run in a subprocess with 8 virtual devices:
proves lower+compile+roofline works under SPMD without the full sweep."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.distributed.sharding import axis_rules, tree_shardings
    from repro.models.registry import get_model, input_specs, batch_axes
    from repro.configs.base import ShapeConfig
    from repro.training import optimizer as opt, train_step as ts
    from repro.roofline.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh_for

    mesh = make_mesh_for(8, model_parallel=4)
    cfg = get_config("stablelm-1.6b").reduced()
    model = get_model(cfg)
    shape = ShapeConfig("t", 32, 4, "train")
    with axis_rules(mesh):
        specs = input_specs(cfg, shape)
        bshard = tree_shardings(mesh, batch_axes(cfg, shape), specs)
        sspecs = jax.eval_shape(lambda: ts.init_train_state(model, jax.random.PRNGKey(0)))
        sshard = tree_shardings(mesh, ts.train_state_axes(model), sspecs,
                                ensure_model=True)
        step = ts.make_train_step(model, opt.AdamWConfig())
        compiled = jax.jit(step, in_shardings=(sshard, bshard),
                           donate_argnums=(0,)).lower(sspecs, specs).compile()
    rc = analyze_hlo(compiled.as_text(), 8)
    print(json.dumps({"flops": rc.flops, "hbm": rc.hbm_bytes,
                      "ici": rc.ici_bytes, "colls": rc.n_collectives}))
""")


@pytest.mark.slow
def test_spmd_dryrun_8dev():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0
    assert res["hbm"] > 0
    assert res["colls"] > 0      # TP induces collectives
    assert res["ici"] > 0
