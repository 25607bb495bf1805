"""Compile a cell's generation program for a described TPU v5e, without a
chip, at every compile bucket the cell's mix warms, and print what
``compiled.memory_analysis()`` says each needs on the device.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py sru_timit.search

Nothing runs: the weights are shapes, the fold is made on the CPU, and the
grids are placeholders (the program's shapes do not depend on them).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(workload: str) -> None:
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cellrun
    from families import common as C

    jax.config.update("jax_enable_compilation_cache", False)
    _, cell, cfg, mix = cellrun.load_cell(workload)
    fam = cellrun.load_family(cfg["family"])
    params = jax.eval_shape(lambda: fam.init_weights(cfg, 0))
    inputs = fam.make_inputs(cfg, mix, 0)
    labels = jnp.zeros(inputs.shape[:2], jnp.int32)
    names = fam.layer_names(cfg)
    grids = C.Grids({n: 1.0 for n in names},
                    {(n, b): 0.5 for n in names for b in (2, 4, 8)},
                    {n: 1.0 for n in names})
    n_sub, rows = mix["fold"]["subsets"], mix["fold"]["rows"]
    subsets = [(inputs[s * rows:(s + 1) * rows],
                labels[s * rows:(s + 1) * rows]) for s in range(n_sub)]
    target = fam.build_target(cfg, params, subsets, grids,
                              score_baseline=False)
    ev = target.batched_evaluator()

    def banks_of(p):
        banks = ev._make_banks(p)
        if ev._folded and ev._extend_banks is not None:
            banks = ev._extend_banks(banks, ev._feats_all)
        return banks

    banks = jax.eval_shape(banks_of, params)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    args = on_chip((params, banks, ev._feats_all, ev._labels_all))
    gib = 2.0 ** 30
    for b in mix["warm_buckets"]:
        stack = jax.ShapeDtypeStruct((b, len(names), 6), jnp.float32,
                                     sharding=chip)
        m = jax.jit(ev._batch_err_fn, donate_argnums=(4,)).lower(
            *args, stack).compile().memory_analysis()
        peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes)
        print(f"{workload} bucket {b:3d}: arguments "
              f"{m.argument_size_in_bytes / gib:.3f} GiB, temporaries "
              f"{m.temp_size_in_bytes / gib:.3f} GiB, output "
              f"{m.output_size_in_bytes} B, peak {peak / gib:.3f} GiB "
              f"(v5e:2x2, one chip)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
