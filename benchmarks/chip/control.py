"""Readings that set a cell's limits: the program, the control and the
planted faults, each put through the harness's own comparison.

    python3 benchmarks/chip/control.py sru_timit.search 101 102 103 ...

For each seed, in one process and at the cell's own sizes: the cell's
set-up, then one whole search through the program (a run's window holds
one or more), and the comparison a run makes of a sample of its
allocations with the plain reference. Then the control, the same
reference one step below the config's stated precision, put in the
program's place for the same allocations; a witness, the reference with
its matmuls in the backend's default passes (the program's setting), set
beside the program and the reference; then one whole search with each
fault of ``faults.py`` planted. Prints one JSON line per seed: every
number compared, for the program, the control and each fault, with
``correct`` as a run would decide it under the config's limits.
"""
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _values(checks):
    return {k: c["value"] for k, c in checks.items()}


def readings(workload, seed, *, require_tpu=True, cfg=None, mix=None,
             faults=None):
    import cellrun
    from faults import FAULTS, planted

    _, cell, cfg0, mix0 = cellrun.load_cell(workload)
    cfg, mix = cfg or cfg0, mix or mix0
    cellrun.device_info(cell["chips"], require_tpu)
    fam, params, inputs, labels, grids, target = cellrun.build(cfg, mix,
                                                               seed)
    ev = cellrun.evaluator_of(target, mix)
    data = (fam, cfg, mix, params, inputs, labels, grids)

    def search():
        rec = cellrun.Recorder(target, ev, annotate=False)
        _, evals, _ = cellrun.window(rec, mix, seed, 0.0)
        return rec.answers, evals

    out = {"workload": workload, "seed": seed}
    sound, out["evals"] = search()
    fault_answers = {}
    for name in (FAULTS if faults is None else faults):
        with planted(name):
            fault_answers[name] = search()[0]
    del target, ev
    gc.collect()

    keys = cellrun.sample(sound, seed, mix["check"]["sample"])
    refs = cellrun.reference_counts(*data, keys)
    checks, ok = cellrun.compare(sound, refs, keys, cfg, mix, len(sound))
    out["program"] = {"correct": ok, **_values(checks)}
    positions = mix["fold"]["rows"] * mix["fold"]["length"]
    ctl = {k: (float("nan") if c is None else cellrun.answer_of(c, positions),
               c) for k, c in zip(keys, cellrun.reference_counts(
                   *data, keys, which="control"))}
    checks, ok = cellrun.compare(ctl, refs, keys, cfg, mix, len(sound))
    out["control"] = {"correct": ok, **_values(checks)}
    # witness: the program against the same reference with its matmuls in
    # the passes the backend picks by default, the program's own setting
    import dataclasses
    from families import common as C
    wit = C.reference_scorer(fam, cfg, params, inputs, labels,
                             mix["fold"]["subsets"], dataclasses.replace(
                                 C.EXACT, activations=C.precision_of(
                                     cfg, "reference").activations,
                                 dot="default"))
    names = fam.layer_names(cfg)
    wits = [wit(C.qp_rows(dict(zip(names, k)), names, grids)) for k in keys]
    checks, _ = cellrun.compare(sound, wits, keys, cfg, mix, len(sound))
    out["program_vs_default_witness"] = _values(checks)
    wit_answers = {k: (float("nan") if c is None
                       else cellrun.answer_of(c, positions), c)
                   for k, c in zip(keys, wits)}
    checks, _ = cellrun.compare(wit_answers, refs, keys, cfg, mix,
                                len(sound))
    out["default_witness_vs_reference"] = _values(checks)
    for name, answers in fault_answers.items():
        checks, ok = cellrun.check(*data, answers, seed)
        out[name] = {"correct": ok, **_values(checks)}
    return out


def main(workload, *seeds):
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    cache = os.path.join(HERE, ".cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for s in seeds:
        print(json.dumps(readings(workload, int(s))), flush=True)


if __name__ == "__main__":
    main(*sys.argv[1:])
