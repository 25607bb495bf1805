"""Chip benchmark of the MOHAQ population search: one run of one cell.

    python3 benchmarks/chip/run.py --workload sru_timit.search --seed 7 \
        --seconds 20 --trace 0

Runs from the root of a checkout, on a machine with the chips the cell asks
for, in one process. Prints the result as one JSON object on the last line
of standard output; the numbers compared with the reference, each beside
its limit, are the last lines of standard error and the result's last key.
Exits non-zero with no result when JAX finds no TPU. JAX's persistent
compilation cache lives in ``benchmarks/chip/.cache/jax`` inside the
checkout, so only a checkout's first run of a cell compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(HERE, ".cache", "jax")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    import cellrun

    result = cellrun.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
