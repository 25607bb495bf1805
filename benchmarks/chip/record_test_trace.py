"""Record the small chip trace that ``test_chipbench.py`` reduces.

    python3 benchmarks/chip/record_test_trace.py [out.xplane.pb]

On a TPU: three "searches", each a short host wait and one call of a
jitted program named ``_batch_err`` (a matmul and a short scan) inside the
harness's host spans, traced as a benchmark window is. Prints the trace's
planes and lines, then the reduction.
"""
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "testdata", "small.xplane.pb")


def main(out: str = OUT) -> None:
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    import xplane

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_test_trace: needs a TPU")

    def _batch_err(x):
        y = x @ x
        _, ys = jax.lax.scan(lambda c, r: (jnp.tanh(c + r), c), y[0], y)
        return ys.sum()

    f = jax.jit(_batch_err)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    tdir = os.path.join(HERE, ".cache", "trace", "record")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    with TraceAnnotation(xplane.WINDOW):
        for _ in range(3):
            with TraceAnnotation("bench.search"):
                time.sleep(0.002)
                with TraceAnnotation("bench.evaluator"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_trace(tdir)
    shutil.copyfile(path, out)
    pd = ProfileData.from_file(out)
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12])
        for ln in plane.lines:
            for e in list(ln.events)[:4]:
                print("   ", ln.name, "|", e.name, e.start_ns, e.duration_ns)
    print(os.path.getsize(out), "bytes")
    print(xplane.reduce(xplane.load(out), "_batch_err"))


if __name__ == "__main__":
    main(*sys.argv[1:])
