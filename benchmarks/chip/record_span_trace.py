"""Record the small chip trace of the program's own spans that
``test_progspans.py`` reads.

    python3 benchmarks/chip/record_span_trace.py

On a TPU: a tiny SRU search (the program's small search config, untrained
weights, 3 generations) through the harness's ``Recorder``, inside the
harness's ``bench.window`` and ``bench.search`` spans, traced as a
benchmark window is. Writes ``testdata/spans.xplane.pb`` and, beside it,
``spans.json`` with what the search counted (allocations scored,
generations, seconds inside ``bench.evaluator``). Prints the layers.
"""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OUT = os.path.join(HERE, "testdata", "spans.xplane.pb")
GENERATIONS = 3


def main() -> None:
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax

    import cellrun
    import progspans
    import xplane
    from repro.core import sru_experiment as X
    from repro.core.api import SearchSession

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_span_trace: needs a TPU")
    target = X.train_small_sru(steps=0)
    rec = cellrun.Recorder(target, target.batched_evaluator(),
                           annotate=True)

    def search():
        return SearchSession(rec, "bitfusion", ("error", "speedup"),
                             sram_override=10 ** 9, share_memo=False).run(
            generations=GENERATIONS, pop=6, initial=10, seed=5)

    search()                                      # compile every bucket
    rec.spans.clear()
    tdir = os.path.join(HERE, ".cache", "trace", "record_spans")
    shutil.rmtree(tdir, ignore_errors=True)
    # no Python call tracing and no HLO protos: the test reads only the
    # spans and the device's operations
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with cellrun.span(xplane.WINDOW, True):
        with cellrun.span("bench.search", True):
            res = search()
    jax.profiler.stop_trace()
    shutil.copyfile(xplane.find_trace(tdir), OUT)
    meta = {"evals": res.problem.n_error_evals, "generations": GENERATIONS,
            "evaluator_s": sum(t1 - t0 for t0, t1 in rec.spans)}
    with open(OUT.replace(".xplane.pb", ".json"), "w") as f:
        json.dump(meta, f)
    events = xplane.load(OUT)
    red = xplane.reduce(events, "_batch_err")
    print(os.path.getsize(OUT), "bytes", meta)
    print(progspans.layers(progspans.load_spans(OUT), events["devices"],
                           red["window_s"]))


if __name__ == "__main__":
    main()
