"""Checks of ``progspans``, the reading of the program's own spans, on
synthetic event lists: self time, clipping to the window, device idle split
by span, the pad share, the identity check against the reduced window, and
the four readers' output; and, where one was recorded, on a small trace of
a tiny search on a TPU v5e (``record_span_trace.py``).

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_progspans.py
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import cellrun  # noqa: E402
import progspans  # noqa: E402
import xplane  # noqa: E402

NS = 1e-9
WINDOW = (0, 1000)


def span(name, s, e, **stats):
    return (name, s, e, stats)


# one generation in a 1000-ns window, nested as the program records it
GENERATION = [
    span("bench.window", *WINDOW),
    span("search.build", 20, 60),
    span("ga.generation", 100, 500, gen=0),
    span("ga.rank", 100, 150),
    span("ga.offspring", 150, 200),
    span("mohaq.evaluate", 200, 400),
    span("bench.evaluator", 250, 350),
    span("evaluator.errors", 260, 340),
    span("evaluator.stack", 260, 270),
    span("evaluator.dispatch", 270, 280, lanes=5, bucket=8),
    span("evaluator.wait", 280, 330),
    span("evaluator.readback", 330, 340),
    span("mohaq.objectives", 380, 400),
    span("ga.survive", 400, 480),
]
# the device runs 120-140 (under ga.rank), 285-325 (under the wait) and
# 600-1000 (after the generation)
DEVICE = {"/device:TPU:0": {xplane.OPS_LINE: [("a", 285, 325)],
                            xplane.MODULES_LINE: [("m", 120, 140),
                                                  ("m", 600, 1000)]}}


def window_s(lo=WINDOW[0], hi=WINDOW[1]):
    return (hi - lo) * NS


def test_self_time_is_union_less_children():
    got = progspans.layers(GENERATION, DEVICE, window_s())
    layers = got["layers"]
    assert layers["ga"]["share"] == pytest.approx(20.0)       # 400 - 200
    assert layers["problem"]["share"] == pytest.approx(10.0)  # 200 - 100
    assert layers["evaluator"]["share"] == pytest.approx(3.0)  # 80 - 50
    assert layers["ga"]["spans_pct"] == pytest.approx(
        {"ga.rank": 5.0, "ga.offspring": 5.0, "ga.survive": 8.0,
         "rest": 2.0})
    assert layers["problem"]["spans_pct"] == pytest.approx(
        {"mohaq.objectives": 2.0, "rest": 8.0})
    assert got["generations"] == 1


def test_overlapping_spans_count_once():
    doubled = GENERATION + [span("ga.generation", 120, 480, gen=0)]
    got = progspans.layers(doubled, DEVICE, window_s())
    assert got["layers"]["ga"]["share"] == pytest.approx(20.0)


def test_spans_are_clipped_to_the_window():
    late = [span("ga.generation", 900, 1300, gen=1),
            span("evaluator.dispatch", 1100, 1200, lanes=3, bucket=4)]
    got = progspans.layers(GENERATION + late, DEVICE, window_s())
    assert got["layers"]["ga"]["share"] == pytest.approx(30.0)
    assert got["dispatch"] == {"dispatches": 1, "lanes": 5,
                               "bucket_lanes": 8}


def test_idle_is_split_by_innermost_span():
    got = progspans.layers(GENERATION, DEVICE, window_s())
    ga, ev = got["layers"]["ga"], got["layers"]["evaluator"]
    # ga self 200 ns, 20 of them busy (under ga.rank)
    assert ga["idle_pct"] == pytest.approx(18.0)
    assert ga["idle_by_span"] == pytest.approx(
        {"ga.rank": 3.0, "ga.offspring": 5.0, "ga.survive": 8.0,
         "rest": 2.0})
    assert got["layers"]["problem"]["idle_pct"] == pytest.approx(10.0)
    # the wait is 50 ns, 40 of them busy
    assert got["wait_idle_pct"] == pytest.approx(1.0)
    assert ev["idle_pct"] == pytest.approx(3.0)
    assert ev["idle_by_span"] == pytest.approx(
        {"evaluator.stack": 1.0, "evaluator.dispatch": 1.0,
         "evaluator.readback": 1.0, "rest": 0.0})
    assert got["layers"]["build"]["idle_pct"] == pytest.approx(4.0)
    # disjoint pieces: never more than the device's idle time
    device_idle = 100.0 - 46.0
    pieces = (sum(v["idle_pct"] for v in got["layers"].values())
              + got["wait_idle_pct"])
    assert pieces == pytest.approx(36.0) and pieces <= device_idle


def test_idle_is_averaged_over_device_planes():
    two = dict(DEVICE, **{"/device:TPU:1": {xplane.OPS_LINE: []}})
    got = progspans.layers(GENERATION, two, window_s())
    # plane 1 is idle all through the wait: (10 + 50) / 2 ns
    assert got["wait_idle_pct"] == pytest.approx(3.0)


def test_pad_share_counts_real_and_padded_lanes():
    more = [span("evaluator.dispatch", 700, 710, lanes=40, bucket=64)]
    got = progspans.layers(GENERATION + more, DEVICE, window_s())
    assert got["dispatch"] == {"dispatches": 2, "lanes": 45,
                               "bucket_lanes": 72}
    value, extra = _read("search.pad_lane_share", got)
    assert value == pytest.approx(100.0 * 27 / 72)
    assert extra["lanes"] == 45


@pytest.mark.parametrize("spans, win", [
    (GENERATION, window_s(0, 999)),                   # another window
    ([s for s in GENERATION if s[0] != "bench.window"], window_s()),
    (GENERATION + [span("bench.window", 0, 1000)], window_s()),
])
def test_a_trace_of_another_window_gives_nothing(spans, win):
    assert progspans.layers(spans, DEVICE, win) is None


METRICS = ("search.ga_share", "search.problem_share",
           "search.evaluator_host_share", "search.pad_lane_share")


def _read(metric, got):
    """What ``metric``'s reader returns when ``progspans.read`` gives
    ``got``."""
    mp = pytest.MonkeyPatch()
    mp.setattr(progspans, "read", lambda ctx, root=None: got)
    try:
        return cellrun.load_metric(metric).read({"trace": {}})
    finally:
        mp.undo()


def test_readers_report_each_layer():
    got = progspans.layers(GENERATION, DEVICE, window_s())
    ga, prob, ev, pad = (_read(m, got) for m in METRICS)
    assert ga[0] == pytest.approx(20.0) and ga[1]["generations"] == 1
    assert prob[0] == pytest.approx(10.0)
    assert prob[1]["idle_pct"] == pytest.approx(10.0)
    assert prob[1]["build_pct"] == pytest.approx(4.0)
    assert ev[0] == pytest.approx(3.0)
    assert set(ev[1]) == {"idle_pct", "wait_idle_pct", "by_span",
                          "spans_pct"}
    assert pad[0] == pytest.approx(37.5)


@pytest.mark.parametrize("metric", METRICS)
def test_readers_give_nothing_without_program_spans(metric):
    """A program that records no spans (an older one): every reader
    returns None, none raises."""
    bench_only = [s for s in GENERATION if s[0].startswith("bench.")]
    got = progspans.layers(bench_only, DEVICE, window_s())
    assert got["layers"] == {}
    assert _read(metric, got) is None
    assert _read(metric, None) is None


def test_read_needs_a_traced_run(tmp_path):
    assert progspans.read({"trace": None}, str(tmp_path)) is None
    assert progspans.read({"trace": {"window_s": 1.0}},
                          str(tmp_path)) is None       # no trace file


RECORDED = os.path.join(HERE, "testdata", "spans.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded span trace")
def test_layers_of_recorded_chip_trace():
    """A tiny search traced on a TPU v5e the way a benchmark window is:
    the file passes its own identity check, the layers cover the host
    time outside the evaluator, the idle pieces stay within the device's
    idle time, and the dispatches' lanes add up to the allocations the
    search scored."""
    with open(RECORDED.replace(".xplane.pb", ".json")) as f:
        meta = json.load(f)
    events = xplane.load(RECORDED)
    red = xplane.reduce(events, "_batch_err")
    got = progspans.layers(progspans.load_spans(RECORDED),
                           events["devices"], red["window_s"])
    layers = got["layers"]
    assert set(layers) == {"ga", "problem", "evaluator", "build"}
    idle = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    pieces = (sum(v["idle_pct"] for v in layers.values())
              + got["wait_idle_pct"])
    assert 0 < pieces <= idle + 1e-9
    assert got["dispatch"]["lanes"] == meta["evals"]
    assert got["generations"] == meta["generations"]
    host = 100.0 * (1.0 - meta["evaluator_s"] / red["window_s"])
    assert layers["ga"]["share"] + layers["problem"]["share"] <= host
