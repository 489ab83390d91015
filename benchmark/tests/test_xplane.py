"""The reduction from a trace to busy time, idle share, pattern time
and named gaps, on a small trace recorded on one TPU v5e chip
(``data/small.xplane.pb``: four executions of one jitted matmul+tanh,
~91.6 us each, ~11.7 ms apart, under ``bench:step`` / ``bench:fetch``
host spans; read by hand in PR 26)."""

import os

import pytest

from benchmark.lib import xplane
from benchmark.reducers import trace_idle, trace_roofline

TRACE = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_interval_arithmetic():
    assert xplane.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert xplane.union_seconds([(0, 1), (0, 1)]) == 1
    assert xplane.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xplane.gaps([(0, 6)], 0, 6) == []
    spans = [("bench:engine_step", 0.0, 10.0), ("bench:submit", 2.0, 3.0)]
    assert xplane.name_gap((2.1, 2.9), spans) == "bench:submit"
    assert xplane.name_gap((4.0, 5.0), spans) == "bench:engine_step"
    assert xplane.name_gap((11.0, 12.0), spans) == "unannotated"
    assert xplane.op_name("%fusion.12 = bf16[8]{0} fusion(%a)") == "fusion.12"
    # a gap is cut at every span boundary inside it; each piece goes to
    # the innermost span it lies in (the program's pt: spans nest in
    # the harness's bench: ones)
    spans = [("bench:engine_step", 0.0, 10.0), ("pt:iteration", 0.5, 9.5),
             ("pt:decode_dispatch", 1.0, 2.0), ("pt:logits_fetch", 2.0, 6.0),
             ("bench:submit", 10.0, 11.0)]
    named = xplane.idle_by_span([(0.0, 3.0), (5.0, 10.5), (20.0, 21.0)],
                                spans)
    assert named == pytest.approx({
        "bench:engine_step": 0.5 + 0.5, "pt:iteration": 0.5 + 3.5,
        "pt:decode_dispatch": 1.0, "pt:logits_fetch": 1.0 + 1.0,
        "bench:submit": 0.5, "unannotated": 1.0})
    assert xplane.idle_by_span([(0.0, 1.0)], []) == {"unannotated": 1.0}
    # a while holds its body's operations: its own time is the rest
    own = dict(xplane.self_seconds([("while.1", 0.0, 10.0),
                                    ("kernel", 1.0, 4.0),
                                    ("fusion", 5.0, 9.0),
                                    ("after", 10.0, 11.0)]))
    assert own == {"while.1": 3.0, "kernel": 3.0, "fusion": 4.0,
                   "after": 1.0}


def test_recorded_trace():
    s = xplane.summarize(TRACE)
    assert s["devices"] == 1
    # four programs of ~91.6 us: copy-start, copy-done, the fusion
    assert s["busy_s"] == pytest.approx(4 * 91.58e-6, rel=0.01)
    assert xplane.pattern_count(s, r"^jit_f\(") == 4
    assert xplane.pattern_seconds(s, r"^jit_f\(", "modules") == \
        pytest.approx(4 * 91.585e-6, rel=1e-3)
    assert xplane.pattern_seconds(s, "convolution_tanh", "ops") == \
        pytest.approx(4 * 91.56e-6, rel=1e-3)
    assert xplane.pattern_seconds(s, "no_such_kernel", "ops") == 0
    # no bench:window span in this trace: first to last device event
    assert s["window_s"] == pytest.approx(78.665254e-3 - 43.882011e-3,
                                          rel=1e-3)
    assert s["device_ops"][0][0] == "convolution_tanh_fusion"
    assert len(s["device_ops"]) <= 20 and len(s["idle_gaps"]) <= 20
    # the three gaps between programs are cut at the host spans' ends:
    # most of each falls under the host's sleep, the rest under the
    # step that follows it and the instants between the two
    assert [n for n, _ in s["idle_gaps"]] == ["bench:fetch", "bench:step",
                                              "unannotated"]
    assert s["idle_gaps"][0][1] == pytest.approx(30.501e-3, rel=1e-3)
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)


def test_trace_reducers():
    s = xplane.summarize(TRACE)
    idle = trace_idle.reduce({}, {"trace": s})
    assert idle == pytest.approx(100 * (1 - s["busy_s"] / s["window_s"]))
    assert 98.0 < idle < 99.5
    peaks = {"flops": 197e12, "bytes_per_s": 819e9}
    # four [2048,2048] x [2048,2048] products: 2 * 2048**3 FLOPs each
    work = {"f": 4 * 2 * 2048 ** 3}
    m = {"pattern": "convolution_tanh", "line": "ops", "flops": "f"}
    share = trace_roofline.reduce(m, {"trace": s, "peaks": peaks,
                                      "work": work})
    assert share == pytest.approx(
        100 * (work["f"] / 197e12) / (4 * 91.56e-6), rel=1e-3)
    assert 90 < share < 100          # ~95%: a large matmul near peak
    # nothing matched, or no trace: nothing to report, never 0
    assert trace_roofline.reduce(dict(m, pattern="splash"), {
        "trace": s, "peaks": peaks, "work": work}) is None
    assert trace_roofline.reduce(m, {"trace": None, "peaks": peaks,
                                     "work": work}) is None
    assert trace_idle.reduce({}, {"trace": None}) is None
