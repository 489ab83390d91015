"""The seam between two decode steps (PR 38): ``iteration_seam`` and
``trace_gap_per_run`` on hand-made readings, and the tiny twins of the
sixteen metric files through a traced CPU rehearsal of a batch and a
chat cell."""

import os

import pytest

from benchmark.lib import xplane
from benchmark.reducers import iteration_seam, trace_gap_per_run
from benchmark.tests import tiny
from benchmark.tests.test_harness import FIXTURE, LIMITS, run

SEAM = {"from": "device_wait", "to": "launch", "minus": ["stamp"]}


def span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


def iteration(n, at, admit=(), stamp=0.5):
    """One scheduler iteration of 10 units starting at ``at``, spans in
    the order they open: a decode step whose device is done at +5.0
    and whose launch ends at +3.0; ``admit``: children of its admit."""
    t = lambda x: at + x
    return [span("iteration", t(0), t(10), n=n),
            span("expire", t(0.1), t(0.2)),
            span("engine_step", t(0.2), t(8)),
            span("retire", t(0.2), t(0.4)),
            span("admit", t(0.4), t(1.0)),
            *[span(name, t(0.5), t(0.9)) for name in admit],
            span("plan", t(1.0), t(2.0), batch=2),
            span("decode_dispatch", t(2.0), t(3.0), batch=2),
            span("upload", t(2.0), t(2.4)),
            span("launch", t(2.5), t(3.0)),
            span("logits_fetch", t(3.0), t(6.0)),
            span("device_wait", t(3.0), t(5.0)),
            span("copy", t(5.0), t(6.0)),
            span("pick", t(6.0), t(7.0)),
            span("stamp", t(7.0), t(7.0 + stamp)),
            span("deliver", t(8.5), t(9.5)),
            span("collect", t(8.5), t(9.0)),
            span("apply", t(9.0), t(9.4))]


def seam(its, **more):
    return iteration_seam.reduce(dict(SEAM, **more), {"iterations": its})


def test_a_pair_of_decode_iterations():
    its = [iteration(1, 0.0), iteration(2, 10.0)]
    # device done at 5.0, the next launch handed over at 13.0, the
    # tracer's own half unit taken out
    assert seam(its) == pytest.approx(8.0 - 0.5)
    assert seam(its, scale=1e3) == pytest.approx(7500.0)
    # without `minus` the stamp stays in
    assert iteration_seam.reduce({"from": "device_wait", "to": "launch"},
                                 {"iterations": its}) == pytest.approx(8.0)
    # the period runs from one "device is done" to the next: 10 units,
    # the tracer's half unit taken out of it as out of the seam
    assert seam(its, over="period") == pytest.approx(100 * 7.5 / 9.5)
    assert iteration_seam.reduce(
        {"from": "device_wait", "to": "launch", "over": "period"},
        {"iterations": its}) == pytest.approx(80.0)
    # leaves inside the seam: copy 1, pick 1, collect .5, apply .4, then
    # expire .1, retire .2, admit .6, plan 1, upload .4, launch .5 = 5.7
    # of 7.5; engine_step, deliver, iteration and the time between two
    # roots name nothing
    assert seam(its, named=True) == pytest.approx(100 * 5.7 / 7.5)


def test_an_admission_or_a_hole_breaks_the_pair():
    # the second iteration ran a chunk fill: not a decode seam
    for name in iteration_seam.ADMITS:
        assert seam([iteration(1, 0.0),
                     iteration(2, 10.0, admit=(name,))]) is None
    # ... but the first may have: its fill came before its own step
    assert seam([iteration(1, 0.0, admit=("prefill_chunk",)),
                 iteration(2, 10.0)]) == pytest.approx(7.5)
    # a hole in `n`: the ring dropped the iteration between
    assert seam([iteration(1, 0.0), iteration(3, 10.0)]) is None
    # three in a row are two pairs; a quantile picks among them
    its = [iteration(1, 0.0), iteration(2, 10.0, stamp=0.1),
           iteration(3, 22.0)]
    assert seam(its) == pytest.approx((7.5 + 9.9) / 2)
    assert seam(its, q=1.0) == pytest.approx(9.9)
    assert seam(its, over="period") == pytest.approx(100 * 17.4 / 21.4)


def test_nothing_to_pair_is_none():
    assert seam([]) is None and seam([iteration(1, 0.0)]) is None
    assert iteration_seam.reduce(SEAM, {}) is None
    # a program without the new spans (the parent): no `from`, no `to`
    old = [[s for s in it if s["name"] not in ("device_wait", "launch")]
           for it in (iteration(1, 0.0), iteration(2, 10.0))]
    for more in ({}, {"over": "period"}, {"named": True}):
        assert seam(old, **more) is None
    # an idle iteration (no decode step) pairs with nothing
    idle = [span("iteration", 10.0, 11.0, n=2),
            span("engine_step", 10.1, 10.9)]
    assert seam([iteration(1, 0.0), idle, iteration(3, 11.0)]) is None


def test_trace_gap_per_run():
    summary = {"idle_gaps": [["pt:device_wait", 0.08], ["pt:launch", 0.03],
                             ["pt:pick", 0.02], ["pt:upload", 0.01],
                             ["unannotated", 0.5]],
               "module_count": {"jit_step(123)": 80, "jit_fill(9)": 7},
               "devices": 1}
    m = {"spans": ["pt:upload", "pt:launch", "pt:device_wait", "pt:copy"],
         "pattern": "^jit_step\\(", "scale": 1e3}
    got = trace_gap_per_run.reduce(m, {"trace": summary})
    assert got == pytest.approx(1e3 * 0.12 / 80)
    # no trace, no run of the program, none of the spans: nothing
    assert trace_gap_per_run.reduce(m, {}) is None
    assert trace_gap_per_run.reduce(dict(m, pattern="^jit_other"),
                                    {"trace": summary}) is None
    assert trace_gap_per_run.reduce(dict(m, spans=["pt:plan"]),
                                    {"trace": summary}) is None
    # four chips: seconds and runs are both a chip's
    four = dict(summary, devices=4, module_count={"jit_step(1)": 320})
    assert trace_gap_per_run.reduce(m, {"trace": four}) == \
        pytest.approx(got)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("seam")), LIMITS)


SPAN_MEANS = ("upload_ms", "launch_ms", "fetch_copy_ms", "plan_ms",
              "pick_ms", "collect_ms", "apply_ms")


@pytest.mark.parametrize("cell,suffix,host", [
    ("tiny-batch", "serve", "iter_host_ms.batch"),
    ("tiny-chat", "chat", "iter_host_ms.chat")])
def test_traced_rehearsal_reports_the_seam(root, monkeypatch, cell, suffix,
                                           host):
    # a CPU has no device plane: read the recorded TPU trace instead
    monkeypatch.setattr(xplane, "find_xplane", lambda d: FIXTURE)
    rc, lines, err = run(root, cell, 2 ** 31 + 38, seconds=2.0, trace=True)
    last = lines[-1]
    assert rc == 0 and last["correct"] is True, err
    m = {k: v["value"] for k, v in last["metrics"].items()}
    gap = m[f"step_gap_ms.{suffix}"]
    # the seam leaves out the wait for the device and the fills, which
    # the host's part of a whole iteration holds
    assert 0 < gap < m[host]
    assert 0 < m[f"step_gap_share.{suffix}"] < 100
    assert 50 < m[f"seam_named_share.{suffix}"] <= 100
    if suffix == "serve":
        for name in SPAN_MEANS:
            assert m[f"{name}.serve"] > 0, name
        # llama_tiny's [4, 256] float32 logits a step
        assert m["fetch_bytes_per_step.serve"] == 4 * 256 * 4
        # the seam's pieces cannot exceed it by more than the stamp
        # left out of it and the clock's grain
        pieces = sum(m[f"{n}.serve"] for n in SPAN_MEANS)
        assert pieces < 2 * gap
    # the recorded trace has no pt: spans: nothing to read, left out
    assert f"handoff_idle_ms.{suffix}" not in m


def test_every_new_metric_file_names_a_reducer_that_is_there():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from benchmark.lib.cell import load_metrics
    for cell, n in (("ling3flash-reason", 12), ("mistral7b-chat", 4)):
        new = [m for m in load_metrics(cell, here)
               if m["reducer"] in ("iteration_seam", "trace_gap_per_run")
               or m["name"].endswith(".serve")]
        assert len(new) == n, [m["name"] for m in new]
        for m in new:
            assert os.path.isfile(os.path.join(
                here, "reducers", m["reducer"] + ".py"))
