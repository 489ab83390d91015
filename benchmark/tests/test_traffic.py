"""Seeded plans reproduce, and every seed gets the same schedule of
sizes and gaps with token ids of its own."""

import numpy as np

from benchmark.lib import model
from benchmark.lib.traffic import PackedDocuments, request_plan

BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def _sizes(plan):
    return sorted((len(r["prompt"]), r["max_new"]) for r in plan)


def test_request_plans():
    for name in ("offline-batch", "chat-steady"):
        t = model.load_json("traffic", name)
        a = request_plan(t, BIG, 30.0, 32768)
        b = request_plan(t, BIG, 30.0, 32768)
        c = request_plan(t, 7, 30.0, 32768)
        assert all(np.array_equal(x["prompt"], y["prompt"])
                   and x["at"] == y["at"] and x["max_new"] == y["max_new"]
                   for x, y in zip(a, b))
        assert _sizes(a) == _sizes(c)
        lens = lambda plan: [len(r["prompt"]) for r in plan]
        # one schedule for every seed: the seed gives the token ids
        # (and the weights), not the work
        assert lens(a) == lens(c)
        assert [r["max_new"] for r in a] == [r["max_new"] for r in c]
        assert not np.array_equal(a[0]["prompt"], c[0]["prompt"])
        lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
        assert all(lo <= len(r["prompt"]) <= hi for r in a)
        assert all(t["output_tokens"]["min"] <= r["max_new"]
                   <= t["output_tokens"]["max"] for r in a)
        ats = [r["at"] for r in a]
        assert ats == sorted(ats)
        if t["arrivals"]["process"] == "poisson":
            assert abs(a[-1]["at"] - c[-1]["at"]) < 1e-6   # same gaps
            rate = len(a) / a[-1]["at"]
            assert 0.6 * t["arrivals"]["rate_rps"] < rate \
                < 1.6 * t["arrivals"]["rate_rps"]
            # the window holds exactly what the rate states, whatever
            # the rate and the window: one draw's chance is stretched out
            for r, secs in ((t["arrivals"]["rate_rps"], 30.0), (4.5, 30.0),
                            (2.0, 10.0)):
                mix = dict(t, arrivals=dict(t["arrivals"], rate_rps=r))
                due = [x["at"] for x in request_plan(mix, 7, secs, 32768)]
                assert sum(x < secs for x in due) == round(r * secs)
                assert abs(due[round(r * secs)] - secs) < 1e-9
        else:
            assert ats[-1] == 0.0


def test_packed_rows():
    t = model.load_json("traffic", "pretrain-2k")
    d = PackedDocuments(t, BIG, 32768)
    ids, labels = d[5]
    again = PackedDocuments(t, BIG, 32768)[5]
    assert ids.shape == labels.shape == (2048,)
    assert np.array_equal(ids, again[0])
    assert np.array_equal(ids[1:], labels[:-1])
    assert not np.array_equal(ids, d[6][0])
    assert not np.array_equal(ids, PackedDocuments(t, 3, 32768)[5][0])
