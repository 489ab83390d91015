"""Seconds from the start of the span ``from`` to the end of the span
``to`` within one scheduler iteration of the program's timeline
(``readings["iterations"]``), less the durations of the spans named in
``minus`` that lie inside; over the iterations that hold both ends:
their mean, or the quantile ``q``, times ``scale``."""

from . import aggregate


def reduce(metric, readings):
    xs = []
    for spans in readings.get("iterations") or []:
        a = [s for s in spans if s["name"] == metric["from"]]
        b = [s for s in spans if s["name"] == metric["to"]]
        if not a or not b:
            continue
        lo, hi = a[0]["t0"], b[-1]["t1"]
        xs.append(hi - lo - sum(
            s["t1"] - s["t0"] for s in spans
            if s["name"] in metric.get("minus", ())
            and lo <= s["t0"] and s["t1"] <= hi))
    return aggregate(xs, metric)
