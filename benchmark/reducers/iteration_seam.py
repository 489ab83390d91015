"""The seam between two decode steps on the host's ONE clock: seconds
from the end of the span ``from`` in one scheduler iteration to the end
of the span ``to`` in the next, less the durations of the spans named
in ``minus`` that lie inside.  Over the pairs of iterations
(``readings["iterations"]``, each a list of spans in the order they
opened, the root first) that are ADJACENT by the root's ``n``, both
holding a ``decode_dispatch``, the second holding no ``prefill_chunk``,
``kv_snapshot`` or ``kv_restore`` (an admission's seam is another
thing): their mean, or the quantile ``q``, times ``scale``.

``"over": "period"``: 100 x the summed seams over the summed periods
(end of ``from`` in the second less its end in the first, less the
``minus`` spans inside as well): the share of a pure decode period that
the seam takes.  ``"named": true``: per cent
of the seams' seconds (less ``minus``) that lie inside a LEAF span, one
with no child: what a span names, against what falls to a parent's own
time or between two roots (the host-clock twin of
``tools/trace_report.py:leaf_share``).  No pair: None."""

from . import aggregate

ADMITS = ("prefill_chunk", "kv_snapshot", "kv_restore")


def _end(spans, name):
    return next((s["t1"] for s in reversed(spans) if s["name"] == name),
                None)


def _leaves(spans):
    """Spans lie in the order they opened: the one opened next is a
    child exactly when it starts before this one ends."""
    return [s for s, nxt in zip(spans, spans[1:] + [None])
            if nxt is None or nxt["t0"] >= s["t1"]]


def _inside(spans, lo, hi):
    return sum(s["t1"] - s["t0"] for s in spans
               if lo <= s["t0"] and s["t1"] <= hi)


def reduce(metric, readings):
    minus = metric.get("minus", ())
    seams, periods, named = [], [], 0.0
    its = readings.get("iterations") or []
    for a, b in zip(its, its[1:]):
        names_a = {s["name"] for s in a}
        names_b = {s["name"] for s in b}
        if a[0]["attrs"].get("n") is None \
                or b[0]["attrs"].get("n") != a[0]["attrs"]["n"] + 1 \
                or "decode_dispatch" not in names_a \
                or "decode_dispatch" not in names_b \
                or names_b.intersection(ADMITS):
            continue
        lo, hi = _end(a, metric["from"]), _end(b, metric["to"])
        again = _end(b, metric["from"])
        if lo is None or hi is None or again is None or hi < lo:
            continue
        out = [s for s in a + b if s["name"] in minus]
        seams.append(hi - lo - _inside(out, lo, hi))
        periods.append(again - lo - _inside(out, lo, again))
        named += sum(max(0.0, min(s["t1"], hi) - max(s["t0"], lo))
                     for s in _leaves(a) + _leaves(b)
                     if s["name"] not in minus)
    if not seams or sum(seams) <= 0:
        return None
    if metric.get("named"):
        return 100.0 * named / sum(seams)
    if metric.get("over") == "period":
        return 100.0 * sum(seams) / sum(periods)
    return aggregate(seams, metric)
