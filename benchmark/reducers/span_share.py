"""Summed duration of the spans named ``span`` over that of the spans
named ``of`` (``readings["spans"]``), times ``scale``: the part of the
one that the other takes."""


def reduce(metric, readings):
    num = readings["spans"].get(metric["span"])
    den = readings["spans"].get(metric["of"])
    if not num or not den or sum(den) <= 0:
        return None
    return metric.get("scale", 1.0) * sum(num) / sum(den)
