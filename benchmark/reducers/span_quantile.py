"""Quantile ``q`` of the spans named ``span`` (seconds), times
``scale`` (nearest rank on the sorted durations)."""


def reduce(metric, readings):
    xs = sorted(readings["spans"].get(metric["span"]) or [])
    if not xs:
        return None
    i = min(len(xs) - 1, max(0, round(metric["q"] * (len(xs) - 1))))
    return metric.get("scale", 1.0) * xs[i]
