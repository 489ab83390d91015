"""One reader a file: ``reduce(metric, readings) -> float | None``.  A
reader that finds nothing to read returns None, never 0 for a share."""


def aggregate(xs, metric):
    """What the duration readers report of their samples: the quantile
    ``q`` (nearest rank on the sorted values) where the metric's file
    gives one, else the mean; times ``scale``.  None of no samples."""
    if not xs:
        return None
    if "q" in metric:
        xs = sorted(xs)
        value = xs[min(len(xs) - 1,
                       max(0, round(metric["q"] * (len(xs) - 1))))]
    else:
        value = sum(xs) / len(xs)
    return metric.get("scale", 1.0) * value
