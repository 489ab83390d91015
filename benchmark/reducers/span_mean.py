"""Mean duration of the spans named ``span`` (seconds), times
``scale``."""


def reduce(metric, readings):
    xs = readings["spans"].get(metric["span"])
    if not xs:
        return None
    return metric.get("scale", 1.0) * sum(xs) / len(xs)
