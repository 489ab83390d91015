"""Mean duration of the spans named ``span`` (seconds), times
``scale``."""

from . import aggregate


def reduce(metric, readings):
    return aggregate(readings["spans"].get(metric["span"]),
                     {"scale": metric.get("scale", 1.0)})
