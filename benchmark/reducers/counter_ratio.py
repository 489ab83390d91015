"""One count of the window over another (``numerator`` /
``denominator``, names of the kind's counters), times ``scale``."""


def reduce(metric, readings):
    c = readings["counters"]
    num, den = c.get(metric["numerator"]), c.get(metric["denominator"])
    if num is None or not den:
        return None
    return metric.get("scale", 1.0) * num / den
