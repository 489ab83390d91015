"""Device idle seconds under named host spans, a run of a program: the
summed seconds of the traced tail's ``idle_gaps`` entries
(``readings["trace"]``: idle time by the innermost host span, the
twenty largest names) whose names are in ``spans``, over the number of
executed programs whose name matches ``pattern``
(``xplane.pattern_count``), times ``scale``.  The device's clock is not
the host's (``lib/xplane.py``), so of neighbouring short spans only the
SUM holds: name them together.  None where nothing matched."""

from ..lib import xplane


def reduce(metric, readings):
    trace = readings.get("trace")
    if trace is None:
        return None
    runs = xplane.pattern_count(trace, metric["pattern"])
    found = [secs for name, secs in trace.get("idle_gaps") or []
             if name in metric["spans"]]
    if not runs or not found:
        return None
    return metric.get("scale", 1.0) * sum(found) / runs
