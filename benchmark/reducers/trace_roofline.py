"""Share (%) of its roofline that the traced work reached: the least
time the chip could take for it — the larger of ``work[flops]`` over
peak FLOP/s and ``work[bytes]`` over peak bytes/s — over the summed
device time of the trace events of ``line`` (``ops`` or ``modules``)
whose names match ``pattern``.  Nothing matched, or no work counted:
nothing to report (never 0)."""

from ..lib import xplane


def reduce(metric, readings):
    trace, peaks, w = readings.get("trace"), readings["peaks"], \
        readings["work"]
    if trace is None or peaks is None:
        return None
    took = xplane.pattern_seconds(trace, metric["pattern"], metric["line"])
    least = max(w.get(metric.get("flops", ""), 0) / peaks["flops"],
                w.get(metric.get("bytes", ""), 0) / peaks["bytes_per_s"])
    if took <= 0 or least <= 0:
        return None
    return 100.0 * least / took
