"""Quantile ``q`` (the mean without ``q``) of the durations of the
timeline's spans named ``span`` whose attributes lie in ``where``:
``{attribute: [least, most]}``, either end ``null`` for open; a span
without the attribute is left out.  Times ``scale``."""

from . import aggregate


def reduce(metric, readings):
    def holds(attrs):
        for key, (lo, hi) in metric["where"].items():
            v = attrs.get(key)
            if v is None or (lo is not None and v < lo) \
                    or (hi is not None and v > hi):
                return False
        return True

    return aggregate([s["t1"] - s["t0"]
                      for spans in readings.get("iterations") or []
                      for s in spans
                      if s["name"] == metric["span"] and holds(s["attrs"])],
                     metric)
