"""Quantile ``q`` of the spans named ``span`` (seconds), times
``scale`` (nearest rank on the sorted durations)."""

from . import aggregate


def reduce(metric, readings):
    return aggregate(readings["spans"].get(metric["span"]), metric)
