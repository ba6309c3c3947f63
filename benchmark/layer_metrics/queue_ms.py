"""Scheduler: median of the ``queue`` spans, admission to dispatch."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "queue", whole=True)
