"""Scheduler: median of the whole ``dispatch`` spans of the ``query``
roots. Served, that is the fused batch's staging (``submit_many``: range
pruning, parameter stacks, the jitted call), which every member waits
through; embedded, one query's own pruning and kernel call."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "dispatch", whole=True)
