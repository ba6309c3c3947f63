"""Scheduler: median of the ``batch.wait`` spans, a member's wait from
the fused dispatch to its own turn in the dispatcher's pull loop, behind
the gathers of the members before it."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "batch.wait", whole=True)
