"""Kernels: median of the ``wait`` segment of the ``agg`` spans
(``block_until_ready`` on an aggregation's result: the density kernel and
whatever queued before it, as the host sees them)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "agg", ("wait",))
