"""Streaming tier: median ``delta`` segment of the ``scan`` spans: the host
NumPy scan of the delta tier (rows persisted since the last compaction)
that follows the device's block scan in every cold read."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "scan", ("delta",))
