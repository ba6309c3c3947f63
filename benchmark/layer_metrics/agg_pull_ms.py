"""Tables / native tier: median of the ``pull`` segment of the ``agg``
spans (``device_get`` of the grid, after the wait)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "agg", ("pull",))
