"""Tables / native tier: median of the ``pull`` segment of the ``scan``
spans that pull (``device_get`` of the result planes, after the wait)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "scan", ("pull",))
