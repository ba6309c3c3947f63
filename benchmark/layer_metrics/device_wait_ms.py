"""Kernels: median of the ``wait`` segment of the ``scan`` spans that
pull (``block_until_ready`` on the result planes: the device and its
queue, as the host sees them; of a fused group one member waits)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "scan", ("wait",))
