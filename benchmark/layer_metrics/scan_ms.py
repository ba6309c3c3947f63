"""Tables: median self time of the ``scan`` spans (dispatch wait, device,
pull and bit decode, until the tracing issue splits them)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "scan")
