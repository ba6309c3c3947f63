"""Tables / native tier: median self time of the ``decode`` spans (the
result gather and the exact refinement of boundary rows)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "decode")
