"""Tables / native tier: median of the ``refine`` and ``post`` segments
of the ``decode`` spans (the exact filter on boundary and residual rows,
then visibility, sort, limit and projection)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "decode", ("refine", "post"))
