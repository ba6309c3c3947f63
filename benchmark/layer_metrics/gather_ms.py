"""Tables / native tier: median of the ``gather`` segment of the
``decode`` spans (``store.gather``: the candidates' rows, all columns)."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "decode", ("gather",))
