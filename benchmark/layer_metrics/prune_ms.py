"""Tables / native tier: median of the ``prune`` segment of the ``dispatch``
spans (any root): a dispatch's candidate row spans, candidate blocks,
padding and parameter stacks, everything before its jitted call."""
from layer_metrics._segments import segment_ms


def read(view):
    return segment_ms(view, "dispatch", ("prune",))
