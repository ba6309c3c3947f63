"""Tables / native tier: per ``query_many`` root the sum of its ``decode`` spans;
the median over the window's roots."""
from layer_metrics._segments import many_ms


def read(view):
    return many_ms(view, "decode")
