"""Tables / native tier, mesh stores: per ``query_many`` root the ``deal``
segments of its ``dispatch`` spans (candidate blocks split per device, the
[D, M] id, query-id and polygon-leg arrays filled); the median over roots."""
from layer_metrics._mesh import many_segment_ms


def read(view):
    return many_segment_ms(view, "dispatch", "deal")
