"""Tables / native tier, mesh stores: per ``query_many`` root the ``merge``
segments of its members' ``scan`` spans (the devices' decoded rows joined
and sorted into one ascending answer); the median over roots."""
from layer_metrics._mesh import many_segment_ms


def read(view):
    return many_segment_ms(view, "scan", "merge")
