"""Shared by the readers of what a mesh table puts inside the program's
spans (``parallel/dtable.py``): one segment of one span name, summed under
each ``query_many`` root. A program whose mesh table marks no such segment
(the parent of PR 26), or a store that is no mesh, gives nothing: None."""

from harness.stats import median
from layer_metrics._segments import spans


def many_segment_ms(view, span_name, segment):
    """Per ``query_many`` root the wall inside ``segment`` of every
    ``span_name`` span of its tree (the staging's ``dispatch`` and the ones
    nested in it; every member's ``scan``); the median over the window's
    roots that carry the segment at all, milliseconds."""
    sums = {}
    for s in spans(view, span_name, roots=("query_many",)):
        segs = s["attrs"].get("segments") or {}
        if segment in segs:
            sums[s["trace"]] = sums.get(s["trace"], 0.0) + segs[segment] * 1e3
    return median(list(sums.values())) if sums else None
