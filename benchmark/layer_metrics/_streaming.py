"""Shared by the readers of what a Lambda store puts inside the program's
spans (``streaming/store.py``, ``streaming/wal.py``, ``streaming/flush.py``,
``storage/delta.py``, the ingest half of ``serving/http.py``). A program
that opens no such span or marks no such segment (the parent of PR 30), or
a store with no hot tier, gives every reader here nothing: None."""

from harness.stats import median
from layer_metrics._segments import spans


def per_root_ms(view, names, root):
    """Per ``root`` trace the summed whole durations of its spans called
    one of ``names``; the median over the traces that have any, ms."""
    sums = {}
    for s in spans(view, roots=(root,)):
        if s["name"] in names:
            sums[s["trace"]] = sums.get(s["trace"], 0.0) + s["dur_s"] * 1e3
    return median(list(sums.values())) if sums else None
