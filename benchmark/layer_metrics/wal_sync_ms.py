"""Streaming tier: median whole ``wal.sync`` under the ``write`` roots: the
buffer written out and the fsync, or the wait for the producer whose
fsync covered this record too (``fsync`` = 0 on the span)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "wal.sync", roots=("write",), whole=True)
