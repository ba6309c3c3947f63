"""Streaming tier: median self time of the ``wal.append`` spans of the
``write`` roots (the record encoded and buffered; the ``wal.sync`` it
calls under ``sync=always`` is a child, read by ``wal_sync_ms``)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "wal.append", roots=("write",))
