"""Host runtime: the 95th percentile, in milliseconds, of the hand-off
probe's samples taken inside the window (``lock_handoff_ms`` is their
median)."""
from layer_metrics._lock import probe


def read(view):
    got = probe(view)
    return None if got is None else got["p95_s"] * 1e3
