"""Host runtime: the median, in milliseconds, of the hand-off probe's
samples taken inside the window: what a thread of the process waited to
hold the interpreter lock again after a native call of 10 ms that had
released it (``obs.trace.lock_probe``)."""
from layer_metrics._lock import probe


def read(view):
    got = probe(view)
    return None if got is None else got["p50_s"] * 1e3
