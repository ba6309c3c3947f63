"""Streaming tier, host clock: the 95th percentile of the time from a batch
sent to its acknowledgement wholly read, over the sound acknowledgements
of the window (``clients/ingest_http.py``). None where the mix has no
writer."""
from harness.stats import percentile


def read(view):
    acks = view["client"].get("ack_ms")
    return percentile(acks, 95.0) if acks else None
