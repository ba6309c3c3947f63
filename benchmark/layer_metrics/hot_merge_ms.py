"""Streaming tier: per served read, ``hot`` (the hot tier queried, the
live-id shadow taken) plus ``merge`` (shadow mask, concat, id dedup) under
its ``http`` root; the median over the window's reads."""
from layer_metrics._streaming import per_root_ms


def read(view):
    return per_root_ms(view, ("hot", "merge"), "http")
