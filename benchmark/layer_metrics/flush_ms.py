"""Streaming tier: median whole ``flush`` root: one persist of the hot
tier (parse, keys, sort in the pool, the commit, the watermark)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "flush", roots=("flush",), whole=True)
