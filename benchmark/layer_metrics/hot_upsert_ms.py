"""Streaming tier: median whole ``hot.upsert`` of the ``write`` roots: the
batch applied to the hot tier (rows adopted, grid index, listeners)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "hot.upsert", roots=("write",), whole=True)
