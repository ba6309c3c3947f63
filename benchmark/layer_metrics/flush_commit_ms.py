"""Streaming tier: median whole ``flush.commit``: the one atomic publish
of a flush (``DataStore.fold_upsert``: the delta tier's append, or the fold)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "flush.commit", roots=("flush",), whole=True)
