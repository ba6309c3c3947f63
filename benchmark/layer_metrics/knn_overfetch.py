"""Tables / native tier: rows the windows of the window's ``knn`` roots
returned (gathered with all their attributes) for each row answered:
``candidates`` over ``returned``, pooled. The window is four times the start
radius wide and a square; a round that misses is paid for again."""
from layer_metrics._process import pooled


def read(view):
    return pooled(view, "knn", "candidates", "returned")
