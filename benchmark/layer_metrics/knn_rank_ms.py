"""Tables / native tier: per ``knn`` root the summed wall of its ``decode``
spans (the gather of every attribute of every row a window returned, and the
exact refinement) and of its ``knn.rank`` spans (haversine over those rows,
partition, sort, ``take`` of the k); the median over the roots, milliseconds."""
from layer_metrics._process import knn_ms


def read(view):
    return knn_ms(view, ("decode", "knn.rank"))
