"""Planner: per ``knn`` root the summed wall of the planner's ``plan`` spans
(one a pending point a round: a window filter parsed, extracted, decomposed
and costed on its own); the median over the window's roots, milliseconds."""
from layer_metrics._process import knn_ms


def read(view):
    return knn_ms(view, ("plan",))
