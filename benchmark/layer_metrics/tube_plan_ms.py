"""Planner: per ``tube`` root the whole wall of its query's ``plan`` span (an
``Or`` of up to 256 ``And(BBox, During)`` parsed, extracted and decomposed
as one z3 scan); the median over the window's roots, milliseconds."""
from layer_metrics._process import tube_ms


def read(view):
    return tube_ms(view, inner=("plan",))
