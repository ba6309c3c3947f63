"""Planner: per ``tube`` root the whole wall of its ``tube.bins`` span (the
track cut into time bins, each bin's box and window computed, and the
query's filter made of them: a ``Slices`` carrier's two arrays since PR 48, a
loop over the bins building an ``And(BBox, During)`` each before); the median
over the window's roots, milliseconds."""
from layer_metrics._process import tube_ms


def read(view):
    return tube_ms(view, own=("tube.bins",))
