"""Planner: median wall, in milliseconds, of the whole ``join.plan`` span
of the window's ``join`` roots: a filter, a ``scan_config`` (the raster
with it) and the broad test for every polygon of a request, in a Python
loop."""
from layer_metrics._join import per_root_ms


def read(view):
    return per_root_ms(view, "join.plan")
