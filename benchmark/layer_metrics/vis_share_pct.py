"""Planner: 100 x the summed wall of the window's ``vis`` spans over
the summed wall of its roots: the share of the program's time that is the
visibility stage. None where no request opened one."""
from layer_metrics._vis import roots, vis_spans


def read(view):
    vis = vis_spans(view)
    wall = sum(s["dur_s"] for s in roots(view))
    return 100.0 * sum(s["dur_s"] for s in vis) / wall if vis and wall else None
