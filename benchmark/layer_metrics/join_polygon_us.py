"""Planner: wall microseconds of ``join.plan`` a polygon: the spans'
seconds summed over the window's ``join`` roots, over the polygons they
planned (a span's ``pip`` + ``rast`` + ``bbox_only`` + ``host_raster`` +
``empty``, which is its root's ``members``)."""
from layer_metrics._join import planned


def read(view):
    got, members = planned(view)
    return 1e6 * sum(s["dur_s"] for s in got) / members if members else None
