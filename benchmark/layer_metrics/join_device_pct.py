"""Planner: of the polygons the window's joins planned, the share the
device decides: 100 x ``pip`` + ``rast`` (the point-in-polygon and the
raster-interval tier) over all five tiers of the ``join.plan`` spans. The
rest are rectangles and polygons with neither stack (``bbox_only``: every
row refined on the host), the host's whole-table route (``host_raster``)
and members with nothing to scan (``empty``)."""
from layer_metrics._join import planned


def read(view):
    got, members = planned(view)
    on_device = sum(s["attrs"].get("pip", 0) + s["attrs"].get("rast", 0) for s in got)
    return 100.0 * on_device / members if members else None
