"""Tables / native tier: median wall, in milliseconds, of the
``join.host`` spans: the broad route, where the host classifies EVERY
point of the table against a polygon's raster and ray-casts the residue,
over the ``join`` roots that hold one (in ``nyc-taxi.zone-join`` the
Manhattan-like borough's request, one in sixteen). None where no root of
the window took it."""
from harness.stats import median
from layer_metrics._join import children


def read(view):
    got = [s["dur_s"] * 1e3 for s in children(view, "join.host")]
    return median(got) if got else None
