"""Tables / native tier: median wall, in milliseconds, of the ``density``
roots whose ``dispatch`` took the whole-table shape (``full`` >= 1): the
tile that outnumbers the bucket ladder, which a mix's percentile may not
reach (in ``osm-gpx.heatmap`` it is a thirty-second of the requests, past
the 95th). A program that does not count ``full`` (before PR 33), or a
window without such a tile, gives None."""
from harness.stats import median
from layer_metrics._density import dispatches
from layer_metrics._segments import spans


def read(view):
    took = {s["trace"] for s in dispatches(view, "full") if s["attrs"]["full"] >= 1}
    got = [s["dur_s"] * 1e3 for s in spans(view, "density", roots=("density",))
           if s["parent"] is None and s["trace"] in took]
    return median(got) if got else None
