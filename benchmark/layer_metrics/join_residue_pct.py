"""Tables / native tier: of the rows the device handed back to the
window's joins, the share it left uncertain and the host checked exactly:
100 x ``uncertain`` over ``rows`` of the ``join.refine`` spans (the f32
band of the point-in-polygon tier, the partial cells of a raster, every
row of a ``bbox_only`` member)."""
from layer_metrics._join import children


def read(view):
    got = [s["attrs"] for s in children(view, "join.refine") if "rows" in s["attrs"]]
    rows = sum(a["rows"] for a in got)
    return 100.0 * sum(a.get("uncertain", 0) for a in got) / rows if rows else None
