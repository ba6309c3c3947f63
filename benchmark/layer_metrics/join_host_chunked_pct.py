"""Tables / native tier: of the points the window's broad routes
classified, the share that went through the chunked pass (PR 42:
``sql/join.py`` walks the table ``filter.raster.CLASSIFY_CHUNK`` points
at a time, so the pass's temporaries are a chunk's and not the table's):
100 x ``chunked`` over ``points``, summed over the ``join.host`` spans
that count ``chunked``. None where no such span counts it (a program
before PR 42, whose pass is one whole-table sweep), or the window holds
no broad member."""
from layer_metrics._join import children


def read(view):
    got = [s["attrs"] for s in children(view, "join.host") if "chunked" in s["attrs"]]
    points = sum(a["points"] for a in got)
    return 100.0 * sum(a["chunked"] for a in got) / points if points else None
