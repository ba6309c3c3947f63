"""Planner: of the rows that reached the visibility stage (every
candidate the filter kept, all columns gathered), the share the caller may
read: 100 x ``kept`` over ``rows``, pooled over the window's ``vis`` spans.
The rest were gathered to be thrown away."""
from layer_metrics._vis import vis_spans


def read(view):
    got = [s["attrs"] for s in vis_spans(view) if "rows" in s["attrs"]]
    rows = sum(a["rows"] for a in got)
    return 100.0 * sum(a.get("kept", 0) for a in got) / rows if rows else None
