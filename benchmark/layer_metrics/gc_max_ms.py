"""Host runtime: the longest single collection that ended inside the
window, milliseconds; 0 where none took a millisecond (the record keeps
shorter ones as sums only)."""
from layer_metrics._stalls import totals


def read(view):
    tot = totals(*view["perf_window"])
    if tot is None:
        return None
    return 1e3 * max(g["max_s"] for g in tot["gc"].values())
