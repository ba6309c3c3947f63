"""Entry points: 100 x the summed wall of the window's ``knn`` and ``tube``
roots over the summed client latencies of its operations: what is missing is
outside the program (the benchmark op's own filter and answer).
``span_coverage_pct``'s arithmetic for the roots it does not name."""
from layer_metrics._process import roots


def read(view):
    lat = view["client"]["query_ms"]
    walls = [s["dur_s"] for name in ("knn", "tube") for s in roots(view, name)]
    if not walls or not lat:
        return None
    return 100.0 * sum(walls) * 1e3 / sum(lat)
