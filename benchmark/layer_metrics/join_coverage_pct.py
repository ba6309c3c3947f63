"""Entry points: 100 x the summed wall of the window's ``join`` roots over
the summed client latencies of its operations: what is missing is outside
the program (the benchmark op's own take of the polygons from their
layer). ``span_coverage_pct``'s arithmetic for a root it does not name."""
from layer_metrics._segments import spans


def read(view):
    lat = view["client"]["query_ms"]
    walls = [s["dur_s"] for s in spans(view, "join", roots=("join",)) if s["parent"] is None]
    if not walls or not lat:
        return None
    return 100.0 * sum(walls) * 1e3 / sum(lat)
