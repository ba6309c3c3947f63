"""Entry points: 100 x the summed wall of the window's roots over the
summed client latencies of its operations. Served, the roots are the
``http`` ones; embedded, ``query``, ``query_many``, ``count`` and
``density``. What is missing is outside the program: the wire and the
client served, the benchmark op's own strings and read-out embedded."""
from layer_metrics._segments import spans

EMBEDDED = ("query", "query_many", "count", "density")


def read(view):
    lat = view["client"]["query_ms"]
    roots = [s for s in spans(view) if s["parent"] is None]
    walls = [s["dur_s"] for s in roots if s["name"] == "http"]
    if not walls:
        walls = [s["dur_s"] for s in roots if s["name"] in EMBEDDED]
    if not walls or not lat:
        return None
    return 100.0 * sum(walls) * 1e3 / sum(lat)
