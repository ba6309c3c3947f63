"""Entry points: what the HTTP front end and the wire add. Median client
latency of the window's queries minus the median wall of the server's
root ``query`` spans over the same window (medians of the two sides, not
of pairs: a request carries nothing that would pair them)."""
from harness.stats import median


def read(view):
    walls = [s["dur_s"] * 1e3 for s in view["spans"]
             if s["name"] == "query" and s["parent"] is None]
    lat = view["client"]["query_ms"]
    if not walls or not lat:
        return None
    return median(lat) - median(walls)
