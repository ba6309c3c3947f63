"""Entry points: the median of the latencies whose 95th percentile is the
end-to-end metric; the steadier statistic beside the tail."""
from harness.stats import median


def read(view):
    lat = view["client"]["query_ms"]
    return median(lat) if lat else None
