"""Entry points: per POST, ``ingest.parse`` (the GeoJSON / Arrow body to
columns) plus ``ingest.rows`` (columns to the hot tier's row dicts) under
its ``http`` root; the median over the window's posts."""
from layer_metrics._streaming import per_root_ms


def read(view):
    return per_root_ms(view, ("ingest.parse", "ingest.rows"), "http")
