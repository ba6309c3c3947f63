"""Entry points: median whole ``sort`` span (under ``decode``: the stable sort
of every matched row by the ``sort_by`` hint and the ``take`` of the page a
limit keeps) over the requests that have one: the sorted classes. A "newest
report" sorts its taxi's whole week to keep one row."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "sort", whole=True)
