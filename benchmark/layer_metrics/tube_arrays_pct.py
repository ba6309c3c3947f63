"""Planner: of the window's ``tube`` roots that carry the counter, the share
whose ``arrays`` is 1: the query's ``plan`` span counted ``slice_rows``, so
the tube's slices reached the indexes as the rows of two arrays and not as an
object a slice. A program that carries slices as objects only (before PR 48)
writes no ``arrays``: nothing to read, None."""
from layer_metrics._process import roots


def read(view):
    got = [s["attrs"]["arrays"] for s in roots(view, "tube") if "arrays" in s["attrs"]]
    return 100.0 * sum(got) / len(got) if got else None
