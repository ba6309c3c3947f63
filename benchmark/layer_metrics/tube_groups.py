"""Planner: the branches the planner cut a tube's query into, per ``tube``
root (``groups``: the scans of a time-sliced union, each sixteen slices or
fewer with their own stretch of the track; 0 where the query stayed one
scan), pooled over the window's roots that carry the counter, over their
number. A program whose tube is always one scan (before PR 47) writes no
``groups``: nothing to read, None."""
from layer_metrics._process import roots


def read(view):
    got = [s["attrs"]["groups"] for s in roots(view, "tube") if "groups" in s["attrs"]]
    return sum(got) / len(got) if got else None
