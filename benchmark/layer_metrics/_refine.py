"""Shared by the readers of the extent refinement's tiers (PR 39): the
``decode`` spans of the window that count them. ``filter/predicates.py``
writes ``refine_rect``, ``refine_accept``, ``refine_exact`` (candidates
decided by rectangle algebra, by the vertex accept tier, by the
per-geometry exact test; on a single spatial predicate they sum to the
span's ``candidates``) and ``refine_exact_s`` (wall seconds in the exact
tier's loop) on the active span of an intersects over a packed geometry
column. A program that counts none of them (before PR 39), or a store of
points, gives every reader here nothing to read: None."""
from layer_metrics._segments import spans


def tiers(view):
    """The attrs of the ``decode`` spans that carry ``refine_exact``."""
    return [s["attrs"] for s in spans(view, "decode") if "refine_exact" in s["attrs"]]
