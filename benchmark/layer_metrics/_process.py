"""Shared by the readers of the two processes' spans (PR 46): the window's
``knn`` and ``tube`` roots and what lies under them.

``process/knn.py`` opens ONE root ``knn`` a ``knn_search`` / ``knn_many``
call (``members``, ``k``, ``rounds``, ``windows``, ``candidates``,
``returned``, ``short``) over ``knn.estimate`` (``probes``) and a
``knn.round`` a round (``pending``, ``radius_max_m``), which holds the
planner's ``plan`` a member, the one ``dispatch`` (a member that dispatches
alone nests its own inside), and a ``scan``, a ``decode`` and a ``knn.rank``
a member. ``process/tube.py`` opens ONE root ``tube`` a ``tube_select``
(``waypoints``, ``bins``, ``buffer_m``, ``boxes``, ``windows``, ``ranges``,
``candidates``, ``rows``, ``kept``) over ``tube.bins`` and ``tube.refine``;
its one query is ``DataStore.query``'s own ``query`` root, whose root span
carries ``tube_trace`` = the ``tube`` root's trace: ``linked`` finds it.

A program that opens no such root (before PR 46) gives every reader here
nothing to read: None."""

from harness.stats import median
from layer_metrics._segments import spans


def roots(view, name):
    """The window's root spans called ``name``, each once."""
    return [s for s in spans(view, name, roots=(name,)) if s["parent"] is None]


def knn_ms(view, names, under=None):
    """Per ``knn`` root the summed wall of its spans called one of
    ``names`` (only those whose parent is a span called ``under`` where
    that is given: the round's own ``dispatch``, not the one a lone member
    nests inside it); the median over the window's roots, milliseconds."""
    sums = {s["trace"]: 0.0 for s in roots(view, "knn")}
    if not sums:
        return None
    inside = spans(view, roots=("knn",))
    parents = {s["id"] for s in inside if s["name"] == under} if under else None
    for s in inside:
        if s["name"] in names and (parents is None or s["parent"] in parents):
            sums[s["trace"]] += s["dur_s"] * 1e3
    return median(list(sums.values()))


def linked(view):
    """{a ``query`` trace: the ``tube`` trace that asked it}."""
    tubes = {s["trace"] for s in roots(view, "tube")}
    return {s["trace"]: s["attrs"]["tube_trace"] for s in roots(view, "query")
            if s["attrs"].get("tube_trace") in tubes}


def tube_ms(view, inner=(), own=()):
    """Per ``tube`` root the summed wall of the spans called one of
    ``inner`` directly under its linked ``query`` root and of its own
    children called one of ``own``; the median over the window's roots,
    milliseconds."""
    sums = {s["trace"]: 0.0 for s in roots(view, "tube")}
    if not sums:
        return None
    asked = linked(view)
    tops = {s["id"]: asked[s["trace"]] for s in roots(view, "query") if s["trace"] in asked}
    for s in spans(view, roots=("query",)):
        if s["name"] in inner and s["parent"] in tops:
            sums[tops[s["parent"]]] += s["dur_s"] * 1e3
    for s in spans(view, roots=("tube",)):
        if s["name"] in own and s["parent"] is not None:
            sums[s["trace"]] += s["dur_s"] * 1e3
    return median(list(sums.values()))


def pooled(view, root, over, under, scale=1.0):
    """``scale`` x the window's sum of the roots' attribute ``over`` above
    the sum of ``under``, over the roots that carry both; None where none
    does or the lower sum is 0."""
    got = [s["attrs"] for s in roots(view, root)
           if over in s["attrs"] and under in s["attrs"]]
    low = sum(a[under] for a in got)
    return scale * sum(a[over] for a in got) / low if low else None
