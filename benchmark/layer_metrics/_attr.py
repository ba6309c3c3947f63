"""Shared by the readers of what PR 49 put on the program's spans for a
type with an attribute index (``tdrive.track-history``):

- a ``plan`` span names the index its plans chose (``index``: a strategy,
  ``"mixed"`` where a batch's members differ) and counts ``costed`` (the
  indexes that offered a plan, summed over its members), ``attr_offered``
  (the members an attribute index offered a plan for: their filter binds
  the attribute) and ``attr_won`` (those it won); its ``plan.probe`` and
  ``plan.decompose`` children name their own ``index``;
- a ``scan`` span over a table whose config clips to a value's row spans
  counts ``clip_in`` and ``clip_kept``;
- a ``sort`` span lies under ``decode`` where an answer is ordered or cut
  to a page (``rows``, ``kept``).

A program that writes none of these (before PR 49) gives every reader here
nothing to read: None."""

from layer_metrics._segments import spans

ROOTS = ("query", "query_many")


def plans(view):
    """The window's ``plan`` spans that say which index won."""
    return [s for s in spans(view, "plan", roots=ROOTS) if "index" in s["attrs"]]


def attr_plans(view):
    return [s for s in plans(view) if str(s["attrs"]["index"]).startswith("attr_")]


def attr_scans(view):
    """The window's ``scan`` spans over an attribute table."""
    return [s for s in spans(view, "scan", roots=ROOTS)
            if str(s["attrs"].get("index", "")).startswith("attr_")]
