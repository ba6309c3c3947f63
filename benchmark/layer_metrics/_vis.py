"""Shared by the readers of row-level security (``gdelt-secured.analyst``):

- a span ``vis`` lies inside ``decode``'s ``post`` segment wherever a store
  opened with auths masked an answer that had rows (``QueryPlanner._post``;
  the served handler's per-request auths open the same span under the
  ``http`` root): ``rows`` in, ``kept`` out, ``labels`` the distinct labels
  evaluated;
- the root of a ``count`` and of a ``density`` carries ``vis_fallback`` where
  auths are set and the type has a label field: 1 where visibility alone
  took the aggregation's device path away, 0 where no device path was
  eligible anyway.

A program that writes neither (before PR 53), and a store without auths,
give every reader here nothing to read: None."""

from layer_metrics._segments import spans


def vis_spans(view):
    """The window's ``vis`` spans, whichever root they lie under."""
    return spans(view, "vis")


def roots(view):
    """The window's root spans."""
    return [s for s in spans(view) if s["parent"] is None]
