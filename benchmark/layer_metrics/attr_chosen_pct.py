"""Planner: of the plans whose filter binds the indexed attribute (an
attribute index offered a plan: ``attr_offered``, a ``query_many``'s members
each count), the share the attribute index won (``attr_won``). A guard on the
cost-based decider: 76 of a round's 78 in ``tdrive.track-history`` (the two
``fleet-256-area`` are a z index's), 97.4."""
from layer_metrics._attr import plans


def read(view):
    got = [s["attrs"] for s in plans(view) if "attr_offered" in s["attrs"]]
    offered = sum(a["attr_offered"] for a in got)
    return 100.0 * sum(a.get("attr_won", 0) for a in got) / offered if offered else None
