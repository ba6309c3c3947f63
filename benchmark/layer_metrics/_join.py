"""Shared by the readers of a broadcast join's spans (PR 41): the window's
``join`` roots and their children. ``sql/join.py``'s
``spatial_join_indexed`` opens root ``join`` (``members``, ``predicate``,
``pairs``) with the children ``join.plan`` (the members by the tier that
decides them: ``pip``, ``rast``, ``bbox_only``, ``host_raster``, ``empty``;
``edges``, ``ranges``, ``candidate_rows``), ``join.host`` (``points``,
``decided``, ``residue``), the table's ``dispatch`` and a ``scan`` a live
member, a ``join.refine`` a member that answered rows (``rows``,
``certain``, ``uncertain``) and ``join.assemble``. A program that opens no
such root (before PR 41) gives every reader here nothing to read: None."""

from harness.stats import median
from layer_metrics._segments import spans

ROOT = ("join",)
#: how a join decides a member, as ``join.plan`` counts them; they sum to its root's ``members``
#: (the readers' own copy of ``sql/join.py``'s ``_TIERS``: these files are also laid over a
#: parent that has no such name, and have to read nothing there, not fail to import)
TIERS = ("pip", "rast", "bbox_only", "host_raster", "empty")


def children(view, *names):
    """The spans called one of ``names`` directly under a ``join`` root."""
    roots = {s["id"] for s in spans(view, "join", roots=ROOT) if s["parent"] is None}
    return [s for name in names for s in spans(view, name, roots=ROOT) if s["parent"] in roots]


def per_root_ms(view, *names):
    """Per ``join`` root the summed wall of its direct children called one
    of ``names`` (0 for a root without one); the median over the window's
    roots, milliseconds. None where the window holds no ``join`` root."""
    sums = {s["id"]: 0.0 for s in spans(view, "join", roots=ROOT) if s["parent"] is None}
    for s in children(view, *names):
        sums[s["parent"]] += s["dur_s"] * 1e3
    return median(list(sums.values())) if sums else None


def planned(view):
    """(the ``join.plan`` spans that count the tiers, the members they planned)."""
    got = [s for s in children(view, "join.plan") if any(t in s["attrs"] for t in TIERS)]
    return got, sum(s["attrs"].get(t, 0) for s in got for t in TIERS)
