"""Tables / native tier: what a pair of an answer costs to put in its
place, in nanoseconds: the window's summed ``join.assemble`` wall over
the summed ``pairs`` of the ``join`` roots those spans hang under (the
span and the root's ``pairs`` are PR 41's, so a program before PR 52,
whose assembly sorts, fills and concatenates a member at a time, reads
here too). None where no root of the window assembled a pair."""
from layer_metrics._join import ROOT, children
from layer_metrics._segments import spans


def read(view):
    got = children(view, "join.assemble")
    pairs = {s["id"]: s["attrs"].get("pairs", 0) for s in spans(view, "join", roots=ROOT)}
    total = sum(pairs[s["parent"]] for s in got)
    return 1e9 * sum(s["dur_s"] for s in got) / total if total else None
