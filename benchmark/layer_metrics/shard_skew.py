"""Tables / native tier, mesh stores: the fullest device's candidate blocks
over an even share, ``blocks_max x devices / blocks`` of every ``dispatch``
span that dealt any (any root; a span of several dispatches sums both), the
median: 1.0 is an even deal, ``devices`` all candidates on one chip."""
from harness.stats import median
from layer_metrics._segments import spans


def read(view):
    got = [a["blocks_max"] * a["devices"] / a["blocks"]
           for a in (s["attrs"] for s in spans(view, "dispatch"))
           if a.get("blocks", 0) > 0 and "blocks_max" in a and "devices" in a]
    return median(got) if got else None
