"""Tables / native tier: what the device's bbox mask over-selects: of the
candidates it hands the host, the share the exact intersects keeps: 100 x
``refine_hits`` over ``candidates``, summed over the ``decode`` spans
that count the refinement's tiers. None where no span counts them."""
from layer_metrics._refine import tiers


def read(view):
    got = [a for a in tiers(view) if "refine_hits" in a]
    candidates = sum(a.get("candidates", 0) for a in got)
    return 100.0 * sum(a["refine_hits"] for a in got) / candidates if candidates else None
