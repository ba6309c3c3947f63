"""Tables / native tier: what the exact tier costs a geometry, in
microseconds: ``refine_exact_s`` over ``refine_exact``, both summed over
the ``decode`` spans that count the tiers. None where no geometry reached
the exact tier, or no span counts them."""
from layer_metrics._refine import tiers


def read(view):
    got = tiers(view)
    tested = sum(a["refine_exact"] for a in got)
    return 1e6 * sum(a.get("refine_exact_s", 0.0) for a in got) / tested if tested else None
