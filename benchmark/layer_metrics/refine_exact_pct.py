"""Tables / native tier: of the candidates the device's bbox mask hands
the host, the share that neither rectangle algebra nor the vertex accept
tier decides and the per-geometry exact test has to: 100 x
``refine_exact`` over ``candidates``, summed over the ``decode`` spans
that count the tiers. None where no span counts them."""
from layer_metrics._refine import tiers


def read(view):
    got = tiers(view)
    candidates = sum(a.get("candidates", 0) for a in got)
    return 100.0 * sum(a["refine_exact"] for a in got) / candidates if candidates else None
