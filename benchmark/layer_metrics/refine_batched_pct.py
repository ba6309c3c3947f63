"""Tables / native tier: of the geometries the exact tier decided, the
share that ``geo.intersects_rows`` decided in its one batched pass over
the column's arrays and not by ``geo.intersects`` a geometry (points,
multipoints, a query without rings): 100 x ``refine_batched`` over
``refine_exact``, summed over the ``decode`` spans that count the tiers.
None where no span counts ``refine_batched`` (a program before PR 40), or
no geometry reached the exact tier."""
from layer_metrics._refine import tiers


def read(view):
    got = [a for a in tiers(view) if "refine_batched" in a]
    tested = sum(a["refine_exact"] for a in got)
    return 100.0 * sum(a["refine_batched"] for a in got) / tested if tested else None
