"""A warm-up ladder for a store of extents: ``generators/ladder.py``'s
rungs as ``query_extents`` requests (``ladder`` emits ``"op": "query"``,
whose answer reader takes a point column or a scalar one). For each class
of ``classes`` a request at every width that grows by ``ratio`` from
``width_from`` to ``width_to`` degrees, height ``height_ratio`` x the
width, centred on the data's heaviest towns in turn: ``view`` a ``BBOX``,
any other class of ``polygons`` an ``INTERSECTS`` with a regular ring of
that many vertices inscribed in the box. A scan kernel's variant is keyed
by the bucket its candidate-block count pads into (powers of two), and a
box's blocks grow with its area: with ``ratio`` under the square root of
two no bucket between the smallest and the largest rung is skipped. Nothing
is drawn: ``rng`` and ``n`` are ignored.
"""

import numpy as np

from generators.footprint_queries import ring_request, view_request


def generate(params, rng, n, ctx):
    widths, w = [], float(params["width_from"])
    while w <= float(params["width_to"]):
        widths.append(w)
        w *= float(params["ratio"])
    ratio = float(params["height_ratio"])
    out, k = [], 0
    for klass in params["classes"]:
        for w in widths:
            x, y = float(ctx["cx"][k % len(ctx["cx"])]), float(ctx["cy"][k % len(ctx["cy"])])
            k += 1
            if klass == "view":
                out.append(view_request("view", x, y, w, w * ratio))
                continue
            v = int(params["polygons"][klass])
            out.append(ring_request(klass, x, y, 2 * np.pi * np.arange(v) / v, np.ones(v),
                                    (w / 2, w * ratio / 2)))
    return out
