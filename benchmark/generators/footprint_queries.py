"""A planning or insurance GIS over a layer of building footprints: pans
from street to borough scale (``BBOX`` viewports, as a WFS GetFeature or a
vector tile asks them) and, one request in three, "which buildings does
this outline touch" (``INTERSECTS`` with a district, catchment or flood
outline). No time predicate: the type has an XZ2 index alone.

Requests come in rounds, as ``generators/notebook.py``'s: a round holds
the classes in the counts ``round`` gives, dealt into a seeded order with
``harness.data.balanced``, so every round of every seed asks the same
multiset of sizes.

  view-<w>  ``BBOX`` of width w degrees, height ``height_ratio`` x w
  <name>    a class of ``polygons``: ``INTERSECTS`` with a ring of
            ``vertices`` vertices at sorted uniform angles round the
            centre, each at ``radius`` (a factor drawn uniformly from that
            range, a vertex) of an ellipse with ``semi_axes_deg``: a star
            round its centre, so simple, and not convex

A request's centre is a town drawn Zipf(``zipf_s``) by rank over the
data's heaviest towns (the context's ``cx``, ``cy``), offset by
N(0, ``offset_sigmas`` x that town's sigma) on each axis: inside the
town's core, where the data holds the same density for every town.
"""

import numpy as np

from harness.data import balanced

VIEW = "view-"


def view_request(klass: str, x: float, y: float, w: float, h: float) -> dict:
    return {"op": "query_extents", "klass": klass,
            "box": [x - w / 2, y - h / 2, x + w / 2, y + h / 2]}


def ring_request(klass: str, x: float, y: float, angles, factors, semi_axes) -> dict:
    a, b = (float(v) for v in semi_axes)
    ring = [[x + a * f * float(np.cos(t)), y + b * f * float(np.sin(t))]
            for t, f in zip(angles, factors)]
    xs, ys = [p[0] for p in ring], [p[1] for p in ring]
    return {"op": "query_extents", "klass": klass, "ring": ring,
            "box": [min(xs), min(ys), max(xs), max(ys)]}


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    classes = [k for k, count in per_round.items() for _ in range(count)]
    n_rounds = -(-n // len(classes))
    cx, cy = np.asarray(ctx["cx"]), np.asarray(ctx["cy"])
    sx, sy = np.asarray(ctx["sx"]), np.asarray(ctx["sy"])
    ranks = np.arange(1, len(cx) + 1, dtype=np.float64) ** -float(params["zipf_s"])
    drawn = n_rounds * len(classes)
    town = rng.choice(len(cx), drawn, p=ranks / ranks.sum())
    off = rng.normal(0.0, float(params["offset_sigmas"]), (drawn, 2))
    px, py = cx[town] + off[:, 0] * sx[town], cy[town] + off[:, 1] * sy[town]
    ratio, (f_lo, f_hi) = float(params["height_ratio"]), params["radius"]
    out = []
    for r in range(n_rounds):
        for klass in balanced(rng, classes, len(classes)):
            klass = str(klass)
            x, y = float(px[len(out)]), float(py[len(out)])
            if klass.startswith(VIEW):
                w = float(klass[len(VIEW):])
                out.append(view_request(klass, x, y, w, w * ratio))
                continue
            poly = params["polygons"][klass]
            k = int(poly["vertices"])
            out.append(ring_request(klass, x, y, np.sort(rng.uniform(0.0, 2 * np.pi, k)),
                                    rng.uniform(f_lo, f_hi, k), poly["semi_axes_deg"]))
    return out[:n]
