"""An analyst's notebook: one caller, big answers, every read operation.

Requests come in rounds. A round holds the classes in the counts that
``round`` gives (so any run, however far it gets, has met the same mix),
in a seeded order:

  z3          bbox AND DURING, boxes and windows from ``box_queries`` x
              ``time_windows`` (1 to 40 degrees, 6 h to 2 weeks)
  z2          bbox alone, boxes of ``z2_widths_deg``
  pip, raster 6- and 24-edge polygons inscribed in such boxes (at most
              12 x 6 degrees): the device point-in-polygon tier and the
              raster-approximated tier
  count       exact count of a z3 filter
  density     ``grid`` x ``grid`` heat map of a z3 filter over its box
  query_many  ``members`` z3 filters in one call
"""

import math

from harness.data import box_queries, time_windows


def _ngon(box, k):
    x0, y0, x1, y1 = box
    cx, cy = (x0 + x1) / 2, (y0 + y1) / 2
    rx, ry = min(x1 - x0, 12.0) / 2, min(y1 - y0, 6.0) / 2
    return [[round(cx + rx * math.cos(2 * math.pi * i / k), 4),
             round(cy + ry * math.sin(2 * math.pi * i / k), 4)] for i in range(k)]


def _ring_box(ring):
    xs, ys = [p[0] for p in ring], [p[1] for p in ring]
    return [min(xs), min(ys), max(xs), max(ys)]


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    n_rounds = -(-n // sum(per_round.values()))
    t0, span = int(ctx["t0"]), int(ctx["span_ms"])
    members = int(params["members"])

    def z3(k, widths):
        boxes = box_queries(rng, k, widths)
        wins = time_windows(rng, k, t0, span, params["hours"])
        return [{"box": list(b), "win": list(w)} for b, w in zip(boxes, wins)]

    # every class's sizes are dealt over the whole run, then cut in rounds
    pools = {
        "z3": [dict(q, op="query", klass="z3")
               for q in z3(n_rounds * per_round.get("z3", 0), params["widths_deg"])],
        "z2": [{"op": "query", "klass": "z2", "box": list(b)} for b in
               box_queries(rng, n_rounds * per_round.get("z2", 0), params["z2_widths_deg"])],
        "count": [dict(q, op="count", klass="count")
                  for q in z3(n_rounds * per_round.get("count", 0), params["widths_deg"])],
        "density": [dict(q, op="density", klass="density", grid=int(params["grid"]))
                    for q in z3(n_rounds * per_round.get("density", 0),
                                params["density_widths_deg"])],
        "query_many": [
            {"op": "query_many", "klass": "query_many",
             "members": z3(members, params["widths_deg"])}
            for _ in range(n_rounds * per_round.get("query_many", 0))],
    }
    for klass, k in (("pip", 6), ("raster", 24)):
        pools[klass] = []
        for b in box_queries(rng, n_rounds * per_round.get(klass, 0), params["poly_widths_deg"]):
            ring = _ngon(b, k)
            pools[klass].append({"op": "query", "klass": klass, "box": _ring_box(ring),
                                 "ring": ring})
    out = []
    for r in range(n_rounds):
        one = []
        for klass, k in per_round.items():
            one.extend(pools[klass][r * k:(r + 1) * k])
        rng.shuffle(one)
        out.extend(one)
    return out[:n]
