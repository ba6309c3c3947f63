"""A taxi regulator's or a fleet operator's track viewer and its analysts'
notebooks over a week of a city's taxi reports: every request says WHICH
taxis, most say WHEN, two say WHERE (``ops/query_attr.py`` has a request's
keys). BerlinMOD/R's object-identity range queries in their point reading,
and the "where is it now" of every fleet screen.

Requests come in rounds, as ``generators/vessel_proximity.py``'s: a round
holds the classes in the counts ``round`` gives, dealt into a seeded order
with ``harness.data.balanced``, so every round of every seed asks the same
multiset. A class is ``classes[name]``:

  ``ids``      how many taxis the filter names: 1 is ``taxiId = 'X'``, more
               ``taxiId IN (...)``, drawn without replacement
  ``from``     where each of the class's requests in a round draws its
               taxis, in turn: ``"fleet"`` (uniform over the fleet; the
               default) or ``"heavy"`` (uniform over the data's taxis with
               the most rows)
  ``hours``    the length of ``dtg DURING``: 24 is one of the week's
               calendar days, fewer a window that starts on a whole hour,
               both uniform over the week; none, no time predicate
  ``box_deg``  [width, height] of a ``BBOX`` whose centre is a hot spot
               drawn Zipf by the data's weights, moved by up to a quarter
               of the box
  ``sort``, ``limit``
               the ``sort_by`` hint (``"dtg"``, ``"-dtg"``) and the limit
  ``many``     ONE ``query_many`` of that many one-taxi filters (distinct
               taxis, one window): its members count as queries
"""

import numpy as np

from harness.data import balanced

HOUR_MS = 3_600_000


def window(rng, ctx, hours):
    if hours is None:
        return None
    hours = int(hours)
    whole = int(ctx["span_ms"]) // HOUR_MS
    first = int(rng.integers(0, whole // 24)) * 24 if hours == 24 else int(
        rng.integers(0, whole - hours + 1))
    lo = int(ctx["t0"]) + first * HOUR_MS
    return [lo, lo + hours * HOUR_MS]


def box_at(ctx, spot: int, size, offset=(0.0, 0.0)):
    w, h = float(size[0]), float(size[1])
    x, y = ctx["cx"][spot] + offset[0] * w, ctx["cy"][spot] + offset[1] * h
    return [x - w / 2, y - h / 2, x + w / 2, y + h / 2]


def taxis(rng, ctx, count: int, where: str = "fleet"):
    """``count`` distinct taxi ids, as strings."""
    if where not in ("fleet", "heavy"):
        raise ValueError(f"taxis are drawn from 'fleet' or 'heavy', not {where!r}")
    pool = np.asarray(ctx["heavy"]) if where == "heavy" else int(ctx["fleet"])
    picked = rng.choice(pool, int(count), replace=False)
    return [str(int(v) + (where == "fleet")) for v in picked]


def request(klass, ids, win=None, box=None, sort=None, limit=None) -> dict:
    req = {"op": "query_attr", "klass": klass, "ids": list(ids)}
    for key, value in (("win", win), ("box", box), ("sort", sort), ("limit", limit)):
        if value is not None:
            req[key] = value
    return req


def one(klass, spec, rng, ctx, turn: int) -> dict:
    """One request of class ``klass``, the ``turn``-th of its round."""
    where = spec.get("from", ["fleet"])
    where = where[turn % len(where)]
    win = window(rng, ctx, spec.get("hours"))
    if "many" in spec:
        members = [request(klass, [x], win) for x in taxis(rng, ctx, spec["many"], where)]
        return {"op": "query_attr", "klass": klass, "members": members}
    box = None
    if "box_deg" in spec:
        w = np.asarray(ctx["w"])
        spot = int(rng.choice(len(w), p=w / w.sum()))
        box = box_at(ctx, spot, spec["box_deg"], rng.uniform(-0.25, 0.25, 2))
    return request(klass, taxis(rng, ctx, spec.get("ids", 1), where), win, box,
                   spec.get("sort"), spec.get("limit"))


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    classes = [k for k, count in per_round.items() for _ in range(count)]
    out = []
    for _ in range(-(-n // len(classes))):
        turn = dict.fromkeys(per_round, 0)
        for klass in balanced(rng, classes, len(classes)):
            klass = str(klass)
            out.append(one(klass, params["classes"][klass], rng, ctx, turn[klass]))
            turn[klass] += 1
    return out[:n]
