"""An analyst's or a dashboard back end's "pickups per zone": broadcast
spatial joins of a few polygons of one of the city's layers with the
indexed trips, ``contains``, no time predicate (TLC's own taxi-zone
reports are this query; Pandey et al.'s join query asks the whole layer,
a request here a patch of it).

Requests come in rounds, as ``generators/notebook.py``'s: a round holds
the classes in the counts ``round`` gives, dealt into a seeded order with
``harness.data.balanced``, so every round of every seed asks the same
multiset of sizes. A class is ``classes[name]`` = {``layer``, ``patch``}:

  patch [w, h]  the w x h patch of the layer's lattice round a centre (the
                patch whose middle line is nearest to it, kept inside the
                lattice): ``blocks-16`` is [4, 4] of ``blocks``,
                ``nbhd-4`` [2, 2] of ``neighborhoods``, which holds the
                centre
  patch "one"   one polygon of a layer without a lattice: ``boro-1`` asks
                the Manhattan-like borough and the others in turn (the
                round's ``boro-1`` requests go Manhattan, then the next of
                the others, round after round)

A patch's centre is a hot spot drawn Zipf(``zipf_s``) by rank over the
data's hot spots (the context's ``cx``, ``cy``), offset by N(0,
``offset_sigmas`` x the spots' sigma) on each axis. ``subset`` holds the
polygons' indices in their layer, ascending.
"""

import numpy as np

from harness.data import balanced


def join_request(klass: str, layer: str, subset, predicate: str) -> dict:
    return {"op": "join", "klass": klass, "layer": layer, "predicate": predicate,
            "subset": sorted(int(k) for k in subset)}


def _first(lines, at: float, k: int) -> int:
    """Where k cells in a row start so that ``at`` lies in their middle:
    an even k round the nearest line, an odd k round the cell that holds
    it; kept inside the lattice."""
    lines = np.asarray(lines)
    if k % 2:
        mid = int(np.searchsorted(lines, at, "right")) - 1
    else:
        mid = int(np.argmin(np.abs(lines - at)))
    return int(np.clip(mid - k // 2, 0, len(lines) - 1 - k))


def patch_round(ctx, layer: str, x: float, y: float, w: int, h: int) -> list:
    """The w x h patch of ``layer``'s lattice with (x, y) in its middle
    (by the lattice's unmoved lines), kept inside the lattice: polygon
    indices, ascending."""
    xs, ys = ctx["layers"][layer]["lines"]
    i0, j0, nx = _first(xs, x, w), _first(ys, y, h), len(xs) - 1
    return [j * nx + i for j in range(j0, j0 + h) for i in range(i0, i0 + w)]


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    classes = [k for k, count in per_round.items() for _ in range(count)]
    n_rounds = -(-n // len(classes))
    cx, cy = np.asarray(ctx["cx"]), np.asarray(ctx["cy"])
    ranks = np.arange(1, len(cx) + 1, dtype=np.float64) ** -float(params["zipf_s"])
    drawn = n_rounds * len(classes)
    spot = rng.choice(len(cx), drawn, p=ranks / ranks.sum())
    off = rng.normal(0.0, float(params["offset_sigmas"]), (drawn, 2))
    px, py = cx[spot] + off[:, 0] * ctx["sx"], cy[spot] + off[:, 1] * ctx["sy"]
    predicate = params["predicate"]
    others = {spec["layer"]: [k for k in range(ctx["layers"][spec["layer"]]["polygons"])
                              if k != ctx["manhattan"]]
              for spec in params["classes"].values() if spec["patch"] == "one"}
    out, turn = [], {}
    for r in range(n_rounds):
        whole = 0  # "one" requests met in this round
        for klass in balanced(rng, classes, len(classes)):
            klass = str(klass)
            spec = params["classes"][klass]
            layer = spec["layer"]
            if spec["patch"] == "one":
                if whole == 0:
                    subset = [ctx["manhattan"]]
                else:
                    subset = [others[layer][turn.get(klass, 0) % len(others[layer])]]
                    turn[klass] = turn.get(klass, 0) + 1
                whole += 1
            else:
                w, h = spec["patch"]
                subset = patch_round(ctx, layer, float(px[len(out)]), float(py[len(out)]),
                                     int(w), int(h))
            out.append(join_request(klass, layer, subset, predicate))
    return out[:n]
