"""A GeoServer heat-map session: WMS tiles of ``grid`` x ``grid`` pixels on
the EPSG:4326 gridset (zoom z: tiles of 180 / 2^z degrees, 2^(z+1) across
and 2^z up, from the south-west corner), each a ``density`` of
``BBOX(geom, tile)`` over the tile, and beside them the row queries of the
points layer a map style switches to at street level. No time predicate:
the type has a Z2 index alone.

Requests come in rounds, as ``generators/notebook.py``'s. A round holds
the classes in the counts ``round`` gives, in a seeded order: ``tile-z<z>``
that many tiles of zoom z, ``z2`` that many row queries of a ``box_deg``
square. Zooms 0 and 1 take their level's 2 and 8 tiles in turn. From zoom
2 on a tile is the one that holds a point drawn N(0, ``jitter_deg``) round
a city chosen Zipf(``zipf_s``) by rank over the data's centres
(``generators/viewports.py``'s skew); a row query's box is centred on such
a point. A tile's corners are whole multiples of 180 / 2^z: exact in f32.
"""

import numpy as np

TILE_CLASS = "tile-z"
WHOLE_LEVELS = 2  # zooms below this are walked tile by tile, not drawn


def tile_size(z: int) -> float:
    return 180.0 / (1 << int(z))


def tile_at(z: int, x: float, y: float):
    """(i, j) of the zoom-z tile that holds the point."""
    s = tile_size(z)
    i = min(max(int((x + 180.0) // s), 0), (2 << z) - 1)
    j = min(max(int((y + 90.0) // s), 0), (1 << z) - 1)
    return i, j


def tile_request(z: int, i: int, j: int, grid: int) -> dict:
    s = tile_size(z)
    x0, y0 = -180.0 + i * s, -90.0 + j * s
    return {"op": "density", "klass": f"{TILE_CLASS}{z}", "box": [x0, y0, x0 + s, y0 + s],
            "grid": int(grid)}


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    size = sum(per_round.values())
    n_rounds = -(-n // size)
    cx, cy = np.asarray(ctx["cx"]), np.asarray(ctx["cy"])
    ranks = np.arange(1, len(cx) + 1, dtype=np.float64) ** -float(params["zipf_s"])
    grid, half = int(params["grid"]), float(params["box_deg"]) / 2
    drawn = n_rounds * size  # a point a request; zooms 0 and 1 leave theirs unused
    which = rng.choice(len(cx), drawn, p=ranks / ranks.sum())
    jit = rng.normal(0.0, float(params["jitter_deg"]), (drawn, 2))
    px = np.clip(cx[which] + jit[:, 0], -180.0 + half, 180.0 - half)
    py = np.clip(cy[which] + jit[:, 1], -90.0 + half, 90.0 - half)
    out, k = [], 0
    for r in range(n_rounds):
        one = []
        for klass, count in per_round.items():
            for c in range(count):
                x, y = float(px[k]), float(py[k])
                k += 1
                if not klass.startswith(TILE_CLASS):
                    one.append({"op": "query", "klass": klass,
                                "box": [x - half, y - half, x + half, y + half]})
                    continue
                z = int(klass[len(TILE_CLASS):])
                if z < WHOLE_LEVELS:
                    turn = (r * count + c) % ((2 << z) * (1 << z))
                    i, j = turn % (2 << z), turn // (2 << z)
                else:
                    i, j = tile_at(z, x, y)
                one.append(tile_request(z, i, j, grid))
        rng.shuffle(one)
        out.extend(one)
    return out[:n]
