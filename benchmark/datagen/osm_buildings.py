"""OSM-building-shaped rows from a seed: the closed ways tagged
``building=*`` of one country extract, as upstream's ``osm-ways`` converter
(``geomesa-convert-osm``; the program's port is ``geomesa_tpu/io/osm.py``)
makes them: an id, a few tags and a POLYGON that is NOT an axis-aligned
rectangle.

Where they lie. The extract's box is lon 6..15, lat 47..55 (Germany's).
``RURAL_SHARE`` of the rows (a coin each) are uniform over it; the rest
belong to ``N_TOWNS`` towns whose centres are uniform over the box less
half a degree and whose weights are Zipf(``TOWN_ZIPF``) by rank (the
heaviest 3.0% of the rows in towns). A town's footprints lie
N(centre, sigma_x, ``SIGMA_RATIO`` sigma_x), and sigma_x is set from the
town's own row count so that EVERY town's centre holds ``CENTRE_DENSITY``
footprints a square degree (Berlin's: 0.55M buildings on 890 km^2 is one
a 1,600 m^2, and a square degree at lat 51 is 7.8e9 m^2; assumed). What a
viewport at a town's centre answers then depends on the viewport's size
and not on which town was drawn, at any number of rows (a smaller table
has smaller towns, not thinner ones).

What a footprint is. One polygon, one ring, no hole, closed (the first
vertex again at the end), counter-clockwise, simple; drawn in metres in a
box of ``SIDES_M`` on each side, turned by an angle uniform over [0, pi)
and laid at its place in degrees (a metre is 1 / 110,540 deg of latitude
and 1 / (111,320 cos lat) of longitude). ``KINDS`` gives the shapes and
their shares: 70% quads (5 vertices with the closing one), 20% L-shapes
(7), 10% a rectangle with a notch (9), an L with a notch (11) or a cross
(13). No multipolygon (courtyards: 2-3% of OSM's buildings) and no hole:
assumed away. The coordinates are f64 and free (no lattice).

The tags. ``osm_id`` a permutation of the rows; ``building`` one of 12
values with OSM's skew (``yes`` 60%, ``house`` 15%, ...); ``name`` empty
for 95% of the rows and else one of 4,096 names of at most 16 characters;
``levels`` 1 to 12, most under 4; ``height`` 3.0 x levels + N(0, 0.5);
``dtg`` (the way's last edit) uniform over ``span_days``.

``make(config, n, seed)`` returns the ``Columns`` the store is loaded from
and the reference reads: the attribute columns (``attrs``, ``t``), the
vertices as one pool (``coords`` [V, 2] f64, footprint i owning
``coords[offsets[i]:offsets[i + 1]]``) and every footprint's exact f64
bounds (``bounds`` [n, 4]). No ``Geometry`` object is made for a row. A
schema with any other attribute is an error. Row i has feature id i.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from datagen.gdelt import parse_schema
from harness.data import DAY_MS

CHUNK_ROWS = 1 << 18
EXTRACT = (6.0, 47.0, 15.0, 55.0)  # lon0, lat0, lon1, lat1
N_TOWNS = 2048
TOWN_ZIPF = 0.7
RURAL_SHARE = 0.125
CENTRE_MARGIN = 0.5  # degrees between a town's centre and the extract's edge
SIGMA_RATIO = 0.667  # sigma_y over sigma_x
CENTRE_DENSITY = 3.0e6  # footprints a square degree at every town's centre
CONTEXT_TOWNS = 256  # the heaviest, which a request generator may know
SIDES_M = (8.0, 30.0)
M_PER_DEG_LAT = 110_540.0
M_PER_DEG_LON = 111_320.0  # at the equator
#: (vertices with the closing one, share of the rows), in this order
KINDS = ((5, 0.70), (7, 0.20), (9, 0.05), (11, 0.03), (13, 0.02))
BUILDING = (("yes", 0.60), ("house", 0.15), ("residential", 0.08), ("garage", 0.04),
            ("apartments", 0.03), ("detached", 0.025), ("industrial", 0.02),
            ("commercial", 0.015), ("shed", 0.015), ("retail", 0.01), ("roof", 0.01),
            ("school", 0.005))
NAMED_SHARE = 0.05
_STEMS = ("Rathaus", "Schule", "Kirche", "Bahnhof", "Halle", "Museum", "Klinik", "Hof",
          "Turm", "Markt", "Werk", "Lager", "Haus", "Bad", "Forum", "Kita")


def _names() -> np.ndarray:
    """4,096 names of at most 16 characters."""
    return np.array([f"{s} {k}" for s in _STEMS for k in range(1, 257)], dtype="<U16")


def town_weights() -> np.ndarray:
    w = np.arange(1, N_TOWNS + 1, dtype=np.float64) ** -TOWN_ZIPF
    return w / w.sum()


def town_sigmas(n: int):
    """(sigma_x, sigma_y) of every town at ``n`` rows: a Gaussian's peak is
    its rows over 2 pi sigma_x sigma_y, and that is ``CENTRE_DENSITY``."""
    rows = town_weights() * (1.0 - RURAL_SHARE) * n
    sx = np.sqrt(rows / (2.0 * np.pi * SIGMA_RATIO * CENTRE_DENSITY))
    return sx, SIGMA_RATIO * sx


def _col(m, *values):
    """[m, len(values)]: a ring's x or y, each entry a constant or an [m] array."""
    return np.stack([np.broadcast_to(np.asarray(v, np.float64), (m,)) for v in values], axis=1)


def unit_rings(vertices: int, rng, m: int) -> np.ndarray:
    """[m, vertices, 2]: m rings of one kind in the unit square, closed,
    counter-clockwise, simple; where the steps and notches lie is drawn."""
    if vertices == 5:  # a quad
        quad = np.array([[0.0, 0.0], [1.0, 0.0], [1.0, 1.0], [0.0, 1.0], [0.0, 0.0]])
        return np.broadcast_to(quad, (m, 5, 2))
    u = rng.random((m, 4))
    if vertices == 7:  # an L: the corner beyond (b, a) cut away
        a, b = 0.35 + 0.3 * u[:, 0], 0.35 + 0.3 * u[:, 1]
        return np.stack([_col(m, 0, 1, 1, b, b, 0, 0), _col(m, 0, 0, a, a, 1, 1, 0)], axis=2)
    if vertices == 9:  # a notch [c1, c2] x [d, 1] in the far side
        c1 = 0.25 + 0.2 * u[:, 0]
        c2, d = c1 + 0.2 + 0.1 * u[:, 1], 0.4 + 0.35 * u[:, 2]
        return np.stack([_col(m, 0, 1, 1, c2, c2, c1, c1, 0, 0),
                         _col(m, 0, 0, 1, 1, d, d, 1, 1, 0)], axis=2)
    if vertices == 11:  # the L, and a notch [n1, n2] x [0, d] in the near side, d < a
        a, b = 0.45 + 0.25 * u[:, 0], 0.45 + 0.25 * u[:, 1]
        n1 = 0.2 + 0.15 * u[:, 2]
        n2, d = n1 + 0.15 + 0.1 * u[:, 3], 0.15 + 0.2 * u[:, 0]
        return np.stack([_col(m, 0, n1, n1, n2, n2, 1, 1, b, b, 0, 0),
                         _col(m, 0, 0, d, d, 0, 0, a, a, 1, 1, 0)], axis=2)
    if vertices == 13:  # a cross: the bars [c1, c2] x [0, 1] and [0, 1] x [d1, d2]
        c1, c2 = 0.25 + 0.15 * u[:, 0], 0.6 + 0.15 * u[:, 1]
        d1, d2 = 0.25 + 0.15 * u[:, 2], 0.6 + 0.15 * u[:, 3]
        return np.stack([_col(m, c1, c2, c2, 1, 1, c2, c2, c1, c1, 0, 0, c1, c1),
                         _col(m, 0, 0, d1, d1, d2, d2, 1, 1, d2, d2, d1, d1, 0)], axis=2)
    raise ValueError(f"no footprint of {vertices} vertices")


class Columns:
    """The generator's columns (the module's docstring names them). Rows
    are drawn in chunks of CHUNK_ROWS, each from its own stream of the
    seed and written in place, on a few threads; the shapes' kinds are
    drawn first, for the whole table, because they place every chunk in
    the pool of vertices."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        names = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        if sorted(names) != sorted(("osm_id", "building", "name", "levels", "height")):
            raise KeyError(f"datagen/osm_buildings.py makes osm_id, building, name, levels and "
                           f"height, not {names}")
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        self.span_ms = int(config["span_days"]) * DAY_MS
        rng = np.random.default_rng([int(seed), 0])
        x0, y0, x1, y1 = EXTRACT
        self.cx = rng.uniform(x0 + CENTRE_MARGIN, x1 - CENTRE_MARGIN, N_TOWNS)
        self.cy = rng.uniform(y0 + CENTRE_MARGIN, y1 - CENTRE_MARGIN, N_TOWNS)
        self.weights = town_weights()
        self.sx, self.sy = town_sigmas(n)
        cuts = np.cumsum([share for _, share in KINDS])
        kind = np.searchsorted(cuts, np.random.default_rng([int(seed), 1]).random(n), "right")
        self.vertices = np.array([v for v, _ in KINDS], np.int8)[np.minimum(kind, len(KINDS) - 1)]
        self.offsets = np.zeros(n + 1, np.int64)
        np.cumsum(self.vertices, out=self.offsets[1:])
        self.coords = np.empty((int(self.offsets[-1]), 2))
        self.bounds = np.empty((n, 4))
        self.t = np.empty(n, np.int64)
        building = np.array([b for b, _ in BUILDING], dtype="<U11")
        building_cuts = np.cumsum([share for _, share in BUILDING])
        vocabulary = _names()
        dtypes = {"osm_id": np.int64, "building": building.dtype, "name": vocabulary.dtype,
                  "levels": np.int32, "height": np.float64}
        self.attrs = {a: np.empty(n, dtypes[a]) for a in names}  # the schema's order

        def chunk(job):
            c, lo = job
            hi = min(lo + CHUNK_ROWS, n)
            m = hi - lo
            rng = np.random.default_rng([int(seed), 2, int(c)])
            town = rng.choice(N_TOWNS, m, p=self.weights)
            px = self.cx[town] + rng.normal(0.0, 1.0, m) * self.sx[town]
            py = self.cy[town] + rng.normal(0.0, 1.0, m) * self.sy[town]
            rural = np.flatnonzero(rng.random(m) < RURAL_SHARE)
            px[rural] = rng.uniform(x0, x1, len(rural))
            py[rural] = rng.uniform(y0, y1, len(rural))
            sides = rng.uniform(*SIDES_M, (m, 2))
            turn = rng.uniform(0.0, np.pi, m)
            cos, sin = np.cos(turn), np.sin(turn)
            per_m = np.stack([1.0 / (M_PER_DEG_LON * np.cos(np.radians(py))),
                              np.full(m, 1.0 / M_PER_DEG_LAT)], axis=1)
            at = self.offsets[lo:hi]
            for v in np.unique(self.vertices[lo:hi]):
                rows = np.flatnonzero(self.vertices[lo:hi] == v)
                ring = (unit_rings(int(v), rng, len(rows)) - 0.5) * sides[rows, None, :]
                east = ring[..., 0] * cos[rows, None] - ring[..., 1] * sin[rows, None]
                north = ring[..., 0] * sin[rows, None] + ring[..., 1] * cos[rows, None]
                x = px[rows, None] + east * per_m[rows, None, 0]
                y = py[rows, None] + north * per_m[rows, None, 1]
                x[:, -1], y[:, -1] = x[:, 0], y[:, 0]  # closed to the last bit
                where = (at[rows, None] + np.arange(int(v))).ravel()
                self.coords[where, 0], self.coords[where, 1] = x.ravel(), y.ravel()
                self.bounds[lo + rows] = np.stack(
                    [x.min(axis=1), y.min(axis=1), x.max(axis=1), y.max(axis=1)], axis=1)
            self.t[lo:hi] = self.t0 + rng.integers(0, self.span_ms, m)
            levels = np.minimum(rng.geometric(0.45, m), 12).astype(np.int32)
            named = rng.random(m) < NAMED_SHARE
            drawn = {
                # a permutation: an odd multiplier coprime to any n that is not its multiple
                "osm_id": 100_000_000 + (np.arange(lo, hi, dtype=np.int64) * 2_654_435_761
                                         + int(seed)) % n,
                "building": building[np.minimum(
                    np.searchsorted(building_cuts, rng.random(m), "right"), len(building) - 1)],
                "name": np.where(named, vocabulary[rng.integers(0, len(vocabulary), m)], ""),
                "levels": levels,
                "height": 3.0 * levels + rng.normal(0.0, 0.5, m),
            }
            for a in names:
                self.attrs[a][lo:hi] = drawn[a]

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(chunk, enumerate(range(0, n, CHUNK_ROWS))))

    def __len__(self) -> int:
        return len(self.t)

    def context(self) -> dict:
        """What a request generator may know of the data: the heaviest
        towns' centres and sigmas, heaviest first."""
        k = CONTEXT_TOWNS
        return {"cx": [float(v) for v in self.cx[:k]], "cy": [float(v) for v in self.cy[:k]],
                "sx": [float(v) for v in self.sx[:k]], "sy": [float(v) for v in self.sy[:k]],
                "t0": self.t0, "span_ms": self.span_ms, "n_rows": len(self)}

    def ring(self, i: int) -> np.ndarray:
        return self.coords[int(self.offsets[i]):int(self.offsets[i + 1])]

    def row(self, i: int) -> dict:
        """Row i as every answer's witness row is brought to: the date as
        epoch millis, the polygon as its ring, a list of [x, y] with the
        closing vertex, the rest as Python values."""
        out = {self.dtg: int(self.t[i]), self.geom: self.ring(i).tolist()}
        out.update({a: c[i].item() for a, c in self.attrs.items()})
        return out


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
