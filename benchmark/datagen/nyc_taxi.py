"""NYC-taxi-shaped trips and, beside them, the three polygon layers of New
York City that Pandey, Kipf, Neumann and Kemper join them with ("How Good
Are Modern Spatial Analytics Systems?", PVLDB 11(11), 2018: the join
query): boroughs (5 polygons, 662 vertices each on average), neighborhoods
(195; 30.6) and census blocks (39,184; 12.5). All from a seed; nothing is
read from TLC's or the city's files (no network here).

**The trips** are rows of upstream's ``nyctaxi`` type (geomesa-tools'
predefined SimpleFeatureType and converter, the TLC trip records) as far
as it is recalled: the pickup point is the default geometry and the pickup
time ``dtg``. Row i has feature id i and ``t`` ascends with it. Where the
pickups lie, as shares of all rows (``SHARES``):

- ``spots``: 256 hot spots inside the Manhattan-like strip (stations,
  hotels, corners), weights Zipf(``SPOT_ZIPF``) by rank, each N(centre,
  ``SPOT_SIGMA`` degrees);
- ``spread``: the rest of the strip's pickups, uniform across it and
  Beta(1.3, 1.8) along it from the southern tip (midtown and downtown are
  denser than the north);
- ``airports``: two spots in the eastern borough, N(centre, 0.006 deg);
- ``outer``: uniform over the city's box, so most of them in the four
  other boroughs;
- ``junk``: outside the box: half at exactly (0, 0), half over lon -80..
  -74.5, lat 35..45 (the TLC data's own unset and mislocated fixes): they
  pair with no polygon.

Coordinates are f64 and free (no lattice).

**The layers.** Each is a planar partition of the city's box ``CITY``:
neighbours share their edges vertex for vertex (the same f64 numbers),
no gap, no overlap, every ring simple, closed (the first vertex again at
the end) and counter-clockwise. A layer's "vertices" count the closing
one, as a WKT ring holds them; counts of polygons and of vertices are
exact under every seed (``VERTICES``): the numbers of interior vertices of
the shared sides are dealt from a multiset with that sum.

- ``blocks``: a 158 x 248 lattice whose interior nodes are moved by up to
  ``NODE_JITTER`` of a cell; a side between two nodes has 1 to 4 interior
  vertices (a box-edge side 2), displaced across it inside a wedge of
  slope ``2 * WIGGLE`` round the straight side, so no two sides cross.
  Polygon ``j * nx + i`` is cell (i, j), i west to east. The lattice's
  lines are not evenly spaced (``grid_lines``): half of its columns and of
  its rows lie over ``DENSE``, the strip's surroundings, as the city's
  zones are smaller in Manhattan: a block there is 0.0022 x 0.0015 deg,
  one elsewhere about twice that each way.
- ``neighborhoods``: the same on a 13 x 15 lattice, sides of 2 to 11
  interior vertices (a box-edge side 6); one over the strip is 0.029 x
  0.027 deg and holds under a sixth of the table, so none takes the
  join's whole-table route.
- ``boroughs``: polygon 0 is the Manhattan-like strip, an oblique
  quadrilateral from (-74.015, 40.70) to (-73.93, 40.875), 0.039 deg
  wide, which holds nine tenths of the pickups; polygons 1 to 4 (south,
  east, north, west) are what four cuts from its corners to the box's
  corners leave of the box. Sides of the strip: 500, 40, 500 and 40
  interior vertices; cuts 140 each; the box's sides 1, 1, 1 and 2: 3,310
  vertices in all. Single polygons (upstream's boroughs are multipolygons:
  assumed away).

``make(config, n, seed)`` returns the ``Columns`` the store is loaded from
and the reference reads: ``x``, ``y``, ``t``, ``attrs`` (the dropoff point
as an (x, y) pair) and ``layers`` {name: ``Layer``}. A schema with any
other attribute is an error.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from datagen.gdelt import parse_schema
from harness.data import DAY_MS

CHUNK_ROWS = 1 << 18
CITY = (-74.26, 40.49, -73.70, 40.92)  # lon0, lat0, lon1, lat1
#: (columns, rows, interior vertices of a shared side from..to, of a box-edge side)
LATTICES = {"blocks": (158, 248, 1, 4, 2), "neighborhoods": (13, 15, 2, 11, 6)}
#: a layer's vertices in all, the closing ones counted: polygons x the source's mean
VERTICES = {"blocks": 489_800, "neighborhoods": 5_967, "boroughs": 3_310}
POLYGONS = {"blocks": 39_184, "neighborhoods": 195, "boroughs": 5}
NODE_JITTER = 0.18  # of the smaller neighbouring cell, each way, on each axis
#: the part of the unit box (x from, y from, x to, y to) that half of a lattice's lines cover
DENSE = (0.36, 0.47, 0.67, 0.91)
WIGGLE = 0.04       # a side's interior vertices leave it by at most this share of its length
BOROUGH_WIGGLE = 0.02
#: the strip's axis in the unit box (south end, north end) and its half width there
STRIP_AXIS = ((0.4375, 0.488), (0.589, 0.895))
STRIP_HALF_WIDTH = 0.035
STRIP_SIDES = (40, 500, 40, 500)   # south end, east side, north end, west side
CUT_VERTICES = 140
BOX_SIDES = (1, 1, 1, 2)           # south, east, north, west
MANHATTAN = 0

N_SPOTS = 256
SPOT_ZIPF = 1.1
SPOT_SIGMA = (0.003, 0.0023)  # degrees of lon, of lat: about 250 m
AIRPORTS = ((-73.7781, 40.6413, 0.6), (-73.8740, 40.7769, 0.4))  # lon, lat, share of the two
AIRPORT_SIGMA = 0.006
SHARES = {"spots": 0.12, "spread": 0.78, "airports": 0.03, "outer": 0.065, "junk": 0.005}
ATTRIBUTES = ("medallion", "hack_license", "vendor_id", "rate_code", "store_and_fwd_flag",
              "dropoff_datetime", "passenger_count", "trip_time_in_secs", "trip_distance",
              "fare_amount", "total_amount", "dropoff_geom")


class Layer:
    """One polygon layer: ``coords`` [V, 2] f64, polygon k owning the
    closed ring ``coords[offsets[k]:offsets[k + 1]]``; ``bounds`` [n, 4]
    the rings' exact f64 bounds; ``lines`` (a lattice's unmoved lines in
    degrees, west to east and south to north) or None."""

    def __init__(self, name: str, coords, offsets, lines=None):
        self.name, self.coords, self.offsets, self.lines = name, coords, offsets, lines
        self.bounds = np.concatenate([np.minimum.reduceat(coords, offsets[:-1]),
                                      np.maximum.reduceat(coords, offsets[:-1])], axis=1)

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def ring(self, k: int) -> np.ndarray:
        return self.coords[int(self.offsets[k]):int(self.offsets[k + 1])]


def to_degrees(uv: np.ndarray) -> np.ndarray:
    """Points of the unit box as lon, lat of the city's box."""
    x0, y0, x1, y1 = CITY
    return np.stack([x0 + uv[..., 0] * (x1 - x0), y0 + uv[..., 1] * (y1 - y0)], axis=-1)


def grid_lines(n: int, lo: float, hi: float) -> np.ndarray:
    """The n + 1 lines of n cells over [0, 1]: ``n // 2`` even cells over
    [lo, hi], the others dealt to the two sides in proportion to their
    widths, even on each."""
    fine = n // 2
    below = min(max(int(round((n - fine) * lo / (lo + 1.0 - hi))), 1), n - fine - 1)
    return np.concatenate([np.linspace(0.0, lo, below + 1), np.linspace(lo, hi, fine + 1)[1:],
                           np.linspace(hi, 1.0, n - fine - below + 1)[1:]])


def deal_counts(rng, m: int, total: int, lo: int, hi: int) -> np.ndarray:
    """m whole numbers in lo..hi with the sum ``total`` exactly: drawn
    round the mean, then single steps up or down at drawn places."""
    mean = total / m
    if not lo <= mean <= hi:
        raise ValueError(f"{m} counts in {lo}..{hi} cannot sum to {total}")
    k = np.clip(np.rint(rng.normal(mean, (hi - lo) / 4.0, m)), lo, hi).astype(np.int64)
    while (gap := total - int(k.sum())) != 0:
        room = np.flatnonzero(k < hi) if gap > 0 else np.flatnonzero(k > lo)
        k[rng.choice(room, min(abs(gap), len(room)), replace=False)] += 1 if gap > 0 else -1
    return k


def _ragged(k: np.ndarray):
    """(owner, place) of every item of m runs of ``k[i]`` items laid end to end."""
    owner = np.repeat(np.arange(len(k)), k)
    return owner, np.arange(len(owner)) - np.repeat(np.cumsum(k) - k, k)


def side_vertices(rng, a: np.ndarray, b: np.ndarray, k: np.ndarray, wiggle):
    """The interior vertices of m sides a[i] -> b[i] (unit-box points),
    ``k[i]`` of them a side, as one pool [sum k, 2] in the sides' order,
    each side's from a to b. Vertex j of a side lies at t in ((j + 0.2) / k,
    (j + 0.8) / k) along it, so t ascends, and leaves the side across it by
    at most ``wiggle`` (one number, or one a side: 0 keeps a side straight)
    x 2 min(t, 1 - t) of its length: a slow wave of three sines and a
    quicker part no larger than half the vertices' spacing. Every side
    stays inside a wedge round its straight line and runs one way along
    it: it meets neither itself nor, for angles between sides over a few
    degrees, a neighbour."""
    owner, j = _ragged(k)
    kk = k[owner].astype(np.float64)
    wiggle = np.broadcast_to(np.asarray(wiggle, np.float64), k.shape)[owner]
    t = (j + 0.5 + rng.uniform(-0.3, 0.3, len(owner))) / kk
    freq = rng.uniform(1.0, 6.0, (len(k), 3))
    phase = rng.uniform(0.0, 2 * np.pi, (len(k), 3))
    slow = np.sin(2 * np.pi * freq[owner] * t[:, None] + phase[owner]).mean(axis=1)
    quick = np.minimum(0.4 * wiggle, 0.5 / kk) * rng.uniform(-1.0, 1.0, len(owner))
    across = 2.0 * np.minimum(t, 1.0 - t) * (0.6 * wiggle * slow + quick)
    d = (b - a)[owner]
    return a[owner] + t[:, None] * d + across[:, None] * np.stack([-d[:, 1], d[:, 0]], axis=1)


def _fill(dst, at, src, src_at, k, reverse: bool) -> None:
    """dst[at[c] + j] = src[src_at[c] + j] for j < k[c] (or the side's
    vertices last to first), for every c at once."""
    owner, j = _ragged(k)
    dst[at[owner] + j] = src[src_at[owner] + (k[owner] - 1 - j if reverse else j)]


def lattice_layer(name: str, rng) -> Layer:
    nx, ny, k_lo, k_hi, k_edge = LATTICES[name]
    n = nx * ny
    lx, ly = grid_lines(nx, DENSE[0], DENSE[2]), grid_lines(ny, DENSE[1], DENSE[3])
    gx, gy = np.meshgrid(lx, ly, indexing="ij")
    nodes = np.stack([gx, gy], axis=-1)  # [nx + 1, ny + 1, 2]
    room = np.stack(np.meshgrid(np.minimum(np.diff(lx)[:-1], np.diff(lx)[1:]),
                                np.minimum(np.diff(ly)[:-1], np.diff(ly)[1:]), indexing="ij"),
                    axis=-1)  # the smaller cell beside each interior node, on each axis
    nodes[1:-1, 1:-1] += rng.uniform(-NODE_JITTER, NODE_JITTER, (nx - 1, ny - 1, 2)) * room
    # a side's interior vertex count: west-east sides [nx, ny + 1], south-north [nx + 1, ny]
    kh = np.full((nx, ny + 1), k_edge, np.int64)
    kv = np.full((nx + 1, ny), k_edge, np.int64)
    n_h, n_v = nx * (ny - 1), (nx - 1) * ny
    # a shared side counts for both its polygons, a box-edge side for one
    shared = VERTICES[name] - 5 * n - k_edge * 2 * (nx + ny)
    if shared % 2:
        raise ValueError(f"{name}: the shared sides' vertices cannot be dealt evenly")
    dealt = deal_counts(rng, n_h + n_v, shared // 2, k_lo, k_hi)
    kh[:, 1:-1] = dealt[:n_h].reshape(nx, ny - 1)
    kv[1:-1, :] = dealt[n_h:].reshape(nx - 1, ny)
    # the box's own sides stay straight; all others wiggle
    h_wiggle = np.full((nx, ny + 1), WIGGLE)
    h_wiggle[:, [0, -1]] = 0.0
    v_wiggle = np.full((nx + 1, ny), WIGGLE)
    v_wiggle[[0, -1], :] = 0.0
    pools = [side_vertices(rng, nodes[:-1, :].reshape(-1, 2), nodes[1:, :].reshape(-1, 2),
                           kh.ravel(), h_wiggle.ravel()),
             side_vertices(rng, nodes[:, :-1].reshape(-1, 2), nodes[:, 1:].reshape(-1, 2),
                           kv.ravel(), v_wiggle.ravel())]
    h_at = (np.cumsum(kh) - kh.ravel()).reshape(nx, ny + 1)
    v_at = (np.cumsum(kv) - kv.ravel()).reshape(nx + 1, ny)
    # cell (i, j), counter-clockwise from its south-west node: south side, east, north, west
    i, j = np.meshgrid(np.arange(nx), np.arange(ny), indexing="xy")  # polygon j * nx + i
    i, j = i.ravel(), j.ravel()
    ks = [kh[i, j], kv[i + 1, j], kh[i, j + 1], kv[i, j]]
    size = 5 + ks[0] + ks[1] + ks[2] + ks[3]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(size, out=offsets[1:])
    if int(offsets[-1]) != VERTICES[name]:
        raise AssertionError(f"{name}: {int(offsets[-1])} vertices, not {VERTICES[name]}")
    uv = np.empty((int(offsets[-1]), 2))
    at = offsets[:-1].copy()
    corners = [nodes[i, j], nodes[i + 1, j], nodes[i + 1, j + 1], nodes[i, j + 1]]
    sides = [(pools[0], h_at[i, j], False), (pools[1], v_at[i + 1, j], False),
             (pools[0], h_at[i, j + 1], True), (pools[1], v_at[i, j], True)]
    for corner, (pool, src_at, reverse), k in zip(corners, sides, ks):
        uv[at] = corner
        _fill(uv, at + 1, pool, src_at, k, reverse)
        at = at + 1 + k
    uv[at] = corners[0]
    x0, y0, x1, y1 = CITY
    return Layer(name, to_degrees(uv), offsets,
                 lines=(x0 + lx * (x1 - x0), y0 + ly * (y1 - y0)))


def strip_corners(rng) -> np.ndarray:
    """The Manhattan-like strip's corners in the unit box, counter-
    clockwise from the south-west one; moved a little by the seed."""
    (sx, sy), (tx, ty) = STRIP_AXIS
    w = STRIP_HALF_WIDTH
    corners = np.array([[sx - w, sy], [sx + w, sy], [tx + w, ty], [tx - w, ty]])
    return corners + rng.uniform(-0.004, 0.004, (4, 2))


def borough_layer(rng, strip: np.ndarray) -> Layer:
    box = np.array([[0.0, 0.0], [1.0, 0.0], [1.0, 1.0], [0.0, 1.0]])
    k_strip, k_cut, k_box = np.array(STRIP_SIDES), np.full(4, CUT_VERTICES), np.array(BOX_SIDES)
    nxt = np.array([1, 2, 3, 0])
    strip_side = side_vertices(rng, strip, strip[nxt], k_strip, BOROUGH_WIGGLE)
    cut = side_vertices(rng, strip, box, k_cut, BOROUGH_WIGGLE)  # corner c of the strip to the box's
    t = [np.arange(1, k + 1) / (k + 1) for k in k_box]
    box_side = [box[c] + t[c][:, None] * (box[nxt[c]] - box[c]) for c in range(4)]
    s_at, c_at = np.cumsum(k_strip) - k_strip, np.cumsum(k_cut) - k_cut

    def strip_part(c, reverse=False):
        part = strip_side[s_at[c]:s_at[c] + k_strip[c]]
        return part[::-1] if reverse else part

    def cut_part(c, reverse=False):
        part = cut[c_at[c]:c_at[c] + k_cut[c]]
        return part[::-1] if reverse else part

    rings = [np.concatenate([np.concatenate([strip[c:c + 1], strip_part(c)]) for c in range(4)]
                            + [strip[:1]])]
    for c in range(4):  # south, east, north, west: box corner c, its side, the next corner, back
        d = nxt[c]
        rings.append(np.concatenate([
            box[c:c + 1], box_side[c], box[d:d + 1], cut_part(d, reverse=True),
            strip[d:d + 1], strip_part(c, reverse=True), strip[c:c + 1], cut_part(c),
            box[c:c + 1]]))
    offsets = np.zeros(len(rings) + 1, np.int64)
    np.cumsum([len(r) for r in rings], out=offsets[1:])
    if int(offsets[-1]) != VERTICES["boroughs"]:
        raise AssertionError(f"boroughs: {int(offsets[-1])} vertices, not {VERTICES['boroughs']}")
    return Layer("boroughs", to_degrees(np.concatenate(rings)), offsets)


def make_layers(seed: int):
    """({name: Layer}, the strip's corners in the unit box)."""
    strip = strip_corners(np.random.default_rng([int(seed), 10]))
    layers = {name: lattice_layer(name, np.random.default_rng([int(seed), 11 + k]))
              for k, name in enumerate(LATTICES)}
    layers["boroughs"] = borough_layer(np.random.default_rng([int(seed), 13]), strip)
    return layers, strip


def in_strip(strip: np.ndarray, a, b) -> np.ndarray:
    """Strip coordinates (a across, west to east; b along, south to north)
    as unit-box points."""
    a, b = np.asarray(a)[:, None], np.asarray(b)[:, None]
    south = strip[0] + a * (strip[1] - strip[0])
    north = strip[3] + a * (strip[2] - strip[3])
    return south + b * (north - south)


def spot_weights() -> np.ndarray:
    w = np.arange(1, N_SPOTS + 1, dtype=np.float64) ** -SPOT_ZIPF
    return w / w.sum()


class Columns:
    """The generator's columns (the module's docstring names them). Rows
    are drawn in chunks of CHUNK_ROWS, each from its own stream of the seed
    and written in place, on a few threads."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        names = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        if sorted(names) != sorted(ATTRIBUTES):
            raise KeyError(f"datagen/nyc_taxi.py makes {ATTRIBUTES}, not {names}")
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        self.span_ms = int(config["span_days"]) * DAY_MS
        self.layers, self.strip = make_layers(seed)
        rng = np.random.default_rng([int(seed), 0])
        centres = to_degrees(in_strip(self.strip, rng.uniform(0.15, 0.85, N_SPOTS),
                                      rng.uniform(0.03, 0.97, N_SPOTS)))
        self.cx, self.cy = centres[:, 0].copy(), centres[:, 1].copy()
        self.weights = spot_weights()
        self.x, self.y = np.empty(n), np.empty(n)
        gaps = np.ones(n + 1)
        dtypes = {"medallion": np.int32, "hack_license": np.int32, "vendor_id": "<U3",
                  "rate_code": np.int32, "store_and_fwd_flag": "<U1",
                  "dropoff_datetime": np.int64, "passenger_count": np.int32,
                  "trip_time_in_secs": np.int32, "trip_distance": np.float64,
                  "fare_amount": np.float64, "total_amount": np.float64}
        self.attrs = {a: (np.empty(n), np.empty(n)) if a == "dropoff_geom"
                      else np.empty(n, dtypes[a]) for a in names}  # the schema's order
        kinds = list(SHARES)
        cuts = np.cumsum([SHARES[k] for k in kinds])
        x0, y0, x1, y1 = CITY
        airports = np.array(AIRPORTS)

        def chunk(job):
            c, lo = job
            hi = min(lo + CHUNK_ROWS, n)
            m = hi - lo
            rng = np.random.default_rng([int(seed), 2, int(c)])
            kind = np.minimum(np.searchsorted(cuts, rng.random(m), "right"), len(kinds) - 1)
            x, y = np.empty(m), np.empty(m)
            for code, name in enumerate(kinds):
                rows = np.flatnonzero(kind == code)
                k = len(rows)
                if name == "spots":
                    s = rng.choice(N_SPOTS, k, p=self.weights)
                    x[rows] = self.cx[s] + rng.normal(0.0, SPOT_SIGMA[0], k)
                    y[rows] = self.cy[s] + rng.normal(0.0, SPOT_SIGMA[1], k)
                elif name == "spread":
                    p = to_degrees(in_strip(self.strip, rng.uniform(0.04, 0.96, k),
                                            rng.beta(1.3, 1.8, k)))
                    x[rows], y[rows] = p[:, 0], p[:, 1]
                elif name == "airports":
                    s = rng.choice(len(airports), k, p=airports[:, 2])
                    x[rows] = airports[s, 0] + rng.normal(0, AIRPORT_SIGMA, k)
                    y[rows] = airports[s, 1] + rng.normal(0, AIRPORT_SIGMA, k)
                elif name == "outer":
                    x[rows], y[rows] = rng.uniform(x0, x1, k), rng.uniform(y0, y1, k)
                else:  # junk: unset fixes at (0, 0), mislocated ones west and south of the box
                    unset = rng.random(k) < 0.5
                    x[rows] = np.where(unset, 0.0, rng.uniform(-80.0, -74.5, k))
                    y[rows] = np.where(unset, 0.0, rng.uniform(35.0, 45.0, k))
            self.x[lo:hi], self.y[lo:hi] = x, y
            gaps[lo:hi] = rng.standard_exponential(m)
            miles = np.round(np.minimum(rng.lognormal(0.6, 0.8, m), 60.0), 2)
            secs = np.maximum((miles * rng.uniform(150.0, 420.0, m)).astype(np.int32), 30)
            fare = np.round((2.5 + 2.5 * miles + secs / 120.0) * 2.0) / 2.0
            tip = np.where(rng.random(m) < 0.55, np.round(0.2 * fare, 2), 0.0)
            turn = rng.uniform(0.0, 2 * np.pi, m)
            drawn = {
                "medallion": 2013000001 + rng.integers(0, 13_437, m),
                "hack_license": 2013000001 + rng.integers(0, 40_000, m),
                "vendor_id": np.where(rng.random(m) < 0.5, "CMT", "VTS"),
                "rate_code": np.array([1, 2, 5, 3, 4], np.int32)[
                    np.minimum(np.searchsorted([0.95, 0.98, 0.99, 0.995], rng.random(m)), 4)],
                "store_and_fwd_flag": np.where(rng.random(m) < 0.02, "Y", "N"),
                "dropoff_datetime": secs.astype(np.int64) * 1000,  # the pickup time is added below
                "passenger_count": np.minimum(rng.geometric(0.6, m), 6).astype(np.int32),
                "trip_time_in_secs": secs,
                "trip_distance": miles,
                "fare_amount": fare,
                "total_amount": fare + 0.5 + tip,
            }
            for a in names:
                if a == "dropoff_geom":  # a mile is 0.019 deg of longitude here, 0.0145 of latitude
                    self.attrs[a][0][lo:hi] = x + miles * 0.019 * np.cos(turn)
                    self.attrs[a][1][lo:hi] = y + miles * 0.0145 * np.sin(turn)
                else:
                    self.attrs[a][lo:hi] = drawn[a]

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(chunk, enumerate(range(0, n, CHUNK_ROWS))))
        # ascending times: the running sum of the gaps, scaled to the span
        np.cumsum(gaps, out=gaps)
        t = (gaps[:-1] * (self.span_ms / gaps[-1])).astype(np.int64)
        np.minimum(t, self.span_ms - 1, out=t)
        self.t = self.t0 + t
        self.attrs["dropoff_datetime"] += self.t

    def __len__(self) -> int:
        return len(self.x)

    def context(self) -> dict:
        """What a request generator may know of the data: the hot spots'
        centres, heaviest first, and sigmas; each layer's polygon count,
        its lattice's lines, and (blocks) a few polygons of more than 16
        edges, which take another kernel variant."""
        layers = {}
        for name, layer in self.layers.items():
            edges = np.diff(layer.offsets) - 1
            layers[name] = {"polygons": len(layer),
                            "lines": layer.lines and [v.tolist() for v in layer.lines],
                            "over_16_edges": [int(k) for k in np.flatnonzero(edges > 16)[:4]]}
        return {"cx": [float(v) for v in self.cx], "cy": [float(v) for v in self.cy],
                "sx": SPOT_SIGMA[0], "sy": SPOT_SIGMA[1], "layers": layers,
                "manhattan": MANHATTAN, "t0": self.t0, "span_ms": self.span_ms,
                "n_rows": len(self)}

    def row(self, i: int) -> dict:
        """Row i as a witness row is brought to: dates as epoch millis,
        points as [x, y], the rest as Python values."""
        out = {self.dtg: int(self.t[i]), self.geom: [float(self.x[i]), float(self.y[i])]}
        for a, c in self.attrs.items():
            out[a] = [float(c[0][i]), float(c[1][i])] if isinstance(c, tuple) else c[i].item()
        return out


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
