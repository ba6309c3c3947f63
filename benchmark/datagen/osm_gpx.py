"""OSM-GPX-shaped rows from a seed: public GPS traces as geomesa-tools'
predefined ``osm-gpx`` type keeps them for a heat map, a time and a point
(``dtg:Date,*geom:Point:srid=4326``), queried without time.

Row i has feature id i and ``t`` ascends with it (the reference's ``cols.t``
contract), uniform over ``span_days``, made as ``datagen/gdelt.py`` makes
its times. Of the rows a tenth (a coin each) are uniform over lon +-180,
lat +-70; the rest are laid as tracks of 200 to 2,000 points round 2,048
city centres (uniform over lon +-160, lat -55..65, a track's city drawn by
weight, the weights Zipf(0.7) by rank: the heaviest city holds 3.3% of the
clustered rows): a track starts N(centre, 0.3 x 0.2 deg) and walks by
steps of N(0, 1e-4 deg).

Two things the configuration asks for, both found on the chip (PERF.md,
Findings, PR 33):

- **``data.coords: "f32"`` rounds every coordinate to the nearest f32**
  (under a metre, inside a GPS fix's error); ``"f64"``, the default,
  leaves the fixes free, as upstream stores them. The cell asks for the
  lattice for ONE reason: a new cell is run on the program before this PR
  too and has to read ``correct`` there, and that program counted a tile
  wrongly on free f64 rows (PERF.md section 7 (u): a tile's edges are cell
  boundaries of the Z2 curve, the scanned ranges were the f64 box's and
  the mask compares f32 columns, so a row half an f32 step beyond an edge,
  which the mask and the reference's f32 semantics keep, lay in a block
  that was not scanned: 2 to 22 rows of 10^7, ``density_sum_gap``). PR 33
  repairs the program (the ranges cover the box the mask keeps) and holds
  it to free f64 rows of this generator on the CPU
  (tests/test_heatmap_cell.py); the first benchmark PR after it sets the
  configuration to ``"f64"``.
- **The eastern hemisphere holds ``EAST_SHARE`` of the cities' weight**
  (cities are dealt to a side heaviest first; OSM's public traces are
  mostly Europe's and Asia's). A zoom-0 tile is a hemisphere: the heavier
  one outnumbers the bucket ladder and takes the whole-table shape, the
  lighter pads into the last bucket. The closed edge at lon 0 reaches one
  column of cells into the other side, which the range decomposition
  covers with its coarsest cells (a hundred row spans, some 130 blocks at
  2^27 rows), so a side that holds over 48.7% of the rows outnumbers the
  ladder too. With the sides drawn freely (49.2% and 50.1% west under two
  seeds of three) both tiles take the whole-table shape, and the 95th
  percentile of the cell's mix, which lies inside the zoom-0 class, reads
  98 ms where the third seed (56.5%) reads 50. So under this deal
  ``query_p95_ms`` is the tile that pads into the last bucket, under every
  seed; the whole-table tile is read by ``density_full_ms`` and carried
  by ``queries_per_s``.

**The bound that decides the cell.** The store's density grid is f32, exact
while a pixel holds at most 2^24 rows. The largest pixel any request of the
cell asks for is a square of the EPSG:4326 pyramid's zoom 0 (180 / 256 =
0.703125 deg), and every higher zoom's pixels lie inside one. ``make``
holds every such square under ``SQUARE_SHARE`` = 6.25% of the rows (2^23
of 2^27) by arithmetic on the centres' weights, before a row is drawn
(``square_bound``), and draws the centres again from the next sub-stream
of the seed until the bound holds. The six heaviest cities together are
10% of the clustered rows, so three of the heaviest have to reach one
square for a redraw: rare, and then one more draw of 2,048 points.

``make(config, n, seed)`` returns the ``Columns`` the store is loaded from
and the reference reads. A schema with any other attribute is an error.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from datagen.gdelt import parse_schema
from harness.data import DAY_MS

CHUNK_ROWS = 1 << 18
N_CITIES = 2048
CITY_ZIPF = 0.7
CITY_SIGMA = (0.3, 0.2)  # degrees, lon x lat: where a track starts round its centre
STEP_SIGMA = 1e-4  # degrees a point
TRACK_POINTS = (200, 2000)
UNIFORM_SHARE = 0.1
EAST_SHARE = 0.6  # of the cities' weight; with the uniform tenth, 59% of the rows
SQUARE_DEG = 180.0 / 256  # a pixel of a 256^2 tile at zoom 0
SQUARE_SHARE = 0.0625  # 2^23 of 2^27 rows
#: how far from its centre a city is counted to a square: three sigmas of
#: the start and ten of the longest walk (1e-4 x sqrt(2,000) = 0.0045 deg)
REACH = (3 * CITY_SIGMA[0] + 0.05, 3 * CITY_SIGMA[1] + 0.05)
#: clustered rows that start beyond three sigmas on either axis (1 - 0.9973^2),
#: all counted to the heaviest square
TAIL_SHARE = 0.0054


def city_weights() -> np.ndarray:
    w = np.arange(1, N_CITIES + 1, dtype=np.float64) ** -CITY_ZIPF
    return w / w.sum()


def square_bound(cx, cy, w) -> float:
    """An upper bound on the share of all rows that one zoom-0 square can
    hold: every city's whole weight counted to every square its reach
    touches, the tails of all cities and the uniform rows' share of a
    square on top, and a twentieth more for what the draw of the tracks
    moves."""
    nx, ny = int(round(360 / SQUARE_DEG)), int(round(180 / SQUARE_DEG))
    load = np.zeros((ny, nx))
    i0 = np.floor((np.asarray(cx) - REACH[0] + 180) / SQUARE_DEG).astype(int)
    i1 = np.floor((np.asarray(cx) + REACH[0] + 180) / SQUARE_DEG).astype(int)
    j0 = np.floor((np.asarray(cy) - REACH[1] + 90) / SQUARE_DEG).astype(int)
    j1 = np.floor((np.asarray(cy) + REACH[1] + 90) / SQUARE_DEG).astype(int)
    for k in range(len(w)):
        load[max(j0[k], 0):j1[k] + 1, max(i0[k], 0):i1[k] + 1] += w[k]
    clustered = (1 - UNIFORM_SHARE) * (float(load.max()) + TAIL_SHARE)
    uniform = UNIFORM_SHARE * SQUARE_DEG * SQUARE_DEG / (360.0 * 140.0)
    return 1.05 * (clustered + uniform)


def _east(w) -> np.ndarray:
    """Which cities lie east of Greenwich: dealt heaviest first to the
    side that is further under its share, so that the east holds
    ``EAST_SHARE`` of the weight to within the lightest city's."""
    east, got = np.zeros(len(w), bool), [0.0, 0.0]
    for k in range(len(w)):
        side = int(got[1] / EAST_SHARE <= got[0] / (1 - EAST_SHARE))
        east[k] = bool(side)
        got[side] += w[k]
    return east


def city_centres(seed: int):
    """(cx, cy, weights, draws): the first draw of the seed's centre
    streams whose ``square_bound`` is under ``SQUARE_SHARE``; heaviest
    city first."""
    w = city_weights()
    sign = np.where(_east(w), 1.0, -1.0)
    for attempt in range(64):
        rng = np.random.default_rng([int(seed), 1, attempt])
        cx, cy = sign * rng.uniform(0, 160, N_CITIES), rng.uniform(-55, 65, N_CITIES)
        if square_bound(cx, cy, w) <= SQUARE_SHARE:
            return cx, cy, w, attempt + 1
    raise RuntimeError("no draw of the city centres kept a zoom-0 square under the bound")


def _tracks(rng, n, cx, cy, w):
    """(x, y) of n points laid as whole tracks, the last one cut at n."""
    lo, hi = TRACK_POINTS
    lengths = rng.integers(lo, hi + 1, n // lo + 1)
    first = np.concatenate([[0], np.cumsum(lengths)])
    first = first[first < n]
    track = np.zeros(n, np.int32)
    track[first[1:]] = 1
    np.cumsum(track, out=track)
    city = rng.choice(len(w), len(first), p=w)
    out = []
    for centre, sigma in ((cx, CITY_SIGMA[0]), (cy, CITY_SIGMA[1])):
        walk = np.cumsum(rng.normal(0.0, STEP_SIGMA, n))
        start = centre[city] + rng.normal(0.0, sigma, len(first)) - walk[first]
        walk += start[track]
        out.append(walk)
    return out


class Columns:
    """The generator's columns, as ``datagen/gdelt.py``'s: ``x``, ``y``,
    ``t`` (ascending epoch millis) and no other attribute. Rows are drawn
    in chunks of CHUNK_ROWS, each from its own stream of the seed, on a few
    threads; a track lies inside one chunk."""

    def __init__(self, config: dict, n: int, seed: int):
        self.schema, self.dtg, self.geom = parse_schema(config["schema"])
        other = [a for a, _ in self.schema if a not in (self.dtg, self.geom)]
        if other:
            raise KeyError(f"datagen/osm_gpx.py makes a date and a point, not {other}")
        self.attrs = {}
        self.t0 = int(np.datetime64(config["data"]["t0"], "ms").astype(np.int64))
        self.span_ms = int(config["span_days"]) * DAY_MS
        coords = config["data"].get("coords", "f64")
        if coords not in ("f32", "f64"):
            raise KeyError(f"data.coords is 'f32' or 'f64', not {coords!r}")
        lattice = coords == "f32"
        self.cx, self.cy, self.weights, self.centre_draws = city_centres(seed)
        self.x, self.y = np.empty(n), np.empty(n)
        gaps = np.ones(n + 1)

        def chunk(job):
            c, lo = job
            hi = min(lo + CHUNK_ROWS, n)
            rng = np.random.default_rng([int(seed), 2, int(c)])
            x, y = _tracks(rng, hi - lo, self.cx, self.cy, self.weights)
            stray = np.flatnonzero(rng.random(hi - lo) < UNIFORM_SHARE)
            x[stray] = rng.uniform(-180, 180, len(stray))
            y[stray] = rng.uniform(-70, 70, len(stray))
            x, y = np.clip(x, -180, 180), np.clip(y, -90, 90)
            self.x[lo:hi] = x.astype(np.float32) if lattice else x
            self.y[lo:hi] = y.astype(np.float32) if lattice else y
            gaps[lo:hi] = rng.standard_exponential(hi - lo)

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(chunk, enumerate(range(0, n, CHUNK_ROWS))))
        # ascending times: the running sum of the gaps, scaled to the span.
        # Made in the gaps' own memory, a chunk at a time: at 2^27 rows a
        # column is 1 GB, and memory new to the process is the slow part
        np.cumsum(gaps, out=gaps)
        scale = self.span_ms / gaps[-1]
        self.t = gaps[:n].view(np.int64)
        for lo in range(0, n, CHUNK_ROWS):
            t = (gaps[lo:lo + CHUNK_ROWS][:n - lo] * scale).astype(np.int64)
            np.minimum(t, self.span_ms - 1, out=t)
            self.t[lo:lo + len(t)] = t + self.t0

    def __len__(self) -> int:
        return len(self.x)

    def context(self) -> dict:
        """What a request generator may know of the data: the city centres,
        heaviest first."""
        return {"cx": [float(v) for v in self.cx], "cy": [float(v) for v in self.cy],
                "t0": self.t0, "span_ms": self.span_ms, "n_rows": len(self)}

    def row(self, i: int) -> dict:
        return {self.dtg: int(self.t[i]), self.geom: [float(self.x[i]), float(self.y[i])]}


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
