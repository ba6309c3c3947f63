"""The plain NumPy reference for the broadcast spatial join: which points
lie in the interior of which polygons, decided on the f64 coordinates the
generator made. It imports nothing of the program and is handed nothing
the program made (``datagen/nyc_taxi.py``'s columns: ``x``, ``y`` and a
``Layer``'s ``coords``, ``offsets``, ``bounds``).

``join_pairs(cols, layer, subset)`` gives, for the polygons ``subset`` of a
layer, the pairs (polygon's index in the layer, point id) sorted by both.
A polygon's points: those inside its f64 bounds in y (two binary searches
over the points sorted by y, made once a data set: ``by_y``), then the
even-odd crossing rule EDGE BY EDGE over the points of each edge's y-band
alone (two more binary searches an edge), so that a ring of a thousand
edges over 2^24 points costs a few passes over the points and not a
thousand. An edge from its lower end (xl, yl) to its upper (xu, yu)
counts for a point with yl <= py < yu and px < xl + (py - yl) (xu - xl) /
(yu - yl). A point ON the ring is in no interior: px equal to that
crossing abscissa, py on a level edge between its ends, or the point an
edge's upper end (its lower end gives the abscissa xl exactly).

Departures from JTS (``RelateOp``, ``contains``), in how a sign is
computed and not in what is asked: JTS locates a point by a robust
orientation test (double-double where the f64 sign is in doubt); this
file compares the f64 crossing abscissa with px, so a point within a
rounding error of an edge (1e-16 of the coordinates' size; no seeded point
comes that close) could be decided otherwise. Single polygons of one
ring, the only kind here: no hole, no multipolygon. THE PROGRAM departs
from JTS where this file does not: ``geo.contains`` / ``sql.join``'s
``contains`` is the even-odd parity alone, which gives a point exactly on
a shared edge to one of the two polygons (docs/joins.md);
tests/test_join_cell.py holds both readings side by side.
"""

from __future__ import annotations

import numpy as np


class PointsByY:
    """The points sorted by y once: ``ys`` ascending, ``xs`` and ``ids``
    in that order."""

    def __init__(self, x, y):
        self.ids = np.argsort(y, kind="stable").astype(np.int64)
        self.xs, self.ys = np.asarray(x)[self.ids], np.asarray(y)[self.ids]


def by_y(cols) -> PointsByY:
    """``cols``' points sorted by y, made at the first call and kept on the
    columns (a window's checks share one sort of 2^24 numbers)."""
    got = getattr(cols, "_reference_by_y", None)
    if got is None:
        got = cols._reference_by_y = PointsByY(cols.x, cols.y)
    return got


def ring_interior(points: PointsByY, ring: np.ndarray) -> np.ndarray:
    """Ascending ids of the points strictly inside the closed ``ring``
    [V, 2] (the first vertex again at the end)."""
    ring = np.asarray(ring, np.float64)
    lo = int(np.searchsorted(points.ys, ring[:, 1].min(), "left"))
    hi = int(np.searchsorted(points.ys, ring[:, 1].max(), "right"))
    xs, ys = points.xs[lo:hi], points.ys[lo:hi]
    odd = np.zeros(hi - lo, bool)
    on = np.zeros(hi - lo, bool)
    for (xa, ya), (xb, yb) in zip(ring[:-1].tolist(), ring[1:].tolist()):
        if ya == yb:  # a level edge crosses no ray; its own points are on the ring
            a, b = np.searchsorted(ys, ya, "left"), np.searchsorted(ys, ya, "right")
            on[a:b] |= (min(xa, xb) <= xs[a:b]) & (xs[a:b] <= max(xa, xb))
            continue
        (xl, yl), (xu, yu) = ((xa, ya), (xb, yb)) if ya < yb else ((xb, yb), (xa, ya))
        a, b = np.searchsorted(ys, yl, "left"), np.searchsorted(ys, yu, "left")
        cross = xl + (ys[a:b] - yl) * (xu - xl) / (yu - yl)
        odd[a:b] ^= xs[a:b] < cross
        on[a:b] |= xs[a:b] == cross
        c = np.searchsorted(ys, yu, "right")
        on[b:c] |= xs[b:c] == xu
    return np.sort(points.ids[lo:hi][odd & ~on])


def join_pairs(cols, layer: str, subset) -> "tuple[np.ndarray, np.ndarray]":
    """(polygon index in the layer, point id), sorted by (index, id), for
    the polygons ``subset`` of ``cols.layers[layer]``."""
    lay, points = cols.layers[layer], by_y(cols)
    ks, ids = [np.zeros(0, np.int64)], [np.zeros(0, np.int64)]
    for k in sorted(int(k) for k in subset):
        inside = ring_interior(points, lay.ring(k))
        ks.append(np.full(len(inside), k, np.int64))
        ids.append(inside)
    return np.concatenate(ks), np.concatenate(ids)
