"""The plain NumPy reference for stores of polygons: which footprints
share a point with a closed query box or polygon, decided on the f64
vertices the generator made. It imports nothing of the program and is
handed nothing the program made (``datagen/osm_buildings.py``'s columns:
``coords``, ``offsets``, ``vertices``, ``bounds``).

``ref_ids(cols, box, ring)``: first the footprints whose exact f64 bounds
overlap the query's (closed on every side; a footprint whose bounds miss
the query's bounds shares no point with it), over ALL rows; then, for the
candidates of each vertex count together, the three ways two simple
polygons can share a point:

- a vertex of the footprint lies inside the query ring (even-odd ray
  cast, ``reference.in_ring``'s arithmetic);
- a vertex of the query ring lies inside the footprint (the same cast,
  every candidate's own ring);
- an edge of one meets an edge of the other: the four orientation signs of
  the two segments (a cross product in f64), a proper crossing where both
  pairs differ in sign, and where a sign is 0 the collinear point tested
  against the other segment's bounds, so that a touch and a collinear
  overlap count (a point ON a boundary is caught here and not by the casts).

Departures from JTS (``RelateOp`` / ``RectangleIntersects``), all in how a
sign is computed, none in what is asked: JTS decides an orientation with
a robust determinant (double-double when the f64 sign is in doubt), this
file and the program take the sign of the plain f64 cross product, so a
vertex within a rounding error of an edge (1e-16 of the coordinates'
size; no seeded request comes that close) could be decided otherwise;
JTS works on a noded topology graph and this file on vertex and edge
tests, which agree for simple rings without holes, the only geometries
here; a BBOX is the closed box as a ring of four corners.
"""

from __future__ import annotations

import numpy as np


def box_ring(box) -> np.ndarray:
    x0, y0, x1, y1 = (float(v) for v in box)
    return np.array([[x0, y0], [x1, y0], [x1, y1], [x0, y1]])


def _inside(px, py, ring) -> np.ndarray:
    """Even-odd ray cast of points against rings, broadcasting: ``px``,
    ``py`` [..., P]; ``ring`` [..., V, 2] open or closed (a closing edge of
    no length casts nothing). Returns [..., P]."""
    ring = np.asarray(ring, np.float64)
    x0, y0 = ring[..., :, None, 0], ring[..., :, None, 1]  # [..., V, 1]
    x1, y1 = np.roll(x0, -1, axis=-2), np.roll(y0, -1, axis=-2)
    px, py = px[..., None, :], py[..., None, :]  # [..., 1, P]
    cross = (y0 > py) != (y1 > py)
    with np.errstate(divide="ignore", invalid="ignore"):
        xi = x0 + (py - y0) * (x1 - x0) / (y1 - y0)
    return (np.sum(cross & (px < xi), axis=-2) % 2).astype(bool)


def _orient(ax, ay, bx, by, cx, cy):
    """Sign of the cross product (b - a) x (c - a): which side of a->b c is on."""
    return np.sign((bx - ax) * (cy - ay) - (by - ay) * (cx - ax))


def _within(ax, ay, bx, by, cx, cy):
    """c inside the bounds of the segment a-b (asked of a collinear c)."""
    return ((np.minimum(ax, bx) <= cx) & (cx <= np.maximum(ax, bx))
            & (np.minimum(ay, by) <= cy) & (cy <= np.maximum(ay, by)))


def _edges_meet(a0, a1, b0, b1) -> np.ndarray:
    """Whether closed segments a0-a1 and b0-b1 share a point, elementwise
    over broadcast arrays whose last axis is (x, y)."""
    ax, ay, bx, by = a0[..., 0], a0[..., 1], a1[..., 0], a1[..., 1]
    cx, cy, dx, dy = b0[..., 0], b0[..., 1], b1[..., 0], b1[..., 1]
    o1, o2 = _orient(ax, ay, bx, by, cx, cy), _orient(ax, ay, bx, by, dx, dy)
    o3, o4 = _orient(cx, cy, dx, dy, ax, ay), _orient(cx, cy, dx, dy, bx, by)
    meet = (o1 * o2 < 0) & (o3 * o4 < 0)
    meet |= (o1 == 0) & _within(ax, ay, bx, by, cx, cy)
    meet |= (o2 == 0) & _within(ax, ay, bx, by, dx, dy)
    meet |= (o3 == 0) & _within(cx, cy, dx, dy, ax, ay)
    meet |= (o4 == 0) & _within(cx, cy, dx, dy, bx, by)
    return meet


def rings_intersect(feet: np.ndarray, ring: np.ndarray) -> np.ndarray:
    """[K] bool: which of K closed footprints ``feet`` [K, V, 2] (the first
    vertex again at the end) share a point with the simple polygon
    ``ring`` [M, 2] (open: the closing edge is added here)."""
    ring = np.asarray(ring, np.float64)
    hit = _inside(feet[:, :-1, 0], feet[:, :-1, 1], ring).any(axis=1)
    hit |= _inside(ring[:, 0][None, :], ring[:, 1][None, :], feet[:, :-1]).any(axis=1)
    todo = np.flatnonzero(~hit)
    if len(todo):
        f = feet[todo]
        a0, a1 = f[:, :-1, None, :], f[:, 1:, None, :]  # [k, V-1, 1, 2]
        b0, b1 = ring[None, None, :, :], np.roll(ring, -1, axis=0)[None, None, :, :]
        hit[todo] = _edges_meet(a0, a1, b0, b1).any(axis=(1, 2))
    return hit


def ref_ids(cols, box, ring=None) -> np.ndarray:
    """Ascending ids of the footprints that intersect the closed ``box``
    or, given a ``ring`` (whose bounds ``box`` then is), the polygon."""
    x0, y0, x1, y1 = (float(v) for v in box)
    b = cols.bounds
    cand = np.flatnonzero((b[:, 0] <= x1) & (b[:, 2] >= x0) & (b[:, 1] <= y1) & (b[:, 3] >= y0))
    query = box_ring(box) if ring is None else np.asarray(ring, np.float64)
    keep = np.zeros(len(cand), bool)
    counts = cols.vertices[cand]
    for v in np.unique(counts):
        group = np.flatnonzero(counts == v)
        at = cols.offsets[cand[group], None] + np.arange(int(v))
        keep[group] = rings_intersect(cols.coords[at], query)
    return cand[keep]
