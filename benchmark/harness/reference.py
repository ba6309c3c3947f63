"""The plain NumPy reference: the same questions over the generator's
columns, under the semantics the configuration states. It imports nothing
of the program and is handed nothing the program made.

Row queries are exact in f64: a closed box, and ``DURING lo/hi`` as the
store documents it, lo <= t < hi on epoch millis (filter/predicates.py
``During``; chip_smoke.py's reference took the interval open, which random
millisecond times never told apart). The preloaded rows' times ascend, so
a window is two binary searches and the box test runs over that slice
only.

``loose_rows`` / ``check_density`` are chip_smoke.py's: the gather-free
device aggregation's documented semantics (f32 columns, box one ulp wider,
whole-second offsets) and what a density grid may differ by.
"""

from __future__ import annotations

import numpy as np


def in_ring(px, py, ring) -> np.ndarray:
    """Even-odd ray cast in f64 (the textbook crossing test)."""
    inside = np.zeros(len(px), bool)
    for (x0, y0), (x1, y1) in zip(ring, ring[1:] + ring[:1]):
        if y0 == y1:
            continue
        cross = (y0 > py) != (y1 > py)
        xi = x0 + (py - y0) * (x1 - x0) / (y1 - y0)
        inside ^= cross & (px < xi)
    return inside


def _kept(x, y, t, box, win, ring):
    x0, y0, x1, y1 = box
    m = x >= x0
    m &= x <= x1
    m &= y >= y0
    m &= y <= y1
    if win is not None:
        m &= t >= win[0]
        m &= t < win[1]
    rows = np.flatnonzero(m)
    if ring is not None and len(rows):
        rows = rows[in_ring(x[rows], y[rows], ring)]
    return rows


def ref_ids(cols, box, win=None, ring=None) -> np.ndarray:
    """Ascending ids of the rows the filter keeps."""
    lo, hi = 0, len(cols.t)
    if win is not None:
        lo, hi = (int(v) for v in np.searchsorted(cols.t, [win[0], win[1]], "left"))
    return lo + _kept(cols.x[lo:hi], cols.y[lo:hi], cols.t[lo:hi], box, win, ring)


def loose_rows(cols, box, win=None):
    """(f32 x, f32 y) of the rows the device aggregations keep: columns
    rounded to f32, the box one f32 ulp wider on every side, [lo, hi) on
    whole seconds."""
    f32 = np.float32
    lo_i, hi_i = 0, len(cols.t)
    if win is not None:
        if win[0] % 1000 or win[1] % 1000:
            raise ValueError("second-aligned window")
        lo_i, hi_i = (int(v) for v in np.searchsorted(cols.t, [win[0], win[1]], "left"))
    x0, y0, x1, y1 = (f32(v) for v in box)
    lo = [np.nextafter(v, f32(-np.inf)) for v in (x0, y0)]
    hi = [np.nextafter(v, f32(np.inf)) for v in (x1, y1)]
    x32, y32 = cols.x[lo_i:hi_i].astype(f32), cols.y[lo_i:hi_i].astype(f32)
    m = (x32 >= lo[0]) & (x32 <= hi[0]) & (y32 >= lo[1]) & (y32 <= hi[1])
    return x32[m], y32[m]


# what three f32 operations can move a pixel coordinate below 512: each
# rounds within half an ulp (the chip's divide within one), 2**-12 px is
# ten times that
PIXEL_EPS = 2.0 ** -12


def check_density(grid, x32, y32, env, width, height) -> dict:
    """The device grid against the rows it was made from. Which rows are
    in (f32 compares) and how many is exact. A row's pixel is exact too
    unless its coordinate, computed in f64 from the same f32 inputs, lies
    within PIXEL_EPS of a pixel edge: only such a row may land on either
    side. Returns ``sum_gap`` (grid total minus rows in the envelope) and
    ``bad_pixels`` (pixels outside decided <= grid <= decided + edge rows
    that can reach them); both must be 0."""
    f32 = np.float32
    x0, y0, x1, y1 = (f32(v) for v in env)
    m = (x32 >= x0) & (x32 <= x1) & (y32 >= y0) & (y32 <= y1)
    fx = (x32[m].astype(np.float64) - x0) / (np.float64(x1) - x0) * width
    fy = (y32[m].astype(np.float64) - y0) / (np.float64(y1) - y0) * height

    def cell(f, size):
        return np.clip(np.floor(f).astype(np.int64), 0, size - 1)

    lo = cell(fy - PIXEL_EPS, height) * width + cell(fx - PIXEL_EPS, width)
    hi = cell(fy + PIXEL_EPS, height) * width + cell(fx + PIXEL_EPS, width)
    decided = lo == hi
    n = width * height
    lower = np.bincount(lo[decided], minlength=n)
    reach = lower.copy()
    e = ~decided
    for cy in (fy[e] - PIXEL_EPS, fy[e] + PIXEL_EPS):
        for cx in (fx[e] - PIXEL_EPS, fx[e] + PIXEL_EPS):
            reach += np.bincount(cell(cy, height) * width + cell(cx, width), minlength=n)
    g = np.asarray(grid)
    if g.shape != (height, width):
        return {"sum_gap": int(m.sum()), "bad_pixels": n, "rows": int(m.sum())}
    g = g.astype(np.int64).ravel()
    return {
        "sum_gap": int(g.sum()) - int(m.sum()),
        "bad_pixels": int(((g < lower) | (g > reach)).sum()),
        "rows": int(m.sum()),
    }
