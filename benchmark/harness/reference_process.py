"""The plain NumPy reference for the two processes of the AIS cell: the k
nearest rows to a point, and the rows inside a corridor round a track.
Straight from the guarantees ``configs/ais-reports-1chip.json`` states, in
f64 over EVERY row of the generator's columns (``x``, ``y``, ``t``): no
index, no window that grows, no bins and no boxes, so a slice box that
drops a true hit, a ``max_bins`` cap that loses a waypoint or a wrong
boundary at the track's last instant shows as a difference. It imports
nothing of the program and is handed nothing the program made; the
haversine is its own.

``knn(cols, x, y, k, win, max_distance_m)``: of the rows with
``win[0] <= t < win[1]`` (``DURING`` as the store documents it) that lie
within ``max_distance_m`` of (x, y), the ``k`` of least distance, nearest
first, ties by id: (ids, distances in metres). Fewer than ``k`` only if no
more exist there.

``tube(cols, track_xy, track_t, buffer_m)``: the rows whose time lies in
[t_first, t_last] of the track and whose distance to the track's position
at the row's own time, linearly interpolated in lon and lat between the
two waypoints round that time, is at most ``buffer_m``: ascending ids.
Where several waypoints share one time (a track may stand still on the
clock), the position AT that time is the last of them, as ``np.interp``
reads a repeated abscissa.

Departures from upstream, in what is asked and not in how it is computed
(docs/processes.md): upstream's ``TubeSelectProcess`` buffers each time
bin's geometry and tests intersection inside the bin (``gapFill`` none /
line / interpolated); the corridor here is the continuous interpolated one,
which is the program's. Upstream's ``KNearestNeighborSearchProcess``
measures by ``GeodeticCalculator`` on the WGS84 ellipsoid; here and in the
program the sphere of R = 6,371,000 m.
"""

from __future__ import annotations

import numpy as np

EARTH_RADIUS_M = 6_371_000.0


def haversine_m(lon1, lat1, lon2, lat2) -> np.ndarray:
    """Great-circle metres between (lon1, lat1) and (lon2, lat2), degrees in."""
    p1, p2 = np.radians(np.asarray(lat1, np.float64)), np.radians(np.asarray(lat2, np.float64))
    dlon = np.radians(np.asarray(lon2, np.float64)) - np.radians(np.asarray(lon1, np.float64))
    h = np.sin((p2 - p1) / 2.0) ** 2 + np.cos(p1) * np.cos(p2) * np.sin(dlon / 2.0) ** 2
    return 2.0 * EARTH_RADIUS_M * np.arcsin(np.minimum(1.0, np.sqrt(h)))


def knn(cols, x: float, y: float, k: int, win, max_distance_m: float):
    """(ids, metres): the guarantee's k nearest, nearest first."""
    rows = np.flatnonzero((cols.t >= int(win[0])) & (cols.t < int(win[1])))
    d = haversine_m(x, y, cols.x[rows], cols.y[rows])
    near = d <= float(max_distance_m)
    rows, d = rows[near], d[near]
    order = np.lexsort((rows, d))[: int(k)]  # by distance, then by id
    return rows[order].astype(np.int64), d[order]


def tube(cols, track_xy, track_t, buffer_m: float) -> np.ndarray:
    """Ascending ids of the rows inside the corridor."""
    xy = np.asarray(track_xy, np.float64).reshape(-1, 2)
    ts = np.asarray(track_t, np.int64)
    rows = np.flatnonzero((cols.t >= ts[0]) & (cols.t <= ts[-1]))
    at = cols.t[rows].astype(np.float64)
    px = np.interp(at, ts.astype(np.float64), xy[:, 0])
    py = np.interp(at, ts.astype(np.float64), xy[:, 1])
    inside = haversine_m(cols.x[rows], cols.y[rows], px, py) <= float(buffer_m)
    return rows[inside].astype(np.int64)
