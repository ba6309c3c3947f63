"""Z3: 3-D space-filling curve over (lon, lat, time-offset) points.

Functional parity with the reference's Z3SFC
(/root/reference/geomesa-z3/src/main/scala/org/locationtech/geomesa/curve/Z3SFC.scala:37-84):
21 bits per dimension; the time dimension spans the offset range of one
time bin (day/week/month/year — see geomesa_tpu.curve.binnedtime).
Per-period singleton instances mirror Z3SFC.apply.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from geomesa_tpu.curve.binnedtime import MAX_OFFSET, TimePeriod
from geomesa_tpu.curve.normalize import NormalizedLat, NormalizedLon, NormalizedTime
from geomesa_tpu.curve.zorder import Z3
from geomesa_tpu.curve.zranges import (
    SCALAR_CORNERS, IndexRange, box_list, box_rows, pad_corners, pad_rows, ranges_from_arrays,
    with_inner, zranges_arrays, zranges_arrays_each,
)

_INSTANCES: dict[TimePeriod, "Z3SFC"] = {}


class Z3SFC:
    def __init__(self, period: "TimePeriod | str" = TimePeriod.WEEK, precision: int = 21):
        self.period = TimePeriod.parse(period)
        self.precision = precision
        self.lon = NormalizedLon(precision)
        self.lat = NormalizedLat(precision)
        self.time = NormalizedTime(precision, float(MAX_OFFSET[self.period]))

    @staticmethod
    def for_period(period: "TimePeriod | str") -> "Z3SFC":
        p = TimePeriod.parse(period)
        if p not in _INSTANCES:
            _INSTANCES[p] = Z3SFC(p)
        return _INSTANCES[p]

    def index(self, x, y, t) -> np.ndarray:
        """(lon, lat, offset) -> z (vectorized). Reference Z3SFC.index:37."""
        return Z3.index(
            self.lon.normalize(x).astype(np.uint64),
            self.lat.normalize(y).astype(np.uint64),
            self.time.normalize(t).astype(np.uint64),
        )

    def normalize(self, x, y, t):
        """(lon, lat, offset) -> int ordinals for the device columns."""
        return (
            self.lon.normalize(x).astype(np.int64),
            self.lat.normalize(y).astype(np.int64),
            self.time.normalize(t).astype(np.int64),
        )

    def invert(self, z):
        xi, yi, ti = Z3.decode(z)
        return (
            self.lon.denormalize(xi.astype(np.int64)),
            self.lat.denormalize(yi.astype(np.int64)),
            self.time.denormalize(ti.astype(np.int64)),
        )

    def ranges(
        self,
        bounds: Sequence[tuple[float, float, float, float]],
        times: Sequence[tuple[float, float]],
        max_ranges: int | None = None,
        max_recurse: int | None = None,
        inner: bool = False,
    ) -> list[IndexRange]:
        """:meth:`ranges_arrays` as one ``IndexRange`` a range."""
        return ranges_from_arrays(
            *self.ranges_arrays(bounds, times, max_ranges, max_recurse, inner)
        )

    def ranges_arrays(
        self,
        bounds: Sequence[tuple[float, float, float, float]],
        times: Sequence[tuple[float, float]],
        max_ranges: int | None = None,
        max_recurse: int | None = None,
        inner: bool = False,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Covering z-ranges for spatial boxes x time-offset windows:
        ``(lower u64[k], upper u64[k], contained bool[k])``.

        Reference Z3SFC.ranges:59-67 — the cartesian product of spatial
        bounds and (in-bin) time windows becomes one box each.

        ``inner=True`` additionally classifies containment against ordinals
        shrunk 2 cells inward per dimension, making contained-range rows
        certain f64 hits (ScanConfig.contained_exact). The 2-cell margin
        absorbs normalize() floor rounding on both the query bounds and the
        stored values.
        """
        mins, maxes = self._corners([list(bounds)], [list(times)])  # [1, nb * nt, 3]
        return zranges_arrays(
            Z3, *with_inner(mins[0], maxes[0], inner),
            max_ranges=max_ranges, max_recurse=max_recurse,
        )

    def ranges_arrays_by_window(
        self,
        bounds: Sequence[tuple[float, float, float, float]],
        times: Sequence[tuple[float, float]],
        inner: bool = False,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """One :meth:`ranges_arrays` decomposition a time window (each
        the union of ``bounds`` under that window alone):
        :meth:`ranges_arrays_each` with the same boxes every window."""
        return self.ranges_arrays_each([list(bounds)] * len(times), times, inner)

    def ranges_arrays_each(
        self,
        bounds: "Sequence[Sequence[tuple[float, float, float, float]]]",
        times: Sequence[tuple[float, float]],
        inner: bool = False,
        cover: "Sequence[Sequence[tuple[float, float, float, float]]] | None" = None,
        max_ranges: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """``len(times)`` decompositions in one native call: query q is
        the union of its boxes ``bounds[q]`` under its one offset window
        ``times[q]``. Returns ``(lower, upper, contained, counts
        i64[nq])``, query q's ranges after query q-1's, ``counts[q]`` of
        them; ``inner`` as :meth:`ranges_arrays`; ``cover`` as
        :meth:`Z2SFC.ranges_arrays_each`'s (the windows stay ``times``);
        ``max_ranges`` bounds each query's ranges (default: the target)."""
        mins, maxes, imins, imaxes = with_inner(*self._corners_each(bounds, times), inner)
        if cover is not None:
            mins, maxes = self._corners_each(cover, times)
        return zranges_arrays_each(Z3, mins, maxes, imins, imaxes, max_ranges)

    def _corners_each(self, bounds, times) -> tuple[np.ndarray, np.ndarray]:
        """:meth:`_corners` of ONE window a query (``times[q]``). Past
        ``SCALAR_CORNERS`` boxes in all the ordinals are one ``normalize``
        a dimension over every query's boxes and one over the windows (the
        same floor, the same clamp as ``normalize_one``'s)."""
        if sum(map(len, bounds)) <= SCALAR_CORNERS:
            return self._corners(bounds, [[w] for w in times])
        flat, counts = box_rows(bounds)
        t = np.asarray(times, dtype=np.float64).reshape(-1, 2)
        if (t[:, 0] > t[:, 1]).any():
            raise ValueError(f"inverted time window: {tuple(t[np.argmax(t[:, 0] > t[:, 1])])}")
        x, y = self.lon.normalize(flat[:, 0::2]), self.lat.normalize(flat[:, 1::2])
        t = np.repeat(self.time.normalize(t), counts, axis=0)
        return (
            pad_rows(np.stack([x[:, 0], y[:, 0], t[:, 0]], axis=1), counts),
            pad_rows(np.stack([x[:, 1], y[:, 1], t[:, 1]], axis=1), counts),
        )

    def _corners(self, bounds, times) -> tuple[np.ndarray, np.ndarray]:
        """The min and max corner ordinals of every box of ``bounds[q]``
        under every window of ``times[q]``, u64 ``[nq, nbox, 3]`` each,
        ``nbox`` the most a query has: a query with fewer repeats its last
        (the union it describes, and so its decomposition, is the same)."""
        lon, lat, time = (
            self.lon.normalize_one, self.lat.normalize_one, self.time.normalize_one
        )
        los, his = [], []
        for boxes, windows in zip(bounds, times):
            lo_q, hi_q = [], []
            for (xmin, ymin, xmax, ymax) in box_list(boxes):
                if xmin > xmax or ymin > ymax:
                    raise ValueError(f"inverted bbox: {(xmin, ymin, xmax, ymax)}")
                for (tmin, tmax) in windows:
                    if tmin > tmax:
                        raise ValueError(f"inverted time window: {(tmin, tmax)}")
                    lo_q.append((lon(xmin), lat(ymin), time(tmin)))
                    hi_q.append((lon(xmax), lat(ymax), time(tmax)))
            los.append(lo_q)
            his.append(hi_q)
        return pad_corners(los, 3), pad_corners(his, 3)
