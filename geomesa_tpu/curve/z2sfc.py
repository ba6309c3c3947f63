"""Z2: 2-D space-filling curve over (lon, lat) points.

Functional parity with the reference's Z2SFC
(/root/reference/geomesa-z3/src/main/scala/org/locationtech/geomesa/curve/Z2SFC.scala):
31 bits per dimension over lon [-180,180] / lat [-90,90].
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from geomesa_tpu.curve.normalize import NormalizedLat, NormalizedLon
from geomesa_tpu.curve.zorder import Z2
from geomesa_tpu.curve.zranges import (
    SCALAR_CORNERS, IndexRange, box_list, box_rows, pad_corners, pad_rows, ranges_from_arrays,
    with_inner, zranges_arrays, zranges_arrays_each,
)


class Z2SFC:
    def __init__(self, precision: int = 31):
        self.precision = precision
        self.lon = NormalizedLon(precision)
        self.lat = NormalizedLat(precision)

    def index(self, x, y) -> np.ndarray:
        """(lon, lat) -> z (vectorized). Reference Z2SFC.index."""
        return Z2.index(self.lon.normalize(x).astype(np.uint64), self.lat.normalize(y).astype(np.uint64))

    def normalize(self, x, y):
        """(lon, lat) -> (x_ord, y_ord) int32 dimension ordinals.

        TPU-first addition: the device table stores these decoded ordinals
        as int32 columns so the scan kernel never touches 64-bit z values.
        """
        return (
            self.lon.normalize(x).astype(np.int64),
            self.lat.normalize(y).astype(np.int64),
        )

    def invert(self, z):
        xi, yi = Z2.decode(z)
        return self.lon.denormalize(xi.astype(np.int64)), self.lat.denormalize(yi.astype(np.int64))

    def ranges(
        self,
        bounds: Sequence[tuple[float, float, float, float]],
        max_ranges: int | None = None,
        max_recurse: int | None = None,
        inner: bool = False,
    ) -> list[IndexRange]:
        """:meth:`ranges_arrays` as one ``IndexRange`` a range."""
        return ranges_from_arrays(
            *self.ranges_arrays(bounds, max_ranges, max_recurse, inner)
        )

    def ranges_arrays(
        self,
        bounds: Sequence[tuple[float, float, float, float]],
        max_ranges: int | None = None,
        max_recurse: int | None = None,
        inner: bool = False,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Covering z-ranges for (xmin, ymin, xmax, ymax) boxes:
        ``(lower u64[k], upper u64[k], contained bool[k])``.

        Boxes must be axis-ordered (min <= max per dimension); callers split
        antimeridian-crossing boxes into two, as the reference's do.
        ``inner=True``: classify containment 2 cells inward so contained
        rows are certain f64 hits (see Z3SFC.ranges_arrays).
        """
        mins, maxes = self._corners([bounds])
        return zranges_arrays(
            Z2, *with_inner(mins[0], maxes[0], inner),
            max_ranges=max_ranges, max_recurse=max_recurse,
        )

    def ranges_arrays_each(
        self,
        bounds: "Sequence[Sequence[tuple[float, float, float, float]]]",
        inner: bool = False,
        cover: "Sequence[Sequence[tuple[float, float, float, float]]] | None" = None,
        max_ranges: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """``len(bounds)`` decompositions in one native call: query q is
        the union of its boxes ``bounds[q]``. Returns ``(lower, upper,
        contained, counts i64[nq])``, query q's ranges after query q-1's;
        ``inner`` as :meth:`ranges_arrays`. ``cover`` (aligned with
        ``bounds``): the boxes the ranges have to cover where those are
        wider than the boxes that decide containment, as the f32 mask's
        are (``index.api.widen_boxes``). ``max_ranges`` bounds each query's
        ranges (default: the target)."""
        mins, maxes, imins, imaxes = with_inner(*self._corners(bounds), inner)
        if cover is not None:
            mins, maxes = self._corners(cover)
        return zranges_arrays_each(Z2, mins, maxes, imins, imaxes, max_ranges)

    def _corners(self, bounds) -> tuple[np.ndarray, np.ndarray]:
        """The min and max corner ordinals of every box of ``bounds[q]``,
        u64 ``[nq, nbox, 2]`` each, ``nbox`` the most a query has: a query
        with fewer repeats its last (the same union). Past
        ``SCALAR_CORNERS`` boxes in all the ordinals are one ``normalize``
        a dimension over every query's boxes (the same floor, the same
        clamp as ``normalize_one``'s)."""
        if sum(map(len, bounds)) > SCALAR_CORNERS:
            flat, counts = box_rows(bounds)
            x, y = self.lon.normalize(flat[:, 0::2]), self.lat.normalize(flat[:, 1::2])
            return (
                pad_rows(np.stack([x[:, 0], y[:, 0]], axis=1), counts),
                pad_rows(np.stack([x[:, 1], y[:, 1]], axis=1), counts),
            )
        lon, lat = self.lon.normalize_one, self.lat.normalize_one
        los, his = [], []
        for boxes in bounds:
            lo_q, hi_q = [], []
            for (xmin, ymin, xmax, ymax) in box_list(boxes):
                if xmin > xmax or ymin > ymax:
                    raise ValueError(f"inverted bbox: {(xmin, ymin, xmax, ymax)}")
                lo_q.append((lon(xmin), lat(ymin)))
                hi_q.append((lon(xmax), lat(ymax)))
            los.append(lo_q)
            his.append(hi_q)
        return pad_corners(los, 2), pad_corners(his, 2)
