"""Z2 index: z-order keys for point features, no time dimension.

Reference: Z2IndexKeySpace (/root/reference/geomesa-index-api/src/main/
scala/org/locationtech/geomesa/index/z2/Z2IndexKeySpace.scala) and the
server-side Z2Filter (index/filters/Z2Filter.scala). Bin is constant 0 so
the sorted table is ordered purely by z2.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from geomesa_tpu.curve.z2sfc import Z2SFC
from geomesa_tpu.curve.zranges import stack_boxes
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.extract import extract_filter
from geomesa_tpu.filter.predicates import Filter, PointColumn
from geomesa_tpu.index.api import (
    ScanConfig, WriteKeys, cover_boxes, shrink_boxes, widen_boxes,
)
from geomesa_tpu.sft import FeatureType


class Z2Index:
    """Spatial-only point index."""

    def __init__(self, sft: FeatureType):
        self.sft = sft
        self.name = "z2"
        self.geom = sft.geom_field
        self.sfc = Z2SFC()

    def supports(self, sft: FeatureType) -> bool:
        return sft.is_points

    def write_keys(self, fc: FeatureCollection) -> WriteKeys:
        col = fc.columns[self.geom]
        if not isinstance(col, PointColumn):
            raise TypeError("z2 index requires a point geometry column")
        n = len(col)

        from geomesa_tpu import native

        fused = native.z2_write_keys(col.x, col.y)
        if fused is not None:
            z, device_cols = fused
            return WriteKeys(
                bins=np.zeros(n, dtype=np.int32), zs=z, device_cols=device_cols
            )

        z = self.sfc.index(col.x, col.y)
        return WriteKeys(
            bins=np.zeros(n, dtype=np.int32),
            zs=z.astype(np.uint64),
            device_cols={
                "x": col.x.astype(np.float32),
                "y": col.y.astype(np.float32),
            },
        )

    def scan_config(self, f: Filter) -> Optional[ScanConfig]:
        return self.scan_configs([extract_filter(f, self.geom, None)])[0]

    def scan_configs(
        self, extractions: list, max_ranges: "int | None" = None
    ) -> "list[Optional[ScanConfig]]":
        """One scan config (None: no spatial constraint) an extraction
        (``filter.extract.extract_filter`` of this type's geom field); the
        boxes of all that take covering ranges from the curve decomposed
        in ONE native call. ``scan_config`` is the one-member case.
        ``max_ranges`` as :meth:`Z3Index.scan_configs`'s."""
        from geomesa_tpu.index.z3 import _poly_edges, _poly_raster

        out: "list[Optional[ScanConfig]]" = [None] * len(extractions)
        pending = []  # (member, poly): covering ranges from the curve
        for m, ex in enumerate(extractions):
            geoms = ex.geoms
            if geoms.disjoint:
                out[m] = ScanConfig.empty(self.name)
                continue
            if not geoms.values:
                continue  # no spatial constraint: a z2 scan would be full-table
            poly = None if ex.boxes_exact else _poly_edges(geoms)
            rast, approx = (None, None) if ex.boxes_exact else _poly_raster(geoms)
            if rast is not None and poly is not None:
                from geomesa_tpu.conf import RASTER_RESIDUE

                if str(RASTER_RESIDUE.get()).lower() != "device":
                    # host residue (default): the kernel runs the raster leg
                    # alone — partial-cell rows come back uncertain and the
                    # planner's exact refinement resolves them on host
                    poly = None
            if approx is None:
                pending.append((m, poly))
                continue
            # raster-derived z-ranges (arXiv 2307.01716): FULL cells emit
            # contained ranges — certain hits even for polygons, because
            # full-cell containment implies membership (margin-safe at
            # f64) — PARTIAL cells emit overlap ranges, and OUT cells
            # inside the bbox are pruned before any device work. The
            # Z2-aligned grid makes every cell one contiguous z-range.
            from geomesa_tpu.conf import SCAN_RANGES_TARGET

            rlo, rhi, rcont = approx.zranges(
                max_ranges=max_ranges or SCAN_RANGES_TARGET.get()
            )
            if len(rlo) == 0:
                out[m] = ScanConfig.empty(self.name)
                continue
            out[m] = ScanConfig(
                index=self.name,
                range_bins=np.zeros(len(rlo), dtype=np.int32),
                range_lo=rlo,
                range_hi=rhi,
                boxes=widen_boxes(ex.bounds),
                windows=None,
                geom_precise=True,
                range_contained=rcont,
                contained_exact=True,
                boxes_inner=shrink_boxes(ex.bounds),
                poly=poly,
                rast=rast,
            )
        if not pending:
            return out
        bounds = [extractions[m].bounds for m, _ in pending]
        flat = stack_boxes(bounds)
        wide, inner = widen_boxes(flat), shrink_boxes(flat)
        # covering ranges of the boxes the mask keeps, containment by the
        # f64 boxes: a contained row is a certain f64 hit as before
        range_lo, range_hi, range_contained, counts = self.sfc.ranges_arrays_each(
            bounds, inner=True, cover=cover_boxes(wide, [len(bs) for bs in bounds]),
            max_ranges=max_ranges,
        )
        zeros = np.zeros(len(range_lo), dtype=np.int32)
        ra = ba = 0
        for (m, poly), n, bs in zip(pending, counts.tolist(), bounds):
            rz, bz = ra + n, ba + len(bs)
            if n == 0:
                out[m] = ScanConfig.empty(self.name)
            else:
                bounds_exact = extractions[m].boxes_exact
                out[m] = ScanConfig(
                    index=self.name,
                    range_bins=zeros[ra:rz],
                    range_lo=range_lo[ra:rz],
                    range_hi=range_hi[ra:rz],
                    boxes=wide[ba:bz],
                    windows=None,
                    # the device PIP tier answers polygon queries exactly (host
                    # refines only the uncertainty band), so the mask decides the
                    # filter; contained-range certainty stays bbox-only
                    geom_precise=bounds_exact or poly is not None,
                    range_contained=range_contained[ra:rz],
                    contained_exact=bool(bounds_exact),
                    boxes_inner=inner[ba:bz],
                    poly=poly,
                )
            ra, ba = rz, bz
        return out
