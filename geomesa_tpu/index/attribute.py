"""Attribute index: lexicoded attribute value keys + spatio-temporal
secondary device columns.

Reference: AttributeIndexKeySpace — rows are [2B attr ordinal][lexicoded
value][secondary z3/date tier][id] (/root/reference/geomesa-index-api/src/
main/scala/org/locationtech/geomesa/index/index/attribute/
AttributeIndexKey.scala:21-70, AttributeIndexKeySpace.scala). The TPU
redesign: the sort key is an order-preserving u64 lexicode of the value
(geomesa_tpu.utils.lexicode) — searchsorted over the sorted code column
prunes to the value range's row spans — and the reference's *secondary
tier* becomes the device predicate columns: candidate tiles still carry
(x, y) / bbox and (tbin, toff) so spatial/temporal parts of the filter
mask on device before the host gather. Attribute semantics are refined
exactly on host (string lexicodes collide beyond 8 bytes)."""

from __future__ import annotations

from typing import Optional

import numpy as np

from geomesa_tpu import geometry as geo
from geomesa_tpu.curve.binnedtime import BinnedTime, TimePeriod
from geomesa_tpu.curve.zranges import stack_boxes
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.extract import extract_attribute_bounds, extract_filter
from geomesa_tpu.filter.predicates import Filter, PointColumn
from geomesa_tpu.index.api import ScanConfig, WriteKeys, widen_boxes
from geomesa_tpu.index.z3 import clamp_bins
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.utils import lexicode


class AttributeIndex:
    """Secondary index over one ``index=true`` attribute."""

    def __init__(self, sft: FeatureType, attr: str):
        self.sft = sft
        self.attr = attr
        self.name = f"attr_{attr}"
        self.attr_type = sft.attr(attr).type
        self._is_string = self.attr_type not in (
            "Integer", "Int", "Long", "Date", "Float", "Double", "Boolean",
        )
        self.geom = sft.geom_field
        self.dtg = sft.dtg_field
        self.binner = (
            BinnedTime(TimePeriod.parse(sft.z3_interval)) if self.dtg else None
        )
        self.bin_range = None  # (min, max) time bins present; see clamp_bins

    def supports(self, sft: FeatureType) -> bool:
        return sft.has(self.attr) and not sft.attr(self.attr).is_geometry

    # -- write side ------------------------------------------------------
    def write_keys(self, fc: FeatureCollection) -> WriteKeys:
        codes = lexicode.lex_column(fc.columns[self.attr], self.attr_type)
        n = len(fc)
        device_cols: dict = {}
        if self.geom is not None:
            col = fc.columns[self.geom]
            if isinstance(col, PointColumn):
                device_cols["x"] = col.x.astype(np.float32)
                device_cols["y"] = col.y.astype(np.float32)
            elif isinstance(col, geo.PackedGeometryColumn):
                device_cols["gxmin"] = col.bboxes[:, 0]
                device_cols["gymin"] = col.bboxes[:, 1]
                device_cols["gxmax"] = col.bboxes[:, 2]
                device_cols["gymax"] = col.bboxes[:, 3]
        if self.dtg is not None:
            millis = np.asarray(fc.columns[self.dtg], dtype=np.int64)
            binned = self.binner.to_binned(millis)
            device_cols["tbin"] = binned.bin.astype(np.int32)
            device_cols["toff"] = binned.offset.astype(np.int32)
        # string values carry variable-width secondary sort words (lexicode
        # bytes past the 8-byte prefix) so prefix-tie runs stay value-
        # sorted and the scan side prunes boundary runs exactly (reference
        # AttributeIndexKey lexicodes FULL values; AttributeIndexKey.scala:
        # 21-70). Cost: 8 bytes/row/word, host-side only.
        sub = None
        if self._is_string:
            sub = lexicode.lex_string_words(fc.columns[self.attr])
        return WriteKeys(
            bins=np.zeros(n, dtype=np.int32),
            zs=codes.astype(np.uint64),
            device_cols=device_cols,
            sub=sub,
        )

    # -- read side -------------------------------------------------------
    def scan_config(self, f: Filter) -> Optional[ScanConfig]:
        return self.scan_configs([extract_filter(f, self.geom, self.dtg)])[0]

    def scan_configs(
        self, extractions: list, max_ranges: "int | None" = None
    ) -> "list[Optional[ScanConfig]]":
        """One scan config (None: the filter does not bound this attribute)
        an extraction (``filter.extract.extract_filter`` of this type's
        geom and date fields): the value bounds of ALL members (an ``IN``
        list's values, a ``query_many``'s members: rows of one array)
        lexicoded in one pass (``lexicode.lex_bounds``) and split by
        member, a range a bound in the bounds' order; the secondary
        predicates from the extraction's boxes and intervals.
        ``scan_config`` is the one-member case. ``max_ranges`` is the point
        indexes' (a value bound is one range whatever the target)."""
        out: "list[Optional[ScanConfig]]" = [None] * len(extractions)
        bound = []  # (member, its value bounds): the filters the index can serve
        for m, ex in enumerate(extractions):
            bounds = extract_attribute_bounds(ex.filter, self.attr)
            if bounds.disjoint or (bounds.values and (
                (self.geom is not None and ex.geoms.disjoint)
                or (self.dtg is not None and ex.intervals.disjoint)
            )):
                out[m] = ScanConfig.empty(self.name)
            elif bounds.values:
                bound.append((m, bounds.values))
        # the secondary predicates: device masks inside candidate tiles
        live, los, his = [], [], []
        for (m, values), windows in zip(
            bound, self._windows([extractions[m] for m, _ in bound])
        ):
            if windows is not None and not len(windows):
                # every queried time bin is absent from the store
                out[m] = ScanConfig.empty(self.name)
                continue
            los.extend(b.lo for b in values)
            his.extend(b.hi for b in values)
            has_box = self.geom is not None and bool(extractions[m].geoms.values)
            live.append((m, windows, has_box, len(los)))
        if not live:
            return out
        lo, hi = lexicode.lex_bounds(los, his, self.attr_type)
        range_lo, range_hi = lo[:, 0].copy(), hi[:, 0].copy()
        range_bins = np.zeros(len(los), dtype=np.int32)
        lo2 = hi2 = None
        if self._is_string:
            lo2, hi2 = np.ascontiguousarray(lo[:, 1:]), np.ascontiguousarray(hi[:, 1:])
        wide = widen_boxes(stack_boxes(
            [extractions[m].bounds for m, _, has_box, _ in live if has_box]
        ))
        extent = self.geom is not None and not self.sft.is_points
        a = ba = 0
        for m, windows, has_box, z in live:
            ex = extractions[m]
            bz = ba + (len(ex.bounds) if has_box else 0)
            out[m] = ScanConfig(
                index=self.name,
                range_bins=range_bins[a:z],
                range_lo=range_lo[a:z],
                range_hi=range_hi[a:z],
                boxes=wide[ba:bz] if has_box else None,
                windows=windows,
                extent_mode=extent,
                geom_precise=not has_box or (not extent and ex.boxes_exact),
                time_precise=windows is None or ex.intervals.precise,
                # value-range spans are row-exact: kernel hits (block granular)
                # must clip back to them before refinement
                clip_rows=True,
                range_lo2=None if lo2 is None else lo2[a:z],
                range_hi2=None if hi2 is None else hi2[a:z],
            )
            a, ba = z, bz
        return out

    def _windows(self, extractions: list) -> list:
        """An extraction's per-bin (bin, lo, hi) offset windows, i32
        ``[n, 3]``, cut to the bins the store holds (no row: every queried
        bin is absent), or None where it bounds no time: the intervals of
        all of them tiled in one pass."""
        out: list = [None] * len(extractions)
        timed = [
            j for j, ex in enumerate(extractions)
            if self.dtg is not None and ex.intervals.values
        ]
        if not timed:
            return out
        iv_lo, iv_hi, iv_of = np.array([
            (iv.lo, iv.hi, k)
            for k, j in enumerate(timed) for iv in extractions[j].intervals.values
        ], dtype=np.int64).T
        bins, los, his, per_iv = self.binner.bins_for_intervals(iv_lo, iv_hi - 1)
        bins, (los, his, row_of) = clamp_bins(
            self.bin_range, bins, los, his, np.repeat(iv_of, per_iv)
        )
        windows = np.stack([bins, los, his], axis=1).astype(np.int32)
        stops = np.cumsum(np.bincount(row_of, minlength=len(timed))).tolist()
        for j, a, z in zip(timed, [0, *stops], stops):
            out[j] = windows[a:z]
        return out
