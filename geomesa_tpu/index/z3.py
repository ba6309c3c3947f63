"""Z3 index: (time bin, z3) keys for point features with time.

Reference: Z3IndexKeySpace (/root/reference/geomesa-index-api/src/main/
scala/org/locationtech/geomesa/index/z3/Z3IndexKeySpace.scala:63-95 write,
:97-194 read). The reference's row is [shard][2B bin][8B z][id]; here the
(bin, z) pair is the lexicographic sort key of the columnar table, and the
shard byte becomes the device axis (geomesa_tpu.parallel). The server-side
Z3Filter membership test (index/filters/Z3Filter.scala:19-65) becomes the
device predicate arrays in the ScanConfig: f32 boxes + (bin, offset)
windows evaluated as one vectorized mask.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from geomesa_tpu.curve.binnedtime import BinnedTime, MAX_BIN, MAX_OFFSET, TimePeriod
from geomesa_tpu.curve.z3sfc import Z3SFC
from geomesa_tpu.curve.zranges import stack_boxes
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.extract import extract_filter
from geomesa_tpu.filter.predicates import Filter, PointColumn
from geomesa_tpu.index.api import (
    IndexKeySpace, ScanConfig, WriteKeys, cover_boxes, expand_runs, shrink_boxes,
    widen_boxes,
)
from geomesa_tpu.sft import FeatureType

WHOLE_WORLD = (-180.0, -90.0, 180.0, 90.0)

# query-endpoint alignment unit: ms per offset unit (BinnedTime offsets are
# ms/sec/sec/min for day/week/month/year)
_OFFSET_UNIT_MS = {
    TimePeriod.DAY: 1,
    TimePeriod.WEEK: 1000,
    TimePeriod.MONTH: 1000,
    TimePeriod.YEAR: 60_000,
}

# packed-time tick shift per period (geomesa.z3.packed-time user-data
# flag; the 1B-row layout — see block_kernels.TW_BITS): device offsets
# store as (offset >> shift) so max_offset >> shift < 2^16. Ticks: day
# ~2 s, week/month 32 s, year 16 min. Bins must fit 15 bits (day-period
# data past 2059-09 must stay unpacked).
PACKED_SHIFT = {
    TimePeriod.DAY: 11,  # 86,400,000 ms >> 11 = 42,187 ticks (~2 s)
    TimePeriod.WEEK: 5,  # 604,800 s  >> 5 = 18,900 ticks (32 s)
    TimePeriod.MONTH: 6,  # 2,678,400 s >> 6 = 41,850 ticks (64 s)
    TimePeriod.YEAR: 4,  # 527,040 min >> 4 = 32,940 ticks (16 min)
}
PACKED_KEY = "geomesa.z3.packed-time"


def pack_tw(tbin: np.ndarray, toff: np.ndarray, shift: int) -> np.ndarray:
    """(tbin, toff) -> packed i32 tw column. Raises when a bin exceeds
    the 15-bit budget or a shifted offset the 16-bit tick field (both
    would silently corrupt neighbouring bits)."""
    from geomesa_tpu.scan.block_kernels import TW_BITS, TW_MASK

    if len(tbin) and int(tbin.max()) >= (1 << (31 - TW_BITS)):
        raise ValueError(
            "packed-time bins exceed 15 bits; disable "
            f"{PACKED_KEY!r} for this data range"
        )
    ticks = toff.astype(np.int64) >> shift
    if len(ticks) and int(ticks.max()) > TW_MASK:
        raise ValueError(
            f"packed-time tick overflow (shift {shift}): offset "
            f"{int(toff.max())} >> {shift} exceeds {TW_MASK}"
        )
    return ((tbin.astype(np.int64) << TW_BITS) | ticks).astype(np.int32)


def unpack_tw(tw: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Packed i32 tw -> (tbin, tick) — the ONE host-side unpack next to
    pack_tw (the jnp kernel shares the constants in block_kernels)."""
    from geomesa_tpu.scan.block_kernels import TW_BITS, TW_MASK

    return tw >> TW_BITS, tw & TW_MASK


def windows_to_ticks(w: "np.ndarray | None", shift: int, inner: bool):
    """[W, 3] (bin, off_lo, off_hi) native-unit windows -> tick windows.
    Wide windows floor both ends (superset: a row's tick is its floored
    offset); inner windows shrink to ticks FULLY inside the interval so
    certainty never overclaims — boundary ticks refine on host."""
    if w is None or len(w) == 0:
        return w
    w = np.asarray(w, np.int64).copy()
    one = 1 << shift
    if inner:
        w[:, 1] = (w[:, 1] + one - 1) >> shift
        w[:, 2] = (w[:, 2] - one + 1) >> shift
    else:
        w[:, 1] >>= shift
        w[:, 2] >>= shift
    return w


class Z3Index:
    """Spatio-temporal point index."""

    def __init__(self, sft: FeatureType):
        self.sft = sft
        self.name = "z3"
        self.geom = sft.geom_field
        self.dtg = sft.dtg_field
        self.period = TimePeriod.parse(sft.z3_interval)
        self.sfc = Z3SFC.for_period(self.period)
        self.binner = BinnedTime(self.period)
        # packed-time device layout: one i32 tw column instead of
        # (tbin, toff) — 12 B/row, the 1e9-rows-on-one-chip budget.
        # Tables read this via getattr(keyspace, "packed_time", None)
        self.packed_time = (
            PACKED_SHIFT[self.period]
            if str(sft.user_data.get(PACKED_KEY, "")).lower() in ("true", "1")
            else None
        )
        # (min_bin, max_bin) actually present in the store, maintained by
        # DataStore on write: open-ended time predicates (dtg >= x) clamp
        # to it, so they cost the data's bins, not every representable bin
        # (an unclamped `dtg >= x` materializes tens of millions of
        # range rows — see clamp_bins)
        self.bin_range: "tuple[int, int] | None" = None

    def supports(self, sft: FeatureType) -> bool:
        return sft.is_points and sft.dtg_field is not None

    # -- write side ------------------------------------------------------
    def write_keys(self, fc: FeatureCollection) -> WriteKeys:
        col = fc.columns[self.geom]
        if not isinstance(col, PointColumn):
            raise TypeError("z3 index requires a point geometry column")
        millis = np.asarray(fc.columns[self.dtg], dtype=np.int64)

        # fused native encoder (bit-exact with the numpy path below; only
        # fixed-width periods — see geomesa_tpu.native)
        from geomesa_tpu import native

        fused = native.z3_write_keys(
            col.x, col.y, millis, self.period.value,
            MAX_OFFSET[self.period], MAX_BIN,
        )
        if fused is not None:
            bins, zs, device_cols = fused
            return WriteKeys(
                bins=bins, zs=zs, device_cols=self._pack_cols(device_cols)
            )

        binned = self.binner.to_binned(millis)
        z = self.sfc.index(col.x, col.y, binned.offset.astype(np.float64))
        return WriteKeys(
            bins=binned.bin.astype(np.int32),
            zs=z.astype(np.uint64),
            device_cols=self._pack_cols({
                "x": col.x.astype(np.float32),
                "y": col.y.astype(np.float32),
                "tbin": binned.bin.astype(np.int32),
                "toff": binned.offset.astype(np.int32),
            }),
        )

    def _pack_cols(self, device_cols: dict) -> dict:
        """(tbin, toff) -> one packed tw column when packed-time is on."""
        if self.packed_time is None:
            return device_cols
        tw = pack_tw(
            device_cols.pop("tbin"), device_cols.pop("toff"), self.packed_time
        )
        device_cols["tw"] = tw
        return device_cols

    # -- read side -------------------------------------------------------
    def scan_config(self, f: Filter) -> Optional[ScanConfig]:
        return self.scan_configs([extract_filter(f, self.geom, self.dtg)])[0]

    def scan_configs(
        self, extractions: list, max_ranges: "int | None" = None
    ) -> "list[Optional[ScanConfig]]":
        """One scan config (None: the index cannot serve the filter) an
        extraction (``filter.extract.extract_filter`` of this type's geom
        and date fields), the per-bin windows of all of them cut in one
        pass and every (filter, distinct offset window) decomposed in ONE
        native call. ``scan_config`` is the one-member case.
        ``max_ranges``: the most ranges a decomposition may emit, where
        the extractions are the branches of one query that share its
        ``geomesa.scan.ranges.target`` (default: the target, each)."""
        out: "list[Optional[ScanConfig]]" = [None] * len(extractions)
        if self.dtg is None:
            return out
        live, iv_lo, iv_hi, iv_member = [], [], [], []
        for m, ex in enumerate(extractions):
            if ex.geoms.disjoint or ex.intervals.disjoint:
                out[m] = ScanConfig.empty(self.name)
            elif ex.intervals.values:  # unbounded time: z3 cannot serve (z2 should)
                for iv in ex.intervals.values:
                    iv_lo.append(iv.lo)
                    iv_hi.append(iv.hi)
                    iv_member.append(len(live))
                live.append(m)
        if not live:
            return out

        # per-bin time windows (reference timesByBin, Z3IndexKeySpace:132-158)
        # plus the *inner* windows: offsets certain to lie inside the query
        # at millisecond precision (offsets are unit-floored at ingest, so
        # an unaligned query endpoint leaves one boundary offset uncertain)
        unit = _OFFSET_UNIT_MS[self.period]
        iv_lo, iv_hi = np.array(iv_lo, np.int64), np.array(iv_hi, np.int64)
        bins, los, his, per_iv = self.binner.bins_for_intervals(iv_lo, iv_hi - 1)
        ilos, ihis = los.copy(), his.copy()
        stops = np.cumsum(per_iv)
        ilos[(stops - per_iv)[iv_lo % unit != 0]] += 1
        ihis[stops[iv_hi % unit != 0] - 1] -= 1
        row_member = np.repeat(np.array(iv_member, np.int64), per_iv)
        bins, (los, his, ilos, ihis, row_member) = clamp_bins(
            self.bin_range, bins, los, his, ilos, ihis, row_member
        )
        row_stops = np.cumsum(np.bincount(row_member, minlength=len(live))).tolist()
        windows = np.empty((len(bins), 3), np.int32)
        windows_inner = np.empty((len(bins), 3), np.int32)
        windows[:, 0] = windows_inner[:, 0] = bins
        windows[:, 1], windows[:, 2] = los, his
        windows_inner[:, 1], windows_inner[:, 2] = ilos, ihis

        # z-ranges: one decomposition per distinct (lo, hi) offset window —
        # interior bins all share the full-offset window, so a long interval
        # costs one BFS, not one per bin (the reference recomputes per bin;
        # sharing is the columnar win since ranges are bin-independent).
        # A query of the native call is one (member, window); ``emit`` lists
        # the window rows in the order their ranges go out: a member's
        # windows in its set's order, each once a row that has it
        lo_l, hi_l = los.tolist(), his.tolist()
        flat = stack_boxes([extractions[m].bounds for m in live])
        wide, inner = widen_boxes(flat), shrink_boxes(flat)
        n_boxes = [len(extractions[m].bounds) for m in live]
        box_stops = np.cumsum(n_boxes).tolist()
        covers = cover_boxes(wide, n_boxes)
        q_bounds, q_cover, q_window, emit_row, emit_q, emit_stops = [], [], [], [], [], []
        a = 0
        for j, z in enumerate(row_stops):
            ex = extractions[live[j]]
            pairs = list(zip(lo_l[a:z], hi_l[a:z]))
            rows_of: dict = {}
            for k, w in enumerate(pairs, a):
                rows_of.setdefault(w, []).append(k)
            for w in set(pairs):
                for k in rows_of[w]:
                    emit_row.append(k)
                    emit_q.append(len(q_window))
                q_window.append(w)
                q_bounds.append(ex.bounds if ex.geoms.values else [WHOLE_WORLD])
                q_cover.append(covers[j] if ex.geoms.values else [WHOLE_WORLD])
            emit_stops.append(len(emit_row))
            a = z
        if not q_window:
            for m in live:
                out[m] = ScanConfig.empty(self.name)
            return out
        # wcont: the 2-cell inner margin (Z3SFC.ranges_arrays inner=True)
        # exceeds one offset unit in every period, so contained cells'
        # offsets are strictly inside the query interval even when its
        # endpoints are not offset-aligned — contained rows are certain at
        # ms precision. The ranges cover the boxes the mask keeps (see z2)
        wlo, whi, wcont, counts = self.sfc.ranges_arrays_each(
            q_bounds, [(float(lo), float(hi)) for lo, hi in q_window], inner=True,
            cover=q_cover, max_ranges=max_ranges,
        )
        if len(emit_q) == len(q_window):  # no window in two rows: as decomposed
            per_row, range_lo, range_hi, range_cont = counts, wlo, whi, wcont
        else:
            emit_q = np.array(emit_q, np.int64)
            per_row = counts[emit_q]
            src = expand_runs((np.cumsum(counts) - counts)[emit_q], per_row)
            range_lo, range_hi, range_cont = wlo[src], whi[src], wcont[src]
        range_bins = np.repeat(bins[np.array(emit_row, np.int64)], per_row)
        range_stops = np.concatenate([[0], np.cumsum(per_row)])[emit_stops].tolist()

        a = ra = 0
        for m, z, rz, bz in zip(live, row_stops, range_stops, box_stops):
            ex = extractions[m]
            if rz == ra:
                out[m] = ScanConfig.empty(self.name)
                a, ra = z, rz
                continue
            # no spatial constraint -> no box predicate: the scan variant then
            # projects away the x/y columns entirely (ColumnGroups analogue)
            no_geom = not ex.geoms.values
            geoms, bounds_exact = ex.geoms, ex.boxes_exact
            poly = None if (no_geom or bounds_exact) else _poly_edges(geoms)
            # kernel-side raster tier only: z3 ranges interleave time, so the
            # 2-D raster cannot reshape them (z2 gets the full range rework),
            # but the interval classification still replaces most per-row PIP
            rast = None
            if not (no_geom or bounds_exact):
                rast, _ = _poly_raster(geoms)
                if rast is not None and poly is not None:
                    from geomesa_tpu.conf import RASTER_RESIDUE

                    if str(RASTER_RESIDUE.get()).lower() != "device":
                        poly = None  # host residue (see z2)
            ba = bz - len(ex.bounds)
            out[m] = ScanConfig(
                index=self.name,
                range_bins=range_bins[ra:rz],
                range_lo=range_lo[ra:rz],
                range_hi=range_hi[ra:rz],
                boxes=None if no_geom else wide[ba:bz],
                windows=windows[a:z],
                # the device PIP/raster tiers make single-polygon queries
                # precise on device (see z2); contained certainty stays
                # bbox-only here (z3 ranges are bbox-derived)
                geom_precise=bounds_exact or poly is not None or rast is not None,
                time_precise=ex.intervals.precise,
                range_contained=range_cont[ra:rz],
                # contained certainty additionally requires the *filter* to be
                # decided by bbox+interval alone — the planner checks kinds; here
                # we require the geometry values themselves to be plain boxes
                contained_exact=bool(bounds_exact and ex.intervals.precise),
                boxes_inner=None if no_geom else inner[ba:bz],
                windows_inner=windows_inner[a:z],
                poly=poly,
                rast=rast,
            )
            a, ra = z, rz
        return out


def clamp_bins(bin_range, b, *cols):
    """Drop per-bin window rows outside the store's known (min, max) bin
    range — exact for scanning (rows in absent bins do not exist), and the
    guard against open-ended time predicates materializing every
    representable bin."""
    if bin_range is None:
        return b, cols
    keep = (b >= bin_range[0]) & (b <= bin_range[1])
    if keep.all():
        return b, cols
    return b[keep], tuple(c[keep] for c in cols)


def _bounds_only(geom_values) -> bool:
    """True when every extracted geometry is its own bbox (the device box
    test is then exact up to f32); polygons need host refinement."""
    from geomesa_tpu.filter.extract import _is_box

    return all(_is_box(g) for g in geom_values)


def _poly_edges(geoms) -> "np.ndarray | None":
    """Packed edge block for the device point-in-polygon tier, or None
    when the extraction cannot ride it: it needs ONE precisely-extracted
    Polygon/MultiPolygon whose edge count fits the kernel's bucket ladder
    (block_kernels.pack_edges). Imprecise extractions (NOT branches,
    DWithin, non-polygon geometries) keep the bbox + host-refine path."""
    from geomesa_tpu.scan import block_kernels as bk

    if not geoms.precise or len(geoms.values) != 1:
        return None
    return bk.pack_edges(geoms.values[0])


def _poly_raster(geoms):
    """(packed [1 + R, 128] raster block, RasterApprox) for the kernel's
    raster-interval tier (arXiv 2307.01716), or (None, None) when the
    extraction cannot ride it — same eligibility as _poly_edges, minus
    the edge-count cap (rasters approximate polygons of ANY complexity,
    which is exactly where they pay: past E_BUCKETS the PIP tier cannot
    run at all and every candidate row used to host-refine)."""
    from geomesa_tpu.conf import RASTER_KERNEL_INTERVALS
    from geomesa_tpu.filter import raster as fr
    from geomesa_tpu.scan import block_kernels as bk

    if not geoms.precise or len(geoms.values) != 1:
        return None, None
    approx = fr.raster_for(geoms.values[0])
    if approx is None:
        return None, None
    bucket = bk.r_bucket_of(
        min(len(approx.ilo), max(int(RASTER_KERNEL_INTERVALS.get()), 1))
    )
    return approx.pack_block(bucket), approx
