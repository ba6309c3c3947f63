"""StatsStore: per-schema sketches maintained at ingest, serving the
planner's cost model and user-facing stats queries.

Reference: GeoMesaStats (/root/reference/geomesa-index-api/src/main/scala/
org/locationtech/geomesa/index/stats/GeoMesaStats.scala:30-110) — counts,
bounds, min/max, histograms — persisted as sketches by MetadataBackedStats
and consumed by CostBasedStrategyDecider. Here the sketches are built with
one pass of vectorized column reductions per write batch.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.stats.sketches import (
    CountStat,
    Frequency,
    Histogram,
    MinMax,
    TopK,
    Z3Histogram,
)

HISTOGRAM_BINS = 1000


class StatsStore:
    """Sketch bundle for one feature type."""

    def __init__(self, sft):
        self.sft = sft
        self.count = CountStat()
        self.minmax: dict[str, MinMax] = {}
        self.histograms: dict[str, Histogram] = {}
        self.frequencies: dict[str, Frequency] = {}
        self.topk: dict[str, TopK] = {}
        self.z3: Z3Histogram | None = None
        # which index's keys feed the z sketch ("z3" or "z2"): estimates
        # are only valid for ranges in THAT index's key space — z2 ranges
        # against a z3-keyed sketch silently estimate ~0
        self.z_index: "str | None" = None

    # -- build -----------------------------------------------------------
    @staticmethod
    def build(sft, fc) -> "StatsStore":
        from geomesa_tpu.filter.predicates import PointColumn

        st = StatsStore(sft)
        st.count.observe(fc.ids)
        for attr in sft.attributes:
            col = fc.columns.get(attr.name)
            if col is None:
                continue
            if attr.is_geometry:
                if isinstance(col, PointColumn):
                    xs, ys = col.x, col.y
                else:
                    b = col.bboxes  # [n, 4] xmin ymin xmax ymax
                    xs = np.concatenate([b[:, 0], b[:, 2]])
                    ys = np.concatenate([b[:, 1], b[:, 3]])
                mm_x, mm_y = MinMax(), MinMax()
                mm_x.observe(xs)
                mm_y.observe(ys)
                st.minmax[attr.name + ".x"] = mm_x
                st.minmax[attr.name + ".y"] = mm_y
                # marginal coordinate histograms: the bbox selectivity
                # estimator (independence product) — much finer spatial
                # resolution than the z-prefix sketch for bbox-only
                # probes on z3-keyed stores
                for suffix, vals, mm in ((".x", xs, mm_x), (".y", ys, mm_y)):
                    if mm.bounds is not None:
                        h = Histogram(
                            HISTOGRAM_BINS, float(mm.min), float(mm.max) + 1e-9
                        )
                        h.observe(np.asarray(vals, dtype=np.float64))
                        st.histograms[attr.name + suffix] = h
                continue
            if attr.type == "Bytes":
                # opaque blobs: equality/range selectivity sketches are
                # meaningless and str-hashing binary data crashes
                continue
            col = np.asarray(col)
            if col.dtype.kind in "iuf" or attr.type == "Date":
                mm = MinMax()
                mm.observe(col)
                st.minmax[attr.name] = mm
                if mm.bounds is not None:
                    h = Histogram(
                        HISTOGRAM_BINS, float(mm.min), float(mm.max) + 1e-9
                    )
                    h.observe(col.astype(np.float64))
                    st.histograms[attr.name] = h
            else:
                if col.dtype.kind == "O":
                    # nulls sketch as "" (IsNull's empty-string semantics);
                    # np.unique cannot sort mixed None/str
                    col = np.array(["" if v is None else str(v) for v in col])
                f = Frequency()
                f.observe(col)
                st.frequencies[attr.name] = f
                tk = TopK()
                tk.observe(col)
                st.topk[attr.name] = tk
        return st

    def observe_index_keys(self, index_name: str, bins, zs, total_bits: int) -> None:
        """Feed (bin, z) write keys into the spatio-temporal sketch."""
        if index_name in ("z3", "z2"):
            if self.z3 is None:
                self.z3 = Z3Histogram(total_bits)
                self.z_index = index_name
            self.z3.observe(np.asarray(bins), np.asarray(zs))

    def merge(self, other: "StatsStore") -> "StatsStore":
        """Partial-sketch merge (per-shard stats -> one; the collective
        reduce analogue)."""
        self.count += other.count
        for d_name in ("minmax", "histograms", "frequencies", "topk"):
            mine, theirs = getattr(self, d_name), getattr(other, d_name)
            for k, v in theirs.items():
                if k in mine:
                    mine[k] += v
                else:
                    mine[k] = v
        if other.z3 is not None:
            if self.z3 is None:
                self.z3 = other.z3
                self.z_index = other.z_index
            else:
                self.z3 += other.z3
        return self

    # -- planner queries -------------------------------------------------
    def total_count(self) -> int:
        return self.count.count

    def estimate_scan(self, index_name: str, cfg) -> float | None:
        """Estimated rows a scan config touches (cost-model input)."""
        if self.z3 is not None and index_name == self.z_index:
            return self.z3.estimate(cfg.range_bins, cfg.range_lo, cfg.range_hi)
        return None

    def estimate_equality(self, attr: str, value) -> float | None:
        f = self.frequencies.get(attr)
        return float(f.estimate(value)) if f is not None else None

    def estimate_range(self, attr: str, lo: float, hi: float) -> float | None:
        h = self.histograms.get(attr)
        return h.estimate_range(lo, hi) if h is not None else None

    def estimate_bbox(self, geom: str, x0, y0, x1, y1) -> float | None:
        """Estimated rows intersecting a bbox: :meth:`estimate_bboxes` of
        one box."""
        est = self.estimate_bboxes(geom, [(x0, y0, x1, y1)])
        return None if est is None else float(est[0])

    def estimate_bboxes(self, geom: str, boxes) -> "np.ndarray | None":
        """Estimated rows intersecting each (x0, y0, x1, y1) box from the
        marginal coordinate histograms under independence (reference
        StatsBasedEstimator's attribute-selectivity composition), f64 a
        box. Correlated multi-cluster data can overestimate; callers treat
        this as a selectivity hint. None without the sketches."""
        hx = self.histograms.get(geom + ".x")
        hy = self.histograms.get(geom + ".y")
        n = self.total_count()
        if hx is None or hy is None or not n:
            return None
        tx = float(hx.counts.sum())
        ty = float(hy.counts.sum())
        if tx <= 0 or ty <= 0:
            return None
        b = np.asarray(boxes, dtype=np.float64).reshape(-1, 4)
        fx = hx.estimate_ranges(b[:, 0], b[:, 2]) / tx
        fy = hy.estimate_ranges(b[:, 1], b[:, 3]) / ty
        return n * fx * fy

    def estimate_filter(self, sft, f) -> float | None:
        """Selectivity-product estimate for a filter's spatial and temporal
        parts: :meth:`estimate_extractions` of one filter."""
        from geomesa_tpu.filter.extract import extract_filter

        return self.estimate_extractions(
            sft, [extract_filter(f, sft.geom_field, sft.dtg_field)]
        )[0]

    def estimate_extractions(self, sft, extractions: list) -> "list[float | None]":
        """Selectivity-product estimate of each filter's spatial and
        temporal parts (``filter.extract.extract_filter`` of the type's
        geom and date fields): bbox marginals x date-histogram fraction,
        every filter's boxes and intervals in one histogram pass each.
        None when neither axis is constrained or sketches are missing."""
        n = self.total_count()
        out: "list[float | None]" = [None] * len(extractions)
        if not n or sft.geom_field is None:
            return out
        boxes = [b for ex in extractions if not ex.geoms.disjoint for b in ex.bounds]
        parts = self.estimate_bboxes(sft.geom_field, boxes) if boxes else None
        h = None if sft.dtg_field is None else self.histograms.get(sft.dtg_field)
        total = float(h.counts.sum()) if h is not None else 0.0
        spans = [
            (float(iv.lo), float(iv.hi))
            for ex in extractions if not ex.geoms.disjoint
            and ex.intervals is not None and not ex.intervals.disjoint
            for iv in ex.intervals.values
        ] if total > 0 else []
        in_span = h.estimate_ranges(*zip(*spans)).tolist() if spans else []
        b = s = 0
        for k, ex in enumerate(extractions):
            if ex.geoms.disjoint:
                out[k] = 0.0
                continue
            ivs = ex.intervals
            nb = len(ex.bounds)
            ni = len(ivs.values) if spans and ivs is not None and not ivs.disjoint else 0
            b, s, b0, s0 = b + nb, s + ni, b, s
            if nb and parts is None:
                continue  # boxes without the coordinate sketches: no estimate
            est = min(float(np.sum(parts[b0:b])), float(n)) if nb else None
            if ivs is not None and ivs.disjoint:
                est = 0.0
            elif ni:
                frac = min(sum(in_span[s0:s]) / total, 1.0)
                est = n * frac if est is None else est * frac
            out[k] = est
        return out

    def attribute_bounds(self, attr: str):
        mm = self.minmax.get(attr)
        return mm.bounds if mm is not None else None

    def to_json(self) -> dict:
        return {
            "count": self.count.to_json(),
            "minmax": {k: v.to_json() for k, v in self.minmax.items()},
            "topk": {k: v.to_json() for k, v in self.topk.items()},
        }
