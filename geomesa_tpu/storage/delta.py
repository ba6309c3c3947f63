"""LSM-style delta tier: recent writes live host-side until compaction.

The reference gets incremental sorted inserts for free from its KV backends
(Accumulo/HBase memtables + minor compaction); the TPU analogue is a small
host-resident unsorted delta per index that absorbs appends, scanned
exactly with vectorized NumPy, while the big sorted device table (the
"SSTable") only rebuilds when the delta outgrows its threshold — write()
cost is proportional to the batch, not the table (SURVEY §7 hard part (c);
reference Lambda hot/cold tiering, lambda/data/LambdaDataStore.scala).

Delta hits are always re-refined by the planner (certain=False): the host
predicate here mirrors the kernel's *wide* semantics.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.index.api import ScanConfig, WriteKeys
from geomesa_tpu.obs.trace import add as _oadd
from geomesa_tpu.obs.trace import event as _oevent
from geomesa_tpu.storage.table import RowSpans


def concat_keys(parts: list[WriteKeys], consume: bool = False) -> WriteKeys:
    """Concatenate per-chunk write keys. ``consume=True`` releases each
    part's arrays as their column finishes concatenating, so the transient
    peak is one column set + one column — NOT the full doubled set. Only
    safe on parts the caller exclusively owns (the pipelined ingest's
    staged chunks); parts already published in a store may be shared with
    concurrent readers and must never be consumed."""
    if len(parts) == 1:
        return parts[0]
    names = tuple(parts[0].device_cols)
    sub = _concat_sub(parts)
    if consume:
        for p in parts:
            p.sub = None
    device_cols = {}
    for name in names:
        device_cols[name] = np.concatenate(
            [p.device_cols.pop(name) if consume else p.device_cols[name]
             for p in parts]
        )
    bins = np.concatenate([p.bins for p in parts])
    zs = np.concatenate([p.zs for p in parts])
    if consume:
        for p in parts:
            p.bins = p.bins[:0]
            p.zs = p.zs[:0]
    return WriteKeys(bins=bins, zs=zs, device_cols=device_cols, sub=sub)


def _concat_sub(parts: list[WriteKeys]) -> "np.ndarray | None":
    """Concatenate variable-width secondary sort words, zero-padding
    narrower batches to the widest word count (0 is the correct pad: a
    shorter string sorts before any extension)."""
    subs = [p.sub for p in parts]
    if all(s is None for s in subs):
        return None
    w = max(s.shape[1] for s in subs if s is not None)
    out = []
    for p, s in zip(parts, subs):
        if s is None:
            s = np.zeros((len(p.bins), w), dtype=np.uint64)
        elif s.shape[1] < w:
            s = np.pad(s, ((0, 0), (0, w - s.shape[1])))
        out.append(s)
    return np.concatenate(out)


def delta_wide_mask(
    config: ScanConfig, keys: WriteKeys, packed_shift: "int | None" = None
) -> np.ndarray:
    """Wide-predicate mask over delta rows (bit-compatible with the kernel's
    wide plane: f32 widened boxes, per-bin windows, bbox-intersects for
    extents; value-range check for predicate-free attribute scans).
    ``packed_shift``: the keyspace's packed-time tick shift (tw column)."""
    cols = keys.device_cols
    n = len(keys.zs)
    m = np.ones(n, dtype=bool)
    if config.boxes is not None:
        if "gxmin" in cols:
            hit = np.zeros(n, dtype=bool)
            for x0, y0, x1, y1 in np.asarray(config.boxes, np.float32):
                hit |= (
                    (cols["gxmin"] <= x1)
                    & (cols["gxmax"] >= x0)
                    & (cols["gymin"] <= y1)
                    & (cols["gymax"] >= y0)
                )
        else:
            x, y = cols["x"], cols["y"]
            hit = np.zeros(n, dtype=bool)
            for x0, y0, x1, y1 in np.asarray(config.boxes, np.float32):
                hit |= (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
        m &= hit
    if config.windows is not None:
        if "tw" in cols:
            # packed-time delta rows: wide tick semantics (floor), same
            # as the kernel — refinement stays exact (delta hits are
            # always uncertain)
            from geomesa_tpu.index.z3 import unpack_tw, windows_to_ticks

            tb, to = unpack_tw(cols["tw"])
            wins = windows_to_ticks(config.windows, packed_shift, inner=False)
        else:
            tb, to = cols["tbin"], cols["toff"]
            wins = config.windows
        hit = np.zeros(n, dtype=bool)
        for b, lo, hi in np.asarray(wins, np.int64):
            hit |= (tb == b) & (to >= lo) & (to <= hi)
        m &= hit
    if config.boxes is None and config.windows is None:
        # pure range scan (attribute primary): match the sort-key ranges
        hit = np.zeros(n, dtype=bool)
        zs = keys.zs
        for b, lo, hi in zip(
            config.range_bins.tolist(),
            config.range_lo.tolist(),
            config.range_hi.tolist(),
        ):
            hit |= (keys.bins == b) & (zs >= lo) & (zs <= hi)
        m &= hit
    elif config.clip_rows:
        # attribute index with secondary predicate: rows must also be in a
        # value range
        hit = np.zeros(n, dtype=bool)
        zs = keys.zs
        for b, lo, hi in zip(
            config.range_bins.tolist(),
            config.range_lo.tolist(),
            config.range_hi.tolist(),
        ):
            hit |= (keys.bins == b) & (zs >= lo) & (zs <= hi)
        m &= hit
    return m


def rep_xy(cols: dict, rows) -> tuple:
    """Representative coordinate per row: the point itself, or the bbox
    midpoint for extent columns — the ONE rule shared by the delta tier,
    the host adapter and (semantically) the device aggregation kernels."""
    if "x" in cols:
        return cols["x"][rows], cols["y"][rows]
    x = (cols["gxmin"][rows] + cols["gxmax"][rows]) * 0.5
    y = (cols["gymin"][rows] + cols["gymax"][rows]) * 0.5
    return x, y


def scatter_density(x, y, envelope, width: int, height: int, grid=None):
    """Clip + scatter-add points into a [height, width] f32 grid (wide
    density semantics; shared by the delta tier and the host adapter)."""
    x0, y0, x1, y1 = (float(v) for v in envelope)
    inb = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
    px = np.clip(((x - x0) / max(x1 - x0, 1e-12) * width).astype(np.int64), 0, width - 1)
    py = np.clip(((y - y0) / max(y1 - y0, 1e-12) * height).astype(np.int64), 0, height - 1)
    if grid is None:
        grid = np.zeros((height, width), np.float32)
    flat = grid.reshape(-1)
    np.add.at(flat, (py * width + px)[inb], np.float32(1))
    return flat.reshape(height, width)


class TieredTable:
    """Main device table + host delta, presenting the IndexTable scan
    surface. Delta hits are uncertain (always refined)."""

    def __init__(self, main, delta_keys: WriteKeys, base_ordinal: int):
        self.main = main
        self.delta = delta_keys
        self.base = base_ordinal
        self.keyspace = main.keyspace
        many = getattr(main, "candidate_rows_many", None)
        if many is not None:
            # :meth:`candidate_spans`' row counts of several configs, where
            # the main table searches them in one pass: its own, plus the
            # whole delta each
            self.candidate_rows_many = lambda configs: many(configs) + len(delta_keys.zs)

    @property
    def n(self) -> int:
        return self.main.n + len(self.delta.zs)

    def _delta_hits(self, config: ScanConfig) -> np.ndarray:
        """Table ordinals of the delta rows the wide predicate keeps.
        Under a caller's span (the planner's ``scan`` or ``agg``) the
        host scan is the segment ``delta``, which runs on to the span's
        end (the concatenation onto the device's rows, the density
        scatter), and the span counts ``delta_rows`` and ``delta_hits``:
        what tells the NumPy scan from the device's."""
        if config.disjoint or len(self.delta.zs) == 0:
            return np.zeros(0, np.int64)
        _oevent("delta")
        hits = self.base + np.flatnonzero(
            delta_wide_mask(
                config, self.delta,
                packed_shift=getattr(self.keyspace, "packed_time", None),
            )
        )
        _oadd("delta_rows", len(self.delta.zs))
        _oadd("delta_hits", len(hits))
        return hits

    def scan(self, config: ScanConfig, deadline=None):
        return self.scan_submit(config, deadline=deadline)()

    def scan_submit(self, config: ScanConfig, deadline=None):
        """Pipelined scan (see IndexTable.scan_submit): the device main-
        table scan dispatches now; the host delta scan runs at finish."""
        finish_main = self.main.scan_submit(config, deadline=deadline)

        def finish():
            ordinals, certain = finish_main()
            d = self._delta_hits(config)
            if len(d) == 0:
                return ordinals, certain
            return (
                np.concatenate([ordinals, d]),
                np.concatenate([certain, np.zeros(len(d), bool)]),
            )

        return finish

    def scan_submit_many(self, configs, deadline=None):
        """Fused multi-query scan over the main table (one kernel dispatch
        per variant chunk — IndexTable.scan_submit_many), each query's
        host delta hits appended at its finish like scan_submit. Returns
        one finish() per config (lazy per-member decode preserved)."""
        fins_main = self.main.scan_submit_many(configs, deadline=deadline)

        def make_finish(config, fin):
            def finish():
                ordinals, certain = fin()
                d = self._delta_hits(config)
                if len(d):
                    ordinals = np.concatenate([ordinals, d])
                    certain = np.concatenate([certain, np.zeros(len(d), bool)])
                return ordinals, certain

            return finish

        return [make_finish(c, f) for c, f in zip(configs, fins_main)]

    def count(self, config: ScanConfig) -> int:
        return self.main.count(config) + len(self._delta_hits(config))

    def candidate_spans(self, config: ScanConfig):
        """Cost-estimator view: main spans plus the whole delta as one
        pseudo-span (a cheap upper bound — the delta is scanned linearly)."""
        spans = self.main.candidate_spans(config)
        if len(self.delta.zs):
            n = self.main.n
            spans = RowSpans(
                np.append(spans.lo, n), np.append(spans.hi, n + len(self.delta.zs))
            )
        return spans

    def bounds_stats(self, config: ScanConfig):
        cnt, env = self.main.bounds_stats(config)
        d = self._delta_hits(config)
        if len(d) == 0:
            return cnt, env
        x, y = rep_xy(self.delta.device_cols, d - self.base)
        denv = (float(x.min()), float(y.min()), float(x.max()), float(y.max()))
        if env is None:
            return cnt + len(d), denv
        return cnt + len(d), (
            min(env[0], denv[0]), min(env[1], denv[1]),
            max(env[2], denv[2]), max(env[3], denv[3]),
        )

    def density(self, config: ScanConfig, bounds, width: int, height: int):
        return self.density_submit(config, bounds, width, height)()

    def density_submit(self, config: ScanConfig, bounds, width: int, height: int):
        """Pipelined density: the main table's grid kernel dispatches now;
        finish() pulls it and scatters the host delta rows on top."""
        finish_main = self.main.density_submit(config, bounds, width, height)
        return lambda: self._density_apply_delta(
            finish_main(), config, bounds, width, height
        )

    def _density_apply_delta(self, grid, config: ScanConfig, bounds, width, height):
        d = self._delta_hits(config)
        if len(d):
            x, y = rep_xy(self.delta.device_cols, d - self.base)
            grid = scatter_density(x, y, bounds, width, height, grid=grid)
        return grid

    @property
    def nbytes_device(self) -> int:
        return self.main.nbytes_device
