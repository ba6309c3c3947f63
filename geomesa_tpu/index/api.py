"""Index key space API: write keys + scan configuration.

Reference contract: IndexKeySpace.toIndexKey / getIndexValues / getRanges /
useFullFilter (/root/reference/geomesa-index-api/src/main/scala/org/
locationtech/geomesa/index/api/IndexKeySpace.scala:23-109). Here the write
side emits columnar sort keys and device columns; the read side emits a
`ScanConfig` = host z-ranges (for tile pruning over the sorted table) plus
the device predicate arrays (the Z3Filter analogue, evaluated as one
vectorized mask over gathered tiles).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Protocol, runtime_checkable

import numpy as np

from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.predicates import Filter
from geomesa_tpu.sft import FeatureType


@dataclass
class WriteKeys:
    """Write-side output of a key space for a batch of features.

    - ``bins``: int32 [n] — coarse sort key (time bin; 0 for atemporal)
    - ``zs``:   uint64 [n] — fine sort key (z / xz sequence code)
    - ``device_cols``: name -> numpy array [n], the columns the scan kernel
      tests (f32 coords / i32 time parts / f32 bboxes)
    - ``sub``: optional uint64 [n] — secondary sort word breaking ``zs``
      ties (attribute indexes over strings: lexicode bytes 8-16, so
      equality/range predicates prune exactly past the 8-byte prefix —
      reference AttributeIndexKey lexicodes FULL values into row keys)
    """

    bins: np.ndarray
    zs: np.ndarray
    device_cols: dict
    sub: "np.ndarray | None" = None


@dataclass
class ScanConfig:
    """Read-side output: how to scan one index for one filter.

    - ``range_bins``/``range_lo``/``range_hi``: parallel arrays of covering
      z-ranges, inclusive, grouped per time bin (tile pruning input)
    - ``boxes``: f32 [B, 4] spatial boxes (xmin, ymin, xmax, ymax), widened
      one f32 ulp outward so the device mask never drops a true hit
    - ``windows``: i32 [W, 3] (bin, off_lo, off_hi) inclusive time windows,
      or None for atemporal indexes
    - ``extent_mode``: device test is bbox-*intersects* against per-feature
      bboxes (XZ indexes) rather than point-in-box
    - ``geom_precise``/``time_precise``: the device mask exactly answers the
      spatial/temporal constraint up to f32 widening (residual host
      refinement still applies exactness; these gate the `loose` fast path)
    """

    index: str
    range_bins: np.ndarray
    range_lo: np.ndarray
    range_hi: np.ndarray
    boxes: Optional[np.ndarray]
    windows: Optional[np.ndarray]
    extent_mode: bool = False
    geom_precise: bool = True
    time_precise: bool = True
    disjoint: bool = False
    # -- exactness tier (round-3; reference contained-range semantics,
    # ZN.scala:110-242, + useFullFilter, Z3IndexKeySpace.scala:240-254) --
    # per-range contained flags: rows in contained ranges are certain hits
    # when contained_exact (ranges were classified against shrunk *inner*
    # ordinals, so containment holds at f64, not just ordinal, precision)
    range_contained: Optional[np.ndarray] = None
    contained_exact: bool = False
    # inner (shrunk) predicate bounds: rows passing them are certain f64
    # hits -> host refinement touches only wide & ~inner boundary rows
    boxes_inner: Optional[np.ndarray] = None
    windows_inner: Optional[np.ndarray] = None
    # row spans are exact (attribute-index primary ranges): clip kernel
    # hits back to the spans (block granularity over-scans)
    clip_rows: bool = False
    # secondary sort-word bounds (string attribute indexes: lexicode bytes
    # 8-16): narrow the boundary tie-runs of each primary range so long
    # strings prune past the 8-byte prefix (VERDICT r4 weak #4)
    range_lo2: Optional[np.ndarray] = None
    range_hi2: Optional[np.ndarray] = None
    # device point-in-polygon tier (point tables; VERDICT r4 #2): the
    # query polygon's packed [E, 128] edge block (block_kernels.pack_edges)
    # — the kernel's spatial test is the exact even-odd parity instead of
    # the box slots, so only the f32-uncertainty band refines on host.
    # geom_precise is True with poly set, but aggregation fast paths must
    # keep gating on it (wide-plane counts would include the near band)
    # and contained-range certainty must NOT (bbox containment does not
    # imply polygon membership)
    poly: Optional[np.ndarray] = None
    # raster-interval tier (round 7, arXiv 2307.01716): the query
    # polygon's packed [1 + R, 128] interval stack
    # (filter.raster.RasterApprox.pack_block) — the kernel classifies
    # candidate rows by integer interval lookup (full cells certain-in,
    # out cells certain-out) and only the boundary residue pays the exact
    # PIP (``poly`` when set — the device residue — else host
    # refinement). With ``rast`` set the z-ranges come from the raster
    # too: full cells are *contained* ranges whose rows are certain even
    # for polygons (contained_exact is True — full-cell containment
    # implies membership, unlike bbox containment), and out cells inside
    # the bbox are pruned before any device work.
    rast: Optional[np.ndarray] = None
    # the candidate row spans of these ranges over ONE sorted table, held
    # by that table's ``scan_spans`` as (weakref to the table, spans) so
    # the planner's cost() and the dispatch that follows compute them
    # once; no part of the config's value
    _spans: Optional[tuple] = field(default=None, init=False, repr=False, compare=False)

    @staticmethod
    def empty(index: str) -> "ScanConfig":
        """A config for an unsatisfiable filter (returns nothing)."""
        return ScanConfig(
            index=index,
            range_bins=np.zeros(0, np.int32),
            range_lo=np.zeros(0, np.uint64),
            range_hi=np.zeros(0, np.uint64),
            boxes=None,
            windows=None,
            disjoint=True,
        )

    @property
    def n_ranges(self) -> int:
        return len(self.range_bins)


def widen_boxes(bounds) -> np.ndarray:
    """f64 boxes -> f32 boxes widened one ulp outward (superset semantics)."""
    b = np.asarray(bounds, dtype=np.float64).reshape(-1, 4)
    lo = np.nextafter(b[:, :2].astype(np.float32), np.float32(-np.inf))
    hi = np.nextafter(b[:, 2:].astype(np.float32), np.float32(np.inf))
    return np.concatenate([lo, hi], axis=1).astype(np.float32)


def cover_boxes(wide: np.ndarray, counts) -> list:
    """The boxes a member's key ranges have to cover: ``widen_boxes``' rows
    (what the device mask keeps) as f64, ``counts[q]`` of them to member q
    (a ``[counts[q], 4]`` array each). A box whose edge is a cell boundary
    of the curve (a WMS tile's) has rows an f32 step beyond it in cells the
    f64 box's ranges do not reach, and an aggregation counts whatever the
    mask keeps."""
    rows = wide.astype(np.float64)
    stops = np.cumsum(counts).tolist()
    return [rows[z - n:z] for n, z in zip(counts, stops)]


def shrink_boxes(bounds) -> np.ndarray:
    """f64 boxes -> f32 boxes shrunk two ulps inward (subset semantics).

    A stored f32 coordinate x32 = round(x64) differs from the true f64
    value by at most half an ulp; a point passing the 2-ulp-shrunk box test
    therefore passes the true f64 box test — the device *inner* mask, whose
    hits skip host refinement entirely."""
    b = np.asarray(bounds, dtype=np.float64).reshape(-1, 4)
    lo = b[:, :2].astype(np.float32)
    hi = b[:, 2:].astype(np.float32)
    for _ in range(2):
        lo = np.nextafter(lo, np.float32(np.inf))
        hi = np.nextafter(hi, np.float32(-np.inf))
    return np.concatenate([lo, hi], axis=1).astype(np.float32)


def expand_runs(first: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenated ``arange(first[k], first[k] + counts[k])`` over k."""
    ends = np.cumsum(counts)
    if not len(ends):
        return np.zeros(0, np.int64)
    return np.arange(int(ends[-1]), dtype=np.int64) + np.repeat(
        first - (ends - counts), counts
    )


@runtime_checkable
class IndexKeySpace(Protocol):
    """One logical index over a feature type."""

    name: str

    def supports(self, sft: FeatureType) -> bool:
        """Can this index be built for the schema?"""
        ...

    def write_keys(self, fc: FeatureCollection) -> WriteKeys:
        """Sort keys + device columns for a batch (reference toIndexKey)."""
        ...

    def scan_config(self, f: Filter) -> Optional[ScanConfig]:
        """Scan configuration for a filter, or None when this index cannot
        serve it (reference getIndexValues + getRanges). An index may also
        offer ``scan_configs(extractions, max_ranges=None)`` (z3, z2 and
        the attribute index do; the extent indexes and s2 do not): one
        config or None an ``filter.extract.Extraction``, all of them
        decomposed in one pass (the point indexes' boxes and windows in
        one native call, the attribute index's value bounds in one
        lexicode), which the planner's ``plan_many`` uses for a batch
        (``max_ranges``: the most ranges a decomposition may emit, for the
        branches of one query that share its range target; no part of a
        value range); ``scan_config`` is then its one-member case."""
        ...
