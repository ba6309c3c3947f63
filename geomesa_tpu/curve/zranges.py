"""Decomposition of query boxes into covering Z-curve ranges.

Functional parity with the reference's ZN.zranges
(/root/reference/geomesa-z3/src/main/scala/org/locationtech/geomesa/zorder/sfcurve/ZN.scala:110-242):
breadth-first quad/oct-tree traversal from the longest common prefix of the
query corners, emitting:

- *contained* ranges: curve cells fully inside every queried dimension
  interval (rows in them need no further spatial/temporal filtering), and
- *overlapping* ranges: cells that straddle the query boundary (rows need
  the per-row membership test — on TPU, the scan kernel mask).

The traversal is budgeted: `max_ranges` caps output size (reference default
``geomesa.scan.ranges.target`` = 2000, QueryProperties.scala) and
`max_recurse` caps depth (ZN.DefaultRecurse = 7 levels past the common
prefix). When the budget is hit, remaining cells are emitted as coarse
overlapping ranges — always a superset of the query, never a miss.

Host-side pure Python/NumPy: this runs once per query over thousands of
cells, not per row. Keeping range count bounded keeps the device scan grid
static-shaped for XLA (SURVEY.md hard part (d)).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

from geomesa_tpu.curve.zorder import _ZN, longest_common_prefix, zdiv  # noqa: F401

DEFAULT_MAX_RECURSE = 7


@dataclass(frozen=True)
class IndexRange:
    """Inclusive z-range [lower, upper]; contained = no row filter needed."""

    lower: int
    upper: int
    contained: bool


@dataclass(frozen=True)
class ZBox:
    """A query box in z-space: per-dimension normalized [min, max] ordinals."""

    mins: tuple[int, ...]
    maxes: tuple[int, ...]


def with_inner(mins: np.ndarray, maxes: np.ndarray, inner: bool):
    """``(mins, maxes, imins, imaxes)`` for :func:`zranges_arrays` from
    the boxes' corner ordinals (u64, dimensions last). ``inner`` adds the
    boxes shrunk 2 cells inward per dimension (None, None otherwise); a
    box under 4 cells wide inverts: never contained."""
    if not inner:
        return mins, maxes, None, None
    two = np.uint64(2)
    return mins, maxes, mins + two, np.maximum(maxes, two) - two


def pad_corners(corners: list, dims: int) -> np.ndarray:
    """u64 ``[nq, nbox, dims]`` for :func:`zranges_arrays_each` from one
    list of corner ordinals a query, ``nbox`` the longest: a shorter list
    repeats its last corner (with mins and maxes padded alike the box
    repeats: the same union, and so the same decomposition)."""
    nbox = max((len(c) for c in corners), default=0)
    if any(len(c) != nbox for c in corners):
        corners = [c + c[-1:] * (nbox - len(c)) for c in corners]
    return np.array(corners, dtype=np.uint64).reshape(len(corners), nbox, dims)


# boxes in all (every query's summed) up to which the corner ordinals come
# out of the scalar loop: under it NumPy's call overhead costs more than a
# ``normalize_one`` a corner (a viewport's one box, a join's polygon); past
# it (a tube's sixteen groups of sixteen boxes) one vector call a dimension
SCALAR_CORNERS = 32


def box_list(boxes) -> list:
    """A query's boxes for the scalar loop: an array's rows as lists of
    Python floats (what a list of tuples holds already)."""
    return boxes.tolist() if isinstance(boxes, np.ndarray) else boxes


def stack_boxes(bounds: list) -> np.ndarray:
    """Every query's boxes as ONE f64 ``[B, 4]`` array, query q's rows
    after query q-1's; a query's boxes are a sequence of (xmin, ymin,
    xmax, ymax) or an array of such rows (what the extraction of a
    ``filter.predicates.Slices`` carrier holds)."""
    if not bounds:
        return np.zeros((0, 4))
    return np.concatenate([np.asarray(b, dtype=np.float64).reshape(-1, 4) for b in bounds])


def box_rows(bounds: list) -> tuple[np.ndarray, np.ndarray]:
    """:func:`stack_boxes` of ``bounds``, none inverted, and the boxes a
    query i64 ``[nq]``."""
    flat = stack_boxes(bounds)
    bad = (flat[:, 0] > flat[:, 2]) | (flat[:, 1] > flat[:, 3])
    if bad.any():
        raise ValueError(f"inverted bbox: {tuple(flat[np.argmax(bad)].tolist())}")
    return flat, np.array([len(b) for b in bounds], dtype=np.int64)


def pad_rows(corners: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """:func:`pad_corners` of corner ordinals ``[B, dims]`` laid out as
    :func:`box_rows` lays boxes out: u64 ``[nq, nbox, dims]``, a query
    with fewer boxes than the most repeating its last."""
    nbox = int(counts.max()) if len(counts) else 0
    first = np.cumsum(counts) - counts
    at = first[:, None] + np.minimum(np.arange(nbox)[None, :], counts[:, None] - 1)
    return corners[at].astype(np.uint64)


def ranges_from_arrays(lower, upper, contained) -> list[IndexRange]:
    """The object view of :func:`zranges_arrays`' result, for callers that
    want one ``IndexRange`` a range (tests, ``explain``); the plan path
    keeps the arrays."""
    return [
        IndexRange(lo, hi, c)
        for lo, hi, c in zip(lower.tolist(), upper.tolist(), contained.tolist())
    ]


def zranges(
    curve,
    boxes: Sequence[ZBox],
    max_ranges: int | None = None,
    max_recurse: int | None = None,
    inner_boxes: "Sequence[ZBox] | None" = None,
) -> list[IndexRange]:
    """Covering z-ranges for the union of ``boxes`` on ``curve``, as
    objects: a view of :func:`zranges_arrays`, which documents the
    arguments. ``inner_boxes`` is aligned with ``boxes``."""
    if not boxes:
        return []
    mins = np.array([b.mins for b in boxes], dtype=np.uint64)  # [nbox, dims]
    maxes = np.array([b.maxes for b in boxes], dtype=np.uint64)
    if inner_boxes is None:
        imins = imaxes = None
    else:
        imins = np.array([b.mins for b in inner_boxes], dtype=np.uint64)
        imaxes = np.array([b.maxes for b in inner_boxes], dtype=np.uint64)
    return ranges_from_arrays(
        *zranges_arrays(curve, mins, maxes, imins, imaxes, max_ranges, max_recurse)
    )


def zranges_arrays(
    curve,
    mins: np.ndarray,
    maxes: np.ndarray,
    imins: "np.ndarray | None" = None,
    imaxes: "np.ndarray | None" = None,
    max_ranges: int | None = None,
    max_recurse: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Covering z-ranges for a union of boxes on ``curve``:
    ``(lower u64[k], upper u64[k], contained bool[k])``, sorted by lower.

    curve: Z2 or Z3 from geomesa_tpu.curve.zorder (needs .dims,
    .bits_per_dim, .index, .decode). ``mins`` / ``maxes`` are the boxes'
    per-dimension normalized ordinals, u64 ``[nbox, dims]``.

    ``imins`` / ``imaxes`` (aligned with the boxes) classify
    *containment*: a cell is contained only when fully inside some inner
    box. Callers pass boxes shrunk below the f64 query bounds so
    contained-range rows are certain hits needing no refinement; default
    (None) classifies against the outer boxes — ordinal-level
    containment, the reference ZN.zranges behavior. Inner boxes may be
    inverted (mins > maxes) to mean "never contained".
    """
    inner = imins is not None
    return zranges_arrays_each(
        curve, mins[None], maxes[None],
        imins[None] if inner else None, imaxes[None] if inner else None,
        max_ranges, max_recurse,
    )[:3]


def zranges_arrays_each(
    curve,
    mins: np.ndarray,
    maxes: np.ndarray,
    imins: "np.ndarray | None" = None,
    imaxes: "np.ndarray | None" = None,
    max_ranges: int | None = None,
    max_recurse: int | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """:func:`zranges_arrays` for ``nq`` unions of ``nbox`` boxes each
    (u64 ``[nq, nbox, dims]``), one decomposition a union, all in one
    native call: ``(lower, upper, contained, counts i64[nq])`` with union
    q's ranges after union q-1's, ``counts[q]`` of them. ``max_ranges``
    bounds each union on its own."""
    nq, nbox = mins.shape[0], mins.shape[1]
    if nq == 0 or nbox == 0:
        return (*ranges_to_arrays([]), np.zeros(nq, dtype=np.int64))
    if max_ranges is None:
        from geomesa_tpu.conf import SCAN_RANGES_TARGET

        max_ranges = SCAN_RANGES_TARGET.get()
    if max_ranges < 1:
        raise ValueError(f"max_ranges must be >= 1: {max_ranges}")
    max_recurse = DEFAULT_MAX_RECURSE if max_recurse is None else max_recurse
    inverted = mins > maxes
    if inverted.any():
        q, b, d = np.argwhere(inverted)[0]
        raise ValueError(
            f"inverted box on dim {d}: {tuple(mins[q, b].tolist())} > "
            f"{tuple(maxes[q, b].tolist())}"
        )
    if imins is None:
        imins, imaxes = mins, maxes

    from geomesa_tpu import native

    nat = native.zranges(
        curve.dims, curve.bits_per_dim, mins, maxes, imins, imaxes,
        max_ranges, max_recurse,
    )
    if nat is not None:
        return nat
    each = [
        _zranges_py(
            curve, mins[q], maxes[q], imins[q], imaxes[q], max_ranges, max_recurse
        )
        for q in range(nq)
    ]
    return (
        *ranges_to_arrays([r for ranges in each for r in ranges]),
        np.array([len(ranges) for ranges in each], dtype=np.int64),
    )


def _zranges_py(
    curve, mins, maxes, imins, imaxes, max_ranges: int, max_recurse: int
) -> list[IndexRange]:
    """The decomposition in plain Python: what runs without the native
    library, and the reference the native tier is tested against."""
    dims = curve.dims
    children = 1 << dims
    zmins = [int(curve.index(*row)) for row in mins.tolist()]
    zmaxes = [int(curve.index(*row)) for row in maxes.tolist()]

    # longest common prefix over all corner z-values, aligned to dims bits
    lcp = longest_common_prefix(curve, *(zmins + zmaxes))
    offset = lcp.offset
    prefix = lcp.prefix

    ranges: list[IndexRange] = []

    def cell_bounds(z_prefix: int, level_bits: int) -> tuple[np.ndarray, np.ndarray]:
        """Per-dimension [lo, hi] ordinals of the cell with the given prefix;
        level_bits = number of low bits free within the cell."""
        zmin = z_prefix
        zmax = z_prefix | ((1 << level_bits) - 1)
        lo = np.array(curve.decode(np.uint64(zmin)), dtype=np.uint64)
        hi = np.array(curve.decode(np.uint64(zmax)), dtype=np.uint64)
        return lo, hi

    def classify(lo: np.ndarray, hi: np.ndarray) -> int:
        """2 = fully contained in some inner box, 1 = overlaps some box,
        0 = disjoint."""
        contained = np.all((lo >= imins) & (hi <= imaxes), axis=1)
        if contained.any():
            return 2
        overlaps = np.all((lo <= maxes) & (hi >= mins), axis=1)
        if overlaps.any():
            return 1
        return 0

    # BFS over cells. Each entry: (z_prefix, free_bits)
    level = [(prefix, offset)]
    recursions = 0
    while level and recursions < max_recurse and len(ranges) + len(level) * children < max_ranges * 2:
        nxt: list[tuple[int, int]] = []
        for z_prefix, free_bits in level:
            if free_bits == 0:
                lo, hi = cell_bounds(z_prefix, 0)
                c = classify(lo, hi)
                if c:
                    ranges.append(IndexRange(z_prefix, z_prefix, c == 2))
                continue
            child_bits = free_bits - dims
            for q in range(children):
                child_prefix = z_prefix | (q << child_bits)
                lo, hi = cell_bounds(child_prefix, child_bits)
                c = classify(lo, hi)
                if c == 2:
                    ranges.append(
                        IndexRange(child_prefix, child_prefix | ((1 << child_bits) - 1), True)
                    )
                elif c == 1:
                    if child_bits == 0:
                        ranges.append(IndexRange(child_prefix, child_prefix, False))
                    else:
                        nxt.append((child_prefix, child_bits))
        level = nxt
        recursions += 1

    # budget exhausted: emit remaining cells as coarse overlapping ranges
    for z_prefix, free_bits in level:
        ranges.append(IndexRange(z_prefix, z_prefix | ((1 << free_bits) - 1), False))

    merged = merge_ranges(ranges, max_ranges)
    return _tighten_ranges(curve, merged, zmins, zmaxes, mins, maxes)


def _tighten_ranges(
    curve,
    ranges: list[IndexRange],
    zmins: list[int],
    zmaxes: list[int],
    mins: np.ndarray,
    maxes: np.ndarray,
) -> list[IndexRange]:
    """Shrink range endpoints to in-union z-values via LITMAX/BIGMIN.

    The reference invokes zdiv from its range decomposition to skip the gap
    at a miss (ZN.scala:309-361 called from the zranges loop); here the BFS
    classifies whole cells, so the equivalent tightening runs as a post-pass
    against the union of query boxes: each range's lower endpoint advances to
    the smallest z >= it inside *some* box (min of per-box BIGMINs), the
    upper retracts to the largest z <= it inside some box (max of per-box
    LITMAXs), and ranges containing no in-union z are dropped. In Morton
    order the z of a box's min/max corner is that box's global min/max z,
    which bounds the per-box candidate search.
    """

    def in_box(z: int, b: int) -> bool:
        pt = np.array(curve.decode(np.uint64(z)), dtype=np.uint64)
        return bool(np.all(pt >= mins[b]) & np.all(pt <= maxes[b]))

    nbox = len(zmins)
    out: list[IndexRange] = []
    for r in ranges:
        lo_cands: list[int] = []
        hi_cands: list[int] = []
        for b in range(nbox):
            zmin, zmax = zmins[b], zmaxes[b]
            if zmax < r.lower or zmin > r.upper:
                continue  # box b has no z in this range's window at all
            # smallest z of box b that is >= r.lower
            if r.lower <= zmin:
                cand = zmin
            elif in_box(r.lower, b):
                cand = r.lower
            else:
                _, cand = zdiv(curve, zmin, zmax, r.lower)
            if cand <= r.upper:
                lo_cands.append(cand)
            # largest z of box b that is <= r.upper
            if r.upper >= zmax:
                cand = zmax
            elif in_box(r.upper, b):
                cand = r.upper
            else:
                cand, _ = zdiv(curve, zmin, zmax, r.upper)
            if cand >= r.lower:
                hi_cands.append(cand)
        if not lo_cands or not hi_cands:
            continue
        lo, hi = min(lo_cands), max(hi_cands)
        if lo > hi:
            continue
        out.append(IndexRange(lo, hi, r.contained))
    return out


def merge_ranges(ranges: list[IndexRange], max_ranges: int | None = None) -> list[IndexRange]:
    """Sort, merge overlapping/adjacent ranges, and reduce below max_ranges
    by closing the smallest gaps first (over-covering, never dropping).

    Reference: the sort+merge at the tail of ZN.zranges (ZN.scala:198-242).
    """
    if not ranges:
        return []
    ranges = sorted(ranges, key=lambda r: (r.lower, r.upper))
    merged: list[IndexRange] = [ranges[0]]
    for r in ranges[1:]:
        last = merged[-1]
        # merge only same-kind neighbors: a contained range keeps its
        # no-refinement guarantee instead of degrading when glued to an
        # overlapping one (BFS cells are disjoint, so ranges only touch)
        if r.lower <= last.upper + 1 and r.contained == last.contained:
            merged[-1] = IndexRange(last.lower, max(last.upper, r.upper), last.contained)
        else:
            merged.append(r)
    if max_ranges is not None and len(merged) > max_ranges:
        # close smallest gaps until under budget
        gaps = np.array(
            [merged[i + 1].lower - merged[i].upper for i in range(len(merged) - 1)]
        )
        k = len(merged) - max_ranges
        cutoff_idx = np.argpartition(gaps, k - 1)[:k]
        close = np.zeros(len(gaps), dtype=bool)
        close[cutoff_idx] = True
        out: list[IndexRange] = [merged[0]]
        for i, r in enumerate(merged[1:]):
            if close[i]:
                last = out[-1]
                out[-1] = IndexRange(last.lower, max(last.upper, r.upper), False)
            else:
                out.append(r)
        merged = out
    return merged


def ranges_to_arrays(ranges: list[IndexRange]):
    """(lower u64[n], upper u64[n], contained bool[n]) arrays for searchsorted."""
    lo = np.array([r.lower for r in ranges], dtype=np.uint64)
    hi = np.array([r.upper for r in ranges], dtype=np.uint64)
    contained = np.array([r.contained for r in ranges], dtype=bool)
    return lo, hi, contained
