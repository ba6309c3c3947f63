"""Filter predicate AST with vectorized (columnar) evaluation.

The reference represents queries as GeoTools/ECQL `Filter` trees and
evaluates them per-feature through JTS + FastFilterFactory
(/root/reference/geomesa-filter/src/main/scala/org/locationtech/geomesa/
filter/factory/FastFilterFactory.scala). The TPU redesign keeps the same
logical algebra (And/Or/Not over spatial, temporal, attribute and id
predicates) but evaluation is *columnar*: ``Filter.evaluate(batch)`` returns
a boolean mask over a whole batch of features at once. The device scan
kernels implement the same semantics over jnp columns for the push-down
tier; this host path is the exactness reference and the fallback for
predicates the device can't run.

Geometry columns in a batch are either a ``PointColumn`` (struct-of-arrays
x/y — the point fast path) or a ``PackedGeometryColumn`` (extents).
"""

from __future__ import annotations

import hashlib
import re
import time
from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from geomesa_tpu import geometry as geo
from geomesa_tpu.obs.trace import tracer as _otracer


@dataclass(frozen=True)
class PointColumn:
    """Struct-of-arrays geometry column for point features."""

    x: np.ndarray
    y: np.ndarray

    def __len__(self) -> int:
        return len(self.x)


GeometryColumn = "PointColumn | geo.PackedGeometryColumn"


class Filter:
    """Base predicate. Subclasses are frozen dataclasses."""

    def evaluate(self, batch: Mapping[str, object]) -> np.ndarray:
        """Boolean mask over the batch (dict: attr name -> column)."""
        raise NotImplementedError

    # -- algebra sugar ---------------------------------------------------
    def __and__(self, other: "Filter") -> "Filter":
        return And((self, other))

    def __or__(self, other: "Filter") -> "Filter":
        return Or((self, other))

    def __invert__(self) -> "Filter":
        return Not(self)


def _batch_len(batch: Mapping[str, object]) -> int:
    for v in batch.values():
        if isinstance(v, (PointColumn, geo.PackedGeometryColumn)):
            return len(v)
        return len(v)
    return 0


def _column(batch: Mapping[str, object], prop: str) -> np.ndarray:
    try:
        return batch[prop]
    except KeyError:
        raise KeyError(f"no column {prop!r} in batch (have {list(batch)})")


@dataclass(frozen=True)
class Include(Filter):
    """Matches everything (ECQL INCLUDE)."""

    def evaluate(self, batch):
        return np.ones(_batch_len(batch), dtype=bool)


@dataclass(frozen=True)
class Exclude(Filter):
    """Matches nothing (ECQL EXCLUDE)."""

    def evaluate(self, batch):
        return np.zeros(_batch_len(batch), dtype=bool)


INCLUDE = Include()
EXCLUDE = Exclude()


# ---------------------------------------------------------------------------
# spatial
# ---------------------------------------------------------------------------


def _ulp_out(x0: float, y0: float, x1: float, y1: float):
    """Bounds widened one f32 ulp outward — matching the widening the
    packed column applied to its stored bboxes, so bbox prefilters built
    on >=/<= comparisons stay conservative."""
    lo = np.nextafter(np.array([x0, y0], dtype=np.float32), -np.inf).astype(np.float64)
    hi = np.nextafter(np.array([x1, y1], dtype=np.float32), np.inf).astype(np.float64)
    return float(lo[0]), float(lo[1]), float(hi[0]), float(hi[1])


def _eval_spatial(col, fn_points, fn_geom, candidates=None) -> np.ndarray:
    """Exact per-geometry evaluation over a packed column, restricted to
    ``candidates`` (a bool mask from a vectorized bbox prefilter — rows
    outside it are definitively False)."""
    if isinstance(col, PointColumn):
        return fn_points(col.x, col.y)
    if isinstance(col, geo.PackedGeometryColumn):
        out = np.zeros(len(col), dtype=bool)
        rows = range(len(col)) if candidates is None else np.nonzero(candidates)[0]
        for i in rows:
            out[i] = fn_geom(col.geometry(int(i)))
        return out
    raise TypeError(f"not a geometry column: {type(col)}")


def _exact_tier(col, g, rows, out, span) -> None:
    """The exact tier of an intersects over a packed column: ``out[rows]``
    = whether each of those geometries intersects ``g``, decided by
    ``geo.intersects_rows`` in one batched pass over the column's arrays
    (points, multipoints and a query without rings: a geometry at a time).
    ``span`` is the thread's active span or None (the planner's ``decode``
    when the refinement runs under a trace): it gets ``refine_exact``
    (geometries decided here), ``refine_batched`` (those of them the
    batched pass decided) and, where there are any, ``refine_exact_s``
    (wall seconds in the tier)."""
    t0 = time.perf_counter() if span is not None else 0.0
    out[rows] = geo.intersects_rows(col, rows, g)
    if span is not None:
        span.add("refine_exact", len(rows))
        span.add("refine_batched", int(geo.flat_form_rows(col, rows, g).sum()))
        if len(rows):
            span.add("refine_exact_s", time.perf_counter() - t0)


def _per_geom_vertex_counts(col: "geo.PackedGeometryColumn", vertex_mask):
    """How many of each geometry's pool vertices satisfy ``vertex_mask``
    ([total_verts] bool) — the cumsum reduction over the contiguous
    per-geometry coord slices."""
    csum = np.concatenate([[0], np.cumsum(vertex_mask)])
    first_ring = col.part_ring_offsets[col.geom_part_offsets].astype(np.int64)
    bounds_ix = col.ring_offsets[first_ring].astype(np.int64)
    return csum[bounds_ix[1:]] - csum[bounds_ix[:-1]]


@dataclass(frozen=True)
class BBox(Filter):
    """BBOX(prop, xmin, ymin, xmax, ymax) — geometry interacts with the box.

    Reference: the `bbox` spatial op extracted by FilterHelper
    (geomesa-filter/.../FilterHelper.scala:100-130).
    """

    prop: str
    xmin: float
    ymin: float
    xmax: float
    ymax: float

    @property
    def bounds(self) -> tuple[float, float, float, float]:
        return (self.xmin, self.ymin, self.xmax, self.ymax)

    def evaluate(self, batch):
        col = _column(batch, self.prop)
        if isinstance(col, PointColumn):
            return (
                (col.x >= self.xmin)
                & (col.x <= self.xmax)
                & (col.y >= self.ymin)
                & (col.y <= self.ymax)
            )
        if isinstance(col, geo.PackedGeometryColumn):
            q = np.array(self.bounds)
            bx = geo.box(*self.bounds)
            return _packed_box_intersects(col, q, bx)
        raise TypeError(f"not a geometry column: {type(col)}")


def _packed_box_intersects(
    col: "geo.PackedGeometryColumn", q: np.ndarray, g: "geo.Geometry"
) -> np.ndarray:
    """Geometry-intersects-axis-aligned-box over a packed column.

    Rectangle features (geometry == bbox: footprints, tiles, extents)
    resolve exactly with vectorized f64 bbox algebra; of the others a
    vertex inside the box accepts, and the rest go to the exact tier."""
    rough = geo.bbox_intersects(col.bboxes.astype(np.float64), q)
    bmask, bb = col.box_info()
    out = (
        bmask
        & (bb[:, 0] <= q[2]) & (bb[:, 2] >= q[0])
        & (bb[:, 1] <= q[3]) & (bb[:, 3] >= q[1])
    )
    hard = rough & ~bmask
    n_hard = int(hard.sum())
    span = _otracer().current()
    if span is not None:
        # every row is decided by exactly one tier: rectangle algebra (a
        # rectangle feature, or a bbox that misses the query's), the
        # vertex accept tier, or the exact tier; refine_hits = rows kept
        span.add("refine_rect", len(col) - n_hard)
    if n_hard:
        # vectorized accept tier for arbitrary (non-rectangle) geometries:
        # the query here is ALWAYS an axis-aligned rect (both call sites
        # gate on is_rectangle), so any geometry VERTEX inside it proves
        # intersection. Each geometry's coords are one contiguous pool
        # slice; a cumsum turns the per-vertex test into per-geometry
        # counts. Only vertex-free overlaps (rect fully inside the
        # geometry, or pure edge crossings) fall to the exact tier.
        c = col.coords
        inb = (
            (c[:, 0] >= q[0]) & (c[:, 0] <= q[2])
            & (c[:, 1] >= q[1]) & (c[:, 1] <= q[3])
        )
        any_vertex = _per_geom_vertex_counts(col, inb) > 0
        out |= hard & any_vertex
        rest = np.nonzero(hard & ~any_vertex)[0]
        if span is not None:
            span.add("refine_accept", n_hard - len(rest))
        _exact_tier(col, g, rest, out, span)
    if span is not None:
        span.add("refine_hits", int(out.sum()))
    return out


@dataclass(frozen=True)
class Intersects(Filter):
    """INTERSECTS(prop, <geometry>)."""

    prop: str
    geom: geo.Geometry

    def evaluate(self, batch):
        col = _column(batch, self.prop)
        g = self.geom
        if isinstance(col, PointColumn):
            # vectorized bbox prefilter bounds the per-point work to
            # near-hit points (the Python loops below are exact but slow)
            x0, y0, x1, y1 = g.bounds()
            near = (col.x >= x0) & (col.x <= x1) & (col.y >= y0) & (col.y <= y1)
            if isinstance(g, (geo.Polygon, geo.MultiPolygon)):
                inside = np.zeros(len(col), dtype=bool)
                ni = np.nonzero(near)[0]
                inside[ni] = geo.points_in_polygon(col.x[ni], col.y[ni], g)
                # boundary counts for intersects — vectorized over the
                # near-but-not-inside candidates (a per-point loop here
                # cost seconds on dense bbox-near outside regions)
                nb = ni[~inside[ni]]
                if len(nb):
                    inside[nb] = geo.points_on_boundary(
                        col.x[nb], col.y[nb], g
                    )
                return inside
            out = np.zeros(len(col), dtype=bool)
            for i in np.nonzero(near)[0]:
                out[i] = geo.intersects(geo.Point(float(col.x[i]), float(col.y[i])), g)
            return out
        if isinstance(col, geo.PackedGeometryColumn):
            q = np.array(g.bounds())
            if geo.is_rectangle(g):
                return _packed_box_intersects(col, q, g)
            rough = geo.bbox_intersects(col.bboxes.astype(np.float64), q)
            out = np.zeros(len(col), dtype=bool)
            n_rough = int(rough.sum())
            span = _otracer().current()
            if span is not None:
                # the tiers as _packed_box_intersects counts them; here
                # rectangle algebra decides only the bboxes that miss
                span.add("refine_rect", len(col) - n_rough)
            if n_rough and isinstance(g, (geo.Polygon, geo.MultiPolygon)):
                # accept tier for a POLYGON query over arbitrary features:
                # any feature vertex inside the query polygon proves
                # intersection (one native ray cast over the coords pool)
                c = col.coords
                inside = geo.points_in_polygon(c[:, 0], c[:, 1], g)
                n_in = _per_geom_vertex_counts(col, inside)
                out |= rough & (n_in > 0)
                rough &= ~out
                if span is not None:
                    span.add("refine_accept", n_rough - int(rough.sum()))
            _exact_tier(col, g, np.nonzero(rough)[0], out, span)
            if span is not None:
                span.add("refine_hits", int(out.sum()))
            return out
        raise TypeError(f"not a geometry column: {type(col)}")


@dataclass(frozen=True)
class Within(Filter):
    """WITHIN(prop, <geometry>): the feature lies within the query geometry."""

    prop: str
    geom: geo.Geometry

    def evaluate(self, batch):
        col = _column(batch, self.prop)
        g = self.geom
        if not isinstance(g, (geo.Polygon, geo.MultiPolygon)):
            raise ValueError("WITHIN requires a polygonal query geometry")
        if isinstance(col, PointColumn):
            return geo.points_in_polygon(col.x, col.y, g)
        # necessary condition, vectorized: the feature's bbox lies inside
        # the query's bbox (within implies bbox containment). Stored
        # bboxes are f32-widened one ulp OUTWARD, so the query bounds
        # widen by an ulp too — no true-within row is ever excluded;
        # extra grazers fall to the exact check below.
        x0, y0, x1, y1 = _ulp_out(*g.bounds())
        b = col.bboxes.astype(np.float64)
        cand = (b[:, 0] >= x0) & (b[:, 1] >= y0) & (b[:, 2] <= x1) & (b[:, 3] <= y1)
        if geo.is_rectangle(g):
            # two-tier for a rect query: rows whose OUTWARD-widened stored
            # bbox fits inside the RAW query bounds are definitely within
            # (true bbox subset of stored; boundary contact allowed, as
            # JTS `within` permits boundary points). Only the sub-ulp
            # boundary band (cand minus sure) needs the exact check, so
            # a protruding vertex 1 ulp past the edge is never accepted.
            rx0, ry0, rx1, ry1 = g.bounds()
            sure = (
                (b[:, 0] >= rx0) & (b[:, 1] >= ry0)
                & (b[:, 2] <= rx1) & (b[:, 3] <= ry1)
            )
            out = _eval_spatial(
                col, None, lambda feat: geo.contains(g, feat),
                candidates=cand & ~sure,
            )
            return out | sure
        return _eval_spatial(
            col, None, lambda feat: geo.contains(g, feat), candidates=cand
        )


@dataclass(frozen=True)
class Contains(Filter):
    """CONTAINS(prop, <geometry>): the feature contains the query geometry."""

    prop: str
    geom: geo.Geometry

    def evaluate(self, batch):
        col = _column(batch, self.prop)
        if isinstance(col, PointColumn):
            if isinstance(self.geom, geo.Point):
                return (col.x == self.geom.x) & (col.y == self.geom.y)
            return np.zeros(len(col), dtype=bool)
        # necessary condition, vectorized: the feature's bbox covers the
        # query geometry's bbox (stored bboxes widen outward, so the
        # direct comparison is already conservative for covering)
        x0, y0, x1, y1 = self.geom.bounds()
        b = col.bboxes.astype(np.float64)
        cand = (b[:, 0] <= x0) & (b[:, 1] <= y0) & (b[:, 2] >= x1) & (b[:, 3] >= y1)
        return _eval_spatial(
            col, None, lambda feat: isinstance(feat, (geo.Polygon, geo.MultiPolygon))
            and geo.contains(feat, self.geom),
            candidates=cand,
        )


@dataclass(frozen=True)
class DWithin(Filter):
    """DWITHIN(prop, <geometry>, distance): within planar distance."""

    prop: str
    geom: geo.Geometry
    dist: float

    def evaluate(self, batch):
        col = _column(batch, self.prop)
        if isinstance(col, PointColumn):
            if isinstance(self.geom, geo.Point):
                return np.hypot(col.x - self.geom.x, col.y - self.geom.y) <= self.dist
            # bbox prefilter: only points inside the distance-expanded
            # envelope can be within range
            x0, y0, x1, y1 = self.bounds
            near = (col.x >= x0) & (col.x <= x1) & (col.y >= y0) & (col.y <= y1)
            out = np.zeros(len(col), dtype=bool)
            for i in np.nonzero(near)[0]:
                out[i] = (
                    geo._point_geom_distance(float(col.x[i]), float(col.y[i]), self.geom)
                    <= self.dist
                )
            return out
        x0, y0, x1, y1 = _ulp_out(*self.bounds)
        b = col.bboxes.astype(np.float64)
        cand = (b[:, 0] <= x1) & (b[:, 2] >= x0) & (b[:, 1] <= y1) & (b[:, 3] >= y0)
        return _eval_spatial(
            col, None, lambda feat: geo.distance(feat, self.geom) <= self.dist,
            candidates=cand,
        )

    @property
    def bounds(self) -> tuple[float, float, float, float]:
        x0, y0, x1, y1 = self.geom.bounds()
        return (x0 - self.dist, y0 - self.dist, x1 + self.dist, y1 + self.dist)


# ---------------------------------------------------------------------------
# temporal (epoch-millis int64 columns)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class During(Filter):
    """prop DURING lo/hi — half-open [lo, hi) on epoch millis, matching the
    reference's During semantics (FilterHelper.extractIntervals treats During
    as exclusive bounds; we use inclusive-lo/exclusive-hi which matches how
    GeoMesa plans Z3 ranges in practice)."""

    prop: str
    lo_ms: int
    hi_ms: int

    def evaluate(self, batch):
        c = np.asarray(_column(batch, self.prop), dtype=np.int64)
        return (c >= self.lo_ms) & (c < self.hi_ms)


# ---------------------------------------------------------------------------
# attribute comparisons
# ---------------------------------------------------------------------------

_OPS = {"=", "<>", "<", "<=", ">", ">="}


def _is_str_col(c: np.ndarray) -> bool:
    return c.dtype.kind in ("U", "S", "O")


@dataclass(frozen=True)
class Cmp(Filter):
    """prop <op> literal, op in =, <>, <, <=, >, >=."""

    prop: str
    op: str
    value: object

    def __post_init__(self):
        if self.op not in _OPS:
            raise ValueError(f"bad op {self.op!r}")

    def evaluate(self, batch):
        c = _column(batch, self.prop)
        c = np.asarray(c)
        v = self.value
        if self.op == "=":
            return c == v
        if self.op == "<>":
            return c != v
        if self.op == "<":
            return c < v
        if self.op == "<=":
            return c <= v
        if self.op == ">":
            return c > v
        return c >= v


@dataclass(frozen=True)
class Between(Filter):
    """prop BETWEEN lo AND hi (inclusive both ends, per ECQL)."""

    prop: str
    lo: object
    hi: object

    def evaluate(self, batch):
        c = np.asarray(_column(batch, self.prop))
        return (c >= self.lo) & (c <= self.hi)


@dataclass(frozen=True)
class In(Filter):
    """prop IN (v1, v2, ...)."""

    prop: str
    values: tuple

    def evaluate(self, batch):
        c = np.asarray(_column(batch, self.prop))
        return np.isin(c, np.asarray(list(self.values)))


@dataclass(frozen=True)
class Like(Filter):
    """prop LIKE 'pattern' with % (any) and _ (one) wildcards."""

    prop: str
    pattern: str

    def _regex(self) -> re.Pattern:
        esc = re.escape(self.pattern).replace("%", ".*").replace("_", ".")
        return re.compile(f"^{esc}$")

    def evaluate(self, batch):
        c = np.asarray(_column(batch, self.prop))
        rx = self._regex()
        return np.array([bool(rx.match(str(v))) for v in c], dtype=bool)


@dataclass(frozen=True)
class IsNull(Filter):
    """prop IS NULL — NaN for floats, sentinel '' for strings, NaT dates."""

    prop: str

    def evaluate(self, batch):
        c = np.asarray(_column(batch, self.prop))
        if c.dtype.kind == "f":
            return np.isnan(c)
        if _is_str_col(c):
            return np.array([v == "" or v is None for v in c], dtype=bool)
        return np.zeros(len(c), dtype=bool)


@dataclass(frozen=True)
class IdFilter(Filter):
    """Feature-id lookup (ECQL `IN ('id1', 'id2')` without a property).

    Reference: IdFilterStrategy / IdIndexKeySpace.
    """

    ids: tuple

    def evaluate(self, batch):
        fids = batch.get("__id__")
        if fids is None:
            raise KeyError("batch has no __id__ column for id filter")
        fids = np.asarray(fids)
        want = np.asarray(list(self.ids))
        if fids.dtype.kind != want.dtype.kind:
            # ECQL id literals are strings; stored ids may be numeric —
            # compare canonically as strings
            fids = fids.astype(str)
            want = want.astype(str)
        return np.isin(fids, want)


# ---------------------------------------------------------------------------
# logical
# ---------------------------------------------------------------------------


def _flatten(cls, filters: Sequence[Filter]) -> tuple[Filter, ...]:
    out: list[Filter] = []
    for f in filters:
        if isinstance(f, cls):
            out.extend(f.filters)
        else:
            out.append(f)
    return tuple(out)


@dataclass(frozen=True)
class And(Filter):
    filters: tuple = ()

    def __init__(self, filters: Sequence[Filter]):
        object.__setattr__(self, "filters", _flatten(And, tuple(filters)))
        if len(self.filters) < 1:
            raise ValueError("And needs >= 1 children")

    def evaluate(self, batch):
        m = self.filters[0].evaluate(batch)
        for f in self.filters[1:]:
            m = m & f.evaluate(batch)
        return m


@dataclass(frozen=True)
class Or(Filter):
    filters: tuple = ()

    def __init__(self, filters: Sequence[Filter]):
        object.__setattr__(self, "filters", _flatten(Or, tuple(filters)))
        if len(self.filters) < 1:
            raise ValueError("Or needs >= 1 children")

    def evaluate(self, batch):
        m = self.filters[0].evaluate(batch)
        for f in self.filters[1:]:
            m = m | f.evaluate(batch)
        return m


@dataclass(frozen=True)
class Not(Filter):
    filter: Filter = None  # type: ignore[assignment]

    def evaluate(self, batch):
        return ~self.filter.evaluate(batch)


# the most slice x row cells one pass of Slices.evaluate compares at once
_EVAL_CELLS = 1 << 20


class Slices(Filter):
    """Box-and-interval slices as two arrays: ``boxes`` f64 ``[n, 4]``
    (xmin, ymin, xmax, ymax) over the geometry attribute ``geom``,
    ``windows`` int64 ``[n, 2]`` half-open ``[lo, hi)`` epoch millis over
    the date attribute ``dtg``. It MEANS exactly ``Or(And(BBox(geom,
    *boxes[i]), During(dtg, *windows[i])) for i)`` (:meth:`expand`), and
    answers what the planner asks of a filter off the arrays, without an
    object a slice: a tube's up to 256 slices (``process/tube.py``) reach
    the indexes this way (docs/processes.md).

    Every box is finite and ordered (min <= max), every window non-empty
    (lo < hi); the arrays are the filter's value and are not written to
    after construction. ``repr`` / ``str`` is the expansion's ECQL text,
    rendered when asked for (the audit writer, the slow-query log)."""

    def __init__(self, geom: str, dtg: str, boxes, windows):
        boxes = np.ascontiguousarray(boxes, dtype=np.float64).reshape(-1, 4)
        windows = np.ascontiguousarray(windows, dtype=np.int64).reshape(-1, 2)
        if len(boxes) < 1 or len(boxes) != len(windows):
            raise ValueError(
                f"Slices needs >= 1 boxes and a window each: {len(boxes)}, {len(windows)}"
            )
        if not np.isfinite(boxes).all() or (boxes[:, :2] > boxes[:, 2:]).any():
            raise ValueError("Slices boxes must be finite with min <= max")
        if (windows[:, 0] >= windows[:, 1]).any():
            raise ValueError("Slices windows are half-open [lo, hi) with lo < hi")
        self.geom, self.dtg, self.boxes, self.windows = geom, dtg, boxes, windows

    def __len__(self) -> int:
        return len(self.boxes)

    def take(self, rows) -> "Slices":
        """The slices ``rows`` (an index array or a slice) as a carrier of
        their own: a subset of valid rows needs no second check."""
        out = object.__new__(Slices)
        out.geom, out.dtg = self.geom, self.dtg
        out.boxes, out.windows = self.boxes[rows], self.windows[rows]
        return out

    @staticmethod
    def of(f: Filter, dtg: str) -> "Slices | None":
        """``f``, an ``Or`` whose EVERY disjunct is ``And`` of one ``BBox``
        (all on one attribute) and one ``During`` on ``dtg``, as the
        carrier that means the same, in one walk; None for anything it
        cannot express exactly as (box, window) rows: a disjunct with a
        polygon, a second predicate, two intervals, an empty window."""
        boxes, windows, geom = [], [], None
        for d in f.filters:
            if not isinstance(d, And) or len(d.filters) != 2:
                return None
            a, b = d.filters
            if isinstance(a, During):
                a, b = b, a
            if not (isinstance(a, BBox) and isinstance(b, During) and b.prop == dtg):
                return None
            if geom is None:
                geom = a.prop
            if a.prop != geom:
                return None
            boxes.append(a.bounds)
            windows.append((b.lo_ms, b.hi_ms))
        try:
            return Slices(geom, dtg, boxes, windows)
        except (ValueError, OverflowError, TypeError):
            return None  # an inverted or non-finite box, an empty window

    def expand(self) -> Filter:
        """The ``Or`` of ``And(BBox, During)`` this carrier means (one
        slice: the ``And`` itself)."""
        parts = [
            And((BBox(self.geom, *b), During(self.dtg, lo, hi)))
            for b, (lo, hi) in zip(self.boxes.tolist(), self.windows.tolist())
        ]
        return parts[0] if len(parts) == 1 else Or(tuple(parts))

    def evaluate(self, batch):
        col = _column(batch, self.geom)
        if not isinstance(col, PointColumn):
            return self.expand().evaluate(batch)
        t = np.asarray(_column(batch, self.dtg), dtype=np.int64)
        b, w = self.boxes[:, :, None], self.windows[:, :, None]
        out = np.empty(len(t), dtype=bool)
        # a row passes if any slice's box and window hold it: [n, rows]
        # comparisons, so many rows a pass that the temporaries stay small
        step = max(1, _EVAL_CELLS // len(self))
        for a in range(0, len(t), step):
            x, y, ts = col.x[a:a + step], col.y[a:a + step], t[a:a + step]
            out[a:a + step] = (
                (x >= b[:, 0]) & (x <= b[:, 2]) & (y >= b[:, 1]) & (y <= b[:, 3])
                & (ts >= w[:, 0]) & (ts < w[:, 1])
            ).any(axis=0)
        return out

    def key(self) -> str:
        """The canonical key: the attribute names and a digest of the
        arrays' bytes with the rows in time order (then box order), so
        equal sets of slices collide whatever order they were given in;
        no string a slice."""
        b, w = self.boxes, self.windows
        order = np.lexsort((b[:, 3], b[:, 2], b[:, 1], b[:, 0], w[:, 1], w[:, 0]))
        h = hashlib.blake2b(digest_size=16)
        h.update(w[order].tobytes())
        h.update(b[order].tobytes())
        return f"Slices(geom={self.geom!r},dtg={self.dtg!r},n={len(self)},{h.hexdigest()})"

    def wrapped(self) -> "Slices":
        """:func:`wrap_box` of every box that leaves [-180, 180], as array
        arithmetic: such a slice becomes one or two rows under its one
        window (latitude clamped, as there). ``self`` where no box does."""
        b = self.boxes
        out = (b[:, 0] < -180.0) | (b[:, 2] > 180.0)
        if not out.any():
            return self
        x0, x1 = b[:, 0].copy(), b[:, 2].copy()
        y0 = np.where(out, np.maximum(b[:, 1], -90.0), b[:, 1])
        y1 = np.where(out, np.minimum(b[:, 3], 90.0), b[:, 3])
        whole = out & (x1 - x0 >= 360.0)
        x0[whole], x1[whole] = -180.0, 180.0
        # a box lying entirely beyond the seam shifts into range first
        while (far := x0 > 180.0).any():
            x0[far] -= 360.0
            x1[far] -= 360.0
        while (far := x1 < -180.0).any():
            x0[far] += 360.0
            x1[far] += 360.0
        west, east = x0 < -180.0, x1 > 180.0  # never both: under 360 wide
        first = np.stack(
            [np.where(west, -180.0, x0), y0, np.where(east, 180.0, x1), y1], axis=1
        )
        second = np.stack(
            [np.where(west, x0 + 360.0, -180.0), y0, np.where(west, 180.0, x1 - 360.0), y1],
            axis=1,
        )
        two = west | east
        row = np.repeat(np.arange(len(b)), 1 + two)
        boxes = first[row]
        boxes[np.cumsum(1 + two)[two] - 1] = second[two]
        return Slices(self.geom, self.dtg, boxes, self.windows[row])

    def ecql(self) -> str:
        """The expansion as ECQL text, which ``filter.ecql.parse`` reads
        back to ``expand()``: floats by ``repr`` (round-trip exact), the
        windows' bounds as ISO-8601 instants to the millisecond."""
        iso = np.datetime_as_string(self.windows.astype("datetime64[ms]")).tolist()
        parts = [
            f"BBOX({self.geom}, {x0!r}, {y0!r}, {x1!r}, {y1!r}) AND "
            f"{self.dtg} DURING {lo}Z/{hi}Z"
            for (x0, y0, x1, y1), (lo, hi) in zip(self.boxes.tolist(), iso)
        ]
        return parts[0] if len(parts) == 1 else " OR ".join(f"({p})" for p in parts)

    __repr__ = ecql

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, Slices)
            and (self.geom, self.dtg) == (other.geom, other.dtg)
            and np.array_equal(self.boxes, other.boxes)
            and np.array_equal(self.windows, other.windows)
        )

    def __hash__(self) -> int:
        return hash((self.geom, self.dtg, self.boxes.tobytes(), self.windows.tobytes()))


def canonical_key(f: Filter) -> str:
    """Deterministic canonical string of a filter tree. Logically-equal
    trees that differ only in And/Or child ORDER produce the SAME string
    (children sort by their own canonical keys), so cache fingerprints and
    plan comparisons treat ``a AND b`` and ``b AND a`` as one query.
    Geometries render as WKT; floats as repr (round-trip exact); a
    :class:`Slices` carrier as a digest of its arrays (``Slices.key``)."""
    if isinstance(f, Slices):
        return f.key()
    if isinstance(f, (And, Or)):
        kids = sorted(canonical_key(c) for c in f.filters)
        return f"{type(f).__name__}({','.join(kids)})"
    if isinstance(f, Not):
        return f"Not({canonical_key(f.filter)})"
    from dataclasses import fields, is_dataclass

    if not is_dataclass(f):  # pragma: no cover - all predicates are dataclasses
        return repr(f)
    parts = [
        f"{fd.name}={_canonical_value(getattr(f, fd.name))}" for fd in fields(f)
    ]
    return f"{type(f).__name__}({','.join(parts)})"


def _canonical_value(v) -> str:
    if isinstance(v, geo.Geometry):
        return v.wkt
    if isinstance(v, (bool, np.bool_)):
        return repr(bool(v))
    if isinstance(v, (float, np.floating)):
        return repr(float(v))
    if isinstance(v, (int, np.integer)):
        return repr(int(v))
    if isinstance(v, (tuple, list)):
        return "(" + ",".join(_canonical_value(x) for x in v) + ")"
    return repr(v)


def wrap_box(prop: str, x0: float, y0: float, x1: float, y1: float) -> Filter:
    """A lon/lat box as a filter, WRAPPING across the antimeridian
    (GeoTools BBOX semantics: a box past +/-180 crosses the seam and
    becomes two boxes). Latitude clamps to [-90, 90]."""
    import math

    y0, y1 = max(y0, -90.0), min(y1, 90.0)
    if not (math.isfinite(x0) and math.isfinite(x1)):
        # non-finite lons (e.g. an overflowed literal): keep the raw box —
        # the shift loops below would never terminate on inf
        return BBox(prop, x0, y0, x1, y1)
    if x1 - x0 >= 360.0:
        return BBox(prop, -180.0, y0, 180.0, y1)
    # a box lying ENTIRELY beyond the seam shifts into range first — the
    # splits below would otherwise emit an inverted (xmin > xmax) arm
    while x0 > 180.0:
        x0 -= 360.0
        x1 -= 360.0
    while x1 < -180.0:
        x0 += 360.0
        x1 += 360.0
    if x0 < -180.0:
        return Or((
            BBox(prop, -180.0, y0, x1, y1),
            BBox(prop, x0 + 360.0, y0, 180.0, y1),
        ))
    if x1 > 180.0:
        return Or((
            BBox(prop, x0, y0, 180.0, y1),
            BBox(prop, -180.0, y0, x1 - 360.0, y1),
        ))
    return BBox(prop, x0, y0, x1, y1)


def normalize_antimeridian(f: Filter) -> Filter:
    """Rewrite out-of-range BBOXes anywhere in a filter tree into their
    wrapped two-box form (reference FilterHelper splits seam-crossing
    boxes the same way; without this the planner's world-clamping would
    silently drop the wrapped part). Returns ``f`` itself when nothing
    in the tree needed rewriting (the common case on every plan())."""
    if isinstance(f, BBox) and (f.xmin < -180.0 or f.xmax > 180.0):
        return wrap_box(f.prop, f.xmin, f.ymin, f.xmax, f.ymax)
    if isinstance(f, Slices):
        return f.wrapped()
    if isinstance(f, (And, Or)):
        kids = tuple(normalize_antimeridian(c) for c in f.filters)
        if all(k is c for k, c in zip(kids, f.filters)):
            return f
        return And(kids) if isinstance(f, And) else Or(kids)
    if isinstance(f, Not):
        inner = normalize_antimeridian(f.filter)
        return f if inner is f.filter else Not(inner)
    return f
