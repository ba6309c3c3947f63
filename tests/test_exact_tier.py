"""The batched exact tier of an intersects over a packed geometry column
(``geo.intersects_rows``) against the definition it answers,
``geo.intersects`` a geometry: the same boolean on every row, whatever
the row's type and the query's; the named hard cases (touches, collinear
edges, containment either way, holes, crossings with no vertex inside);
and the tier's counters through ``BBox.evaluate`` and
``Intersects.evaluate`` under a trace (docs/observability.md, the
``decode`` row)."""

import numpy as np
import pytest

from geomesa_tpu import conf, geometry as geo, obs
from geomesa_tpu.filter.predicates import BBox, Intersects

TIERS = ("refine_rect", "refine_accept", "refine_exact")


def _ring(cx, cy, r, n, rng=None, phase=0.0):
    """A star-shaped closed ring of n vertices: convex with no ``rng``."""
    ang = phase + np.linspace(0.0, 2 * np.pi, n, endpoint=False)
    rad = r * (rng.uniform(0.35, 1.0, n) if rng is not None else np.ones(n))
    ring = np.stack([cx + rad * np.cos(ang), cy + rad * np.sin(ang)], axis=1)
    return np.vstack([ring, ring[:1]])


def _row(kind, rng):
    """One geometry of a type code, somewhere in [0, 10]^2."""
    cx, cy = rng.uniform(0.0, 10.0, 2)
    r = rng.uniform(0.1, 1.5)
    if kind == geo.POINT:
        return geo.Point(cx, cy)
    if kind == geo.LINESTRING:
        return geo.LineString(_ring(cx, cy, r, int(rng.integers(3, 7)), rng)[:-2])
    if kind == geo.POLYGON:
        holes = [_ring(cx, cy, 0.2 * r, 4, phase=0.3)] if rng.random() < 0.5 else None
        return geo.Polygon(_ring(cx, cy, r, int(rng.integers(3, 9)), rng), holes)
    if kind == geo.MULTIPOINT:
        return geo.MultiPoint([geo.Point(*rng.uniform(0.0, 10.0, 2)) for _ in range(3)])
    if kind == geo.MULTILINESTRING:
        return geo.MultiLineString(
            [geo.LineString(_ring(cx + d, cy, r, 4, rng)[:-2]) for d in (0.0, 1.0)])
    return geo.MultiPolygon([
        geo.Polygon(_ring(cx + d, cy + d, 0.5 * r, 5, rng),
                    [_ring(cx + d, cy + d, 0.1 * r, 4)] if d else None)
        for d in (0.0, 1.2)])


KINDS = (geo.POINT, geo.LINESTRING, geo.POLYGON, geo.MULTIPOINT, geo.MULTILINESTRING,
         geo.MULTIPOLYGON)


@pytest.fixture(scope="module")
def mixed():
    """400 geometries of each type, shuffled into one packed column."""
    rng = np.random.default_rng(40)
    kinds = rng.permutation(np.repeat(KINDS, 400))
    return geo.PackedGeometryColumn.from_geometries([_row(int(k), rng) for k in kinds])


_QRNG = np.random.default_rng(41)
QUERIES = {
    "convex": geo.Polygon(_ring(5.0, 5.0, 2.5, 12)),
    "non-convex": geo.Polygon(_ring(4.0, 6.0, 3.0, 24, _QRNG)),
    "hole": geo.Polygon(_ring(5.0, 5.0, 3.5, 16), [_ring(5.0, 5.0, 2.0, 9, phase=0.2)]),
    "multipolygon": geo.MultiPolygon([
        geo.Polygon(_ring(2.5, 2.5, 2.0, 10, _QRNG)),
        geo.Polygon(_ring(7.0, 7.0, 2.5, 8), [_ring(7.0, 7.0, 1.0, 5)])]),
    "rectangle": geo.box(2.0, 3.0, 7.5, 6.5),
    "linestring": geo.LineString(_ring(5.0, 5.0, 4.0, 9, _QRNG)[:-3]),
    "multilinestring": geo.MultiLineString([
        geo.LineString([(0.0, 0.0), (10.0, 9.0)]), geo.LineString([(0.0, 8.0), (9.0, 1.0)])]),
    "point": geo.Point(5.0, 5.0),
    "multipoint": geo.MultiPoint([geo.Point(3.0, 3.0), geo.Point(6.5, 6.0)]),
}
#: the queries the flat form takes: a point asks whether a geometry COVERS it
RINGED_QUERIES = ("convex", "non-convex", "hole", "multipolygon", "rectangle", "linestring",
                  "multilinestring")
FLAT_KINDS = (geo.LINESTRING, geo.POLYGON, geo.MULTILINESTRING, geo.MULTIPOLYGON)


def _loop(col, rows, g):
    return np.array([geo.intersects(col.geometry(int(i)), g) for i in rows], dtype=bool)


@pytest.mark.parametrize("kind", KINDS, ids=[geo.TYPE_NAMES[k] for k in KINDS])
@pytest.mark.parametrize("query", list(QUERIES))
def test_every_row_reads_what_intersects_reads(query, kind, mixed):
    g = QUERIES[query]
    rows = np.flatnonzero(mixed.types == kind)
    want = _loop(mixed, rows, g)
    got = geo.intersects_rows(mixed, rows, g)
    assert got.dtype == bool and np.array_equal(got, want)
    batched = geo.flat_form_rows(mixed, rows, g)
    assert batched.all() if (query in RINGED_QUERIES and kind in FLAT_KINDS) else not batched.any()
    if batched.all() or (query in RINGED_QUERIES and "line" not in query):
        assert 0 < want.sum() < len(want)  # both outcomes are met (a point on a line is not)


@pytest.mark.parametrize("query", list(QUERIES))
def test_rows_in_any_order_and_repeated(query, mixed):
    g = QUERIES[query]
    rng = np.random.default_rng(42)
    rows = rng.integers(0, len(mixed), 700)  # unsorted, with repeats, all types together
    assert np.array_equal(geo.intersects_rows(mixed, rows, g), _loop(mixed, rows, g))
    assert geo.intersects_rows(mixed, rows[:0], g).shape == (0,)


def _sq(x0, y0, x1, y1):
    return [(x0, y0), (x1, y0), (x1, y1), (x0, y1), (x0, y0)]


_L = [(0, 0), (4, 0), (4, 1), (1, 1), (1, 4), (0, 4), (0, 0)]  # non-convex, bounds 0..4
_HOLED = geo.Polygon(_sq(0, 0, 10, 10), [_sq(3, 3, 7, 7)])
#: (name, feature, query, answer)
HARD = [
    ("shared-vertex", geo.Polygon(_sq(0, 0, 1, 1)), geo.Polygon([(1, 1), (2, 1.5), (2, 3), (1, 1)]), True),
    ("vertex-on-a-query-edge", geo.Polygon([(1, 0.5), (2, 0), (2, 1), (1, 0.5)]),
     geo.Polygon(_sq(0, 0, 1, 1)), True),
    ("collinear-overlapping-edges", geo.Polygon(_sq(1, 0.25, 2, 0.75)), geo.Polygon(_sq(0, 0, 1, 1)), True),
    # four points of y = 0.3 x + 0.7 as f64 rounds them: their orientation signs are noise,
    # which read a proper crossing into the pair until a crossing had to lie within bounds
    ("collinear-oblique-apart",
     geo.LineString([(5.0, 9.0), (0.42, 0.3 * 0.42 + 0.7), (3.6, 0.3 * 3.6 + 0.7)]),
     geo.LineString([(3.79, 0.3 * 3.79 + 0.7), (7.59, 0.3 * 7.59 + 0.7), (7.59, -3.0)]), False),
    ("footprint-inside-the-ring", geo.Polygon(_sq(4, 4, 4.5, 4.5)), geo.Polygon(_ring(4.2, 4.2, 3.0, 24)), True),
    ("ring-inside-the-footprint", geo.Polygon(_sq(0, 0, 10, 10)), geo.Polygon(_ring(5, 5, 1.0, 24)), True),
    ("footprint-in-the-query's-hole", geo.Polygon(_sq(4, 4, 6, 6)), _HOLED, False),
    ("query-in-the-footprint's-hole", _HOLED, geo.Polygon(_sq(4, 4, 6, 6)), False),
    ("footprint-across-the-query's-hole", geo.Polygon(_sq(4, 4, 8, 6)), _HOLED, True),
    ("edge-crossings-no-vertex-inside", geo.Polygon(_sq(2, -1, 3, 5)), geo.Polygon(_sq(0, 1, 5, 2)), True),
    ("disjoint-bounds-overlap", geo.Polygon(_sq(2, 2, 3.5, 3.5)), geo.Polygon(_L), False),
    ("disjoint-bounds-apart", geo.Polygon(_sq(0, 0, 1, 1)), geo.Polygon(_sq(2, 2, 3, 3)), False),
    ("touch-along-a-bound", geo.Polygon(_sq(0, 0, 1, 1)), geo.Polygon(_sq(1, 0, 2, 1)), True),
    ("a-step-apart", geo.Polygon(_sq(0, 0, 1, 1)), geo.Polygon(_sq(np.nextafter(1.0, 2.0), 0, 2, 1)), False),
    ("line-through-a-polygon", geo.LineString([(-1, 0.5), (2, 0.5)]), geo.Polygon(_sq(0, 0, 1, 1)), True),
    ("line-inside-a-polygon", geo.LineString([(0.2, 0.2), (0.8, 0.6)]), geo.Polygon(_sq(0, 0, 1, 1)), True),
    ("line-in-a-hole", geo.LineString([(4, 4), (6, 6)]), _HOLED, False),
    ("polygon-around-a-line", geo.Polygon(_sq(0, 0, 1, 1)), geo.LineString([(0.2, 0.2), (0.8, 0.6)]), True),
    ("lines-cross", geo.LineString([(0, 0), (2, 2)]), geo.LineString([(0, 2), (2, 0)]), True),
    ("lines-end-on-a-line", geo.LineString([(0, 0), (1, 1)]), geo.LineString([(0, 2), (2, 0)]), True),
    ("second-part-hits", geo.MultiPolygon([geo.Polygon(_sq(20, 20, 21, 21)), geo.Polygon(_sq(0, 0, 1, 1))]),
     geo.Polygon(_sq(0.5, 0.5, 3, 3)), True),
    ("query-in-the-second-part", geo.MultiPolygon([geo.Polygon(_sq(20, 20, 21, 21)), geo.Polygon(_sq(0, 0, 9, 9))]),
     geo.Polygon(_sq(4, 4, 5, 5)), True),
]


@pytest.mark.parametrize("name,feature,query,answer", HARD, ids=[h[0] for h in HARD])
def test_the_hard_cases(name, feature, query, answer):
    # the case between two neighbours it must not be confused with
    col = geo.PackedGeometryColumn.from_geometries(
        [geo.Polygon(_sq(50, 50, 51, 51)), feature, geo.Polygon(_sq(-9, -9, 60, 60))])
    assert geo.intersects(feature, query) is answer
    assert geo.flat_form_rows(col, np.arange(3), query).all()
    assert geo.intersects_rows(col, np.arange(3), query).tolist() == [False, answer, True]
    # and the other way round: the query a row, the feature asked
    col = geo.PackedGeometryColumn.from_geometries([query])
    assert geo.intersects_rows(col, np.array([0]), feature).tolist() == [answer]


@pytest.mark.parametrize("cells", [1, 7, 1000])
@pytest.mark.parametrize("query", ["non-convex", "hole", "linestring"])
def test_a_small_chunk_of_the_pair_mask_answers_the_same(query, cells, mixed, monkeypatch):
    g = QUERIES[query]
    rows = np.flatnonzero(np.isin(mixed.types, FLAT_KINDS))
    whole = geo.intersects_rows(mixed, rows, g)
    monkeypatch.setattr(geo, "_PAIR_CELLS", cells)
    assert np.array_equal(geo.intersects_rows(mixed, rows, g), whole)


# ------------------------------------------------- the tiers and their counters


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _footprints(n, rng, points=0):
    """n rotated footprints of 4-8 vertices inside [0, 1]^2 (none a
    rectangle), then ``points`` point rows."""
    feet = [geo.Polygon(_ring(*rng.uniform(0.0, 1.0, 2), 0.02, int(rng.integers(4, 9)), rng,
                              phase=rng.uniform(0, 6)))
            for _ in range(n)]
    feet += [geo.Point(*rng.uniform(0.3, 0.7, 2)) for _ in range(points)]
    return geo.PackedGeometryColumn.from_geometries(feet)


FILTERS = {
    "bbox": BBox("geom", 0.2, 0.3, 0.8, 0.7),
    "ring": Intersects("geom", geo.Polygon(_ring(0.5, 0.5, 0.4, 48, np.random.default_rng(43)))),
    "line": Intersects("geom", geo.LineString([(0.0, 0.1), (0.6, 0.9), (1.0, 0.2)])),
}


@pytest.mark.parametrize("n", [1, 44, 64, 65, 3000])
@pytest.mark.parametrize("which", list(FILTERS))
def test_the_tiers_sum_to_the_candidates_at_any_count(which, n, traced):
    f = FILTERS[which]
    col = _footprints(n, np.random.default_rng(n), points=3 if n > 1 else 0)
    query = geo.box(*f.bounds) if which == "bbox" else f.geom
    want = _loop(col, range(len(col)), query)
    with traced.trace("query") as tr:
        got = f.evaluate({"geom": col})
    assert np.array_equal(got, want)
    assert np.array_equal(f.evaluate({"geom": col}), want)  # and with no span to count on
    a = tr.root.attrs
    assert sum(a.get(k, 0) for k in TIERS) == len(col), a
    assert a["refine_hits"] == int(want.sum())
    exact = a.get("refine_exact", 0)  # a column whose every bbox misses counts no tier but the first
    assert ("refine_exact_s" in a) == (exact > 0)
    assert 0 <= a.get("refine_batched", 0) <= exact
    if n == 3000 and which != "bbox":
        # footprints across the query's boundary have no vertex inside it (a box many times
        # their size leaves the accept tier next to none); the three point rows are the only
        # ones a per-geometry call could be asked about
        assert 0 < exact - 3 <= a["refine_batched"]
        assert a.get("refine_accept", 0) > 0 or which == "line"


def test_a_point_query_takes_no_batched_pass(traced):
    col = _footprints(200, np.random.default_rng(7))
    c = col.geometry(5).shell.mean(axis=0)
    f = Intersects("geom", geo.Point(float(c[0]), float(c[1])))
    with traced.trace("query") as tr:
        got = f.evaluate({"geom": col})
    assert np.array_equal(got, _loop(col, range(len(col)), f.geom)) and got[5]
    a = tr.root.attrs
    assert a["refine_batched"] == 0 < a["refine_exact"]
