"""Box-and-interval slices carried as arrays (PR 48; ``filter/predicates.py``
``Slices``, ``filter/dnf.py`` ``time_slices``, ``filter/extract.py``
``extract_filter``, the planner's ``_plan_members``): a ``Slices`` carrier
MEANS ``Or(And(BBox, During) ...)`` and is planned, scanned and refined to the
same rows as that ``Or``, with the union's plans equal field for field to the
object path's.

The fixtures are tests/test_time_sliced_union.py's (a 2^16-row z3 + z2 store
along a diagonal corridor, a NumPy brute force that knows nothing of filters),
with a thousand rows either side of the antimeridian beside them.
"""

import numpy as np
import pytest

from geomesa_tpu import conf, geometry as geo, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.filter.dnf import MAX_DISJUNCTS, time_slices
from geomesa_tpu.filter.extract import extract_filter, extract_geometries, extract_intervals
from geomesa_tpu.filter.predicates import (
    And, BBox, Cmp, During, Intersects, Or, PointColumn, Slices, canonical_key,
    normalize_antimeridian,
)
from geomesa_tpu.serving import QueryScheduler, ServingConfig
from geomesa_tpu.sft import FeatureType

N = 1 << 16
SEAM = 2_000  # of them, either side of the antimeridian
T0 = 1_700_000_000_000
STEP_MS = 60_000
SLICES_MAX = 256
TYPE = "rep"


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(48)
    f = rng.uniform(-0.02, 1.02, N)
    x = -122.0 + 2.0 * f + rng.normal(0, 0.02, N)
    y = 36.0 + 1.0 * f + rng.normal(0, 0.02, N)
    t = T0 + (f * SLICES_MAX * STEP_MS).astype(np.int64) // 1000 * 1000
    t += rng.integers(-300, 300, N) * 1000
    on_minute = rng.random(N) < 0.125
    t[on_minute] = T0 + (t[on_minute] - T0) // STEP_MS * STEP_MS
    x[:SEAM] = np.where(rng.random(SEAM) < 0.5, rng.uniform(179.0, 180.0, SEAM),
                        rng.uniform(-180.0, -179.0, SEAM))
    y[:SEAM] = rng.uniform(-1.0, 1.0, SEAM)
    return x, y, t, rng.integers(0, 100, N).astype(np.int32)


@pytest.fixture(scope="module")
def store(rows):
    x, y, t, mmsi = rows
    sft = FeatureType.from_spec(TYPE, "mmsi:Integer,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    ds = DataStore()
    ds.create_schema(sft)
    ds.write(TYPE, FeatureCollection.from_columns(
        sft, np.arange(N, dtype=np.int64), {"mmsi": mmsi, "dtg": t, "geom": (x, y)}),
        check_ids=False)
    return ds


def _boxes(n, overlap_ms=0, half=0.05):
    """``n`` (xmin, ymin, xmax, ymax, lo, hi) along the corridor, slice i
    over minute i (widened by ``overlap_ms`` each way)."""
    out = []
    for i in range(n):
        c = (i + 0.5) / SLICES_MAX
        cx, cy = -122.0 + 2.0 * c, 36.0 + 1.0 * c
        out.append((cx - half, cy - half, cx + half, cy + half,
                    T0 + i * STEP_MS - overlap_ms, T0 + (i + 1) * STEP_MS + overlap_ms))
    return out


def _carrier(boxes):
    b = np.array(boxes, dtype=np.float64)
    return Slices("geom", "dtg", b[:, :4], np.array([r[4:] for r in boxes], dtype=np.int64))


def _objects(boxes):
    parts = [And((BBox("geom", a, b, c, d), During("dtg", lo, hi))) for a, b, c, d, lo, hi in boxes]
    return parts[0] if len(parts) == 1 else Or(tuple(parts))


def _brute(rows, boxes, more=None):
    """The ids the slices hold: a closed box, a half-open interval; a box
    past the antimeridian holds what lies in it modulo 360."""
    x, y, t, _ = rows
    hit = np.zeros(N, bool)
    for a, b, c, d, lo, hi in boxes:
        inside = np.zeros(N, bool)
        for shift in (-360.0, 0.0, 360.0):
            inside |= (x + shift >= a) & (x + shift <= c)
        hit |= inside & (y >= b) & (y <= d) & (t >= lo) & (t < hi)
    if more is not None:
        hit &= more
    return np.flatnonzero(hit)


def _ids(fc):
    return np.sort(np.asarray(fc.ids).astype(np.int64))


@pytest.fixture()
def object_path(monkeypatch):
    """``time_slices`` as before PR 48: no ``Or`` is converted."""
    monkeypatch.setattr(Slices, "of", staticmethod(lambda f, dtg: None))


# --------------------------------------------------------------- the carrier


def test_it_means_the_or_of_its_slices():
    boxes = _boxes(5)
    s = _carrier(boxes)
    assert len(s) == 5 and s.expand() == _objects(boxes)
    assert isinstance(_carrier(boxes[:1]).expand(), And)
    assert s == _carrier(boxes) and hash(s) == hash(_carrier(boxes)) and s != _carrier(boxes[:4])
    assert s.take([3, 1]) == _carrier([boxes[3], boxes[1]])


@pytest.mark.parametrize("bad", ["no-rows", "a-window-short", "inverted-x", "inverted-y", "nan",
                                 "inf", "empty-window", "backward-window"])
def test_it_refuses_what_it_cannot_mean(bad):
    boxes, windows = [[0.0, 0.0, 1.0, 1.0], [2.0, 2.0, 3.0, 3.0]], [[10, 20], [20, 30]]
    if bad == "no-rows":
        boxes, windows = [], []
    elif bad == "a-window-short":
        windows = windows[:1]
    elif bad == "inverted-x":
        boxes[1] = [3.0, 2.0, 2.0, 3.0]
    elif bad == "inverted-y":
        boxes[1] = [2.0, 3.0, 3.0, 2.0]
    elif bad == "nan":
        boxes[0][2] = float("nan")
    elif bad == "inf":
        boxes[0][0] = float("-inf")
    elif bad == "empty-window":
        windows[1] = [20, 20]
    else:
        windows[1] = [30, 20]
    with pytest.raises(ValueError):
        Slices("geom", "dtg", boxes, windows)


@pytest.mark.parametrize("seed", range(6))
def test_evaluate_is_the_ors_on_random_rows(seed):
    """One vectorised pass against sixteen-times-two predicates: rows ON a
    box's edge and ON a window's two ends among them."""
    rng = np.random.default_rng(seed)
    n, m = int(rng.integers(1, 40)), 5000
    x0, y0 = rng.uniform(-10, 9, n), rng.uniform(-5, 4, n)
    lo = rng.integers(0, 900, n) * 1000
    boxes = np.stack([x0, y0, x0 + rng.uniform(0, 2, n), y0 + rng.uniform(0, 2, n)], 1)
    windows = np.stack([lo, lo + rng.integers(1, 200, n) * 1000], 1)
    s = Slices("geom", "dtg", boxes, windows)
    x, y = rng.uniform(-11, 11, m), rng.uniform(-6, 6, m)
    t = rng.integers(-5, 1100, m) * 1000
    k = rng.integers(0, n, m // 4)  # a quarter exactly on some slice's corner and instant
    x[: m // 4] = boxes[k, rng.choice([0, 2], m // 4)]
    y[: m // 4] = boxes[k, rng.choice([1, 3], m // 4)]
    t[: m // 4] = windows[k, rng.choice([0, 1], m // 4)]
    batch = {"geom": PointColumn(x, y), "dtg": t}
    want = s.expand().evaluate(batch)
    assert np.array_equal(s.evaluate(batch), want) and 0 < want.sum() < m
    assert s.evaluate({"geom": PointColumn(x[:0], y[:0]), "dtg": t[:0]}).shape == (0,)


def test_evaluate_walks_many_rows_in_passes(monkeypatch):
    import geomesa_tpu.filter.predicates as P

    boxes = _boxes(64)
    s, rng = _carrier(boxes), np.random.default_rng(3)
    x, y = rng.uniform(-122.1, -120.9, 20_000), rng.uniform(35.9, 36.6, 20_000)
    t = T0 + rng.integers(0, 70 * 60, 20_000) * 1000
    batch = {"geom": PointColumn(x, y), "dtg": t}
    whole = s.evaluate(batch)
    monkeypatch.setattr(P, "_EVAL_CELLS", 64 * 777)  # 777 rows a pass
    assert np.array_equal(s.evaluate(batch), whole) and whole.any()


def test_evaluate_over_extents_is_the_expansions():
    col = geo.PackedGeometryColumn.from_geometries(
        [geo.box(0, 0, 1, 1), geo.box(5, 5, 6, 6), geo.Point(0.5, 0.5)])
    batch = {"geom": col, "dtg": np.array([15, 15, 25], dtype=np.int64)}
    s = Slices("geom", "dtg", [[0.5, 0.5, 2, 2], [4, 4, 7, 7]], [[10, 20], [20, 30]])
    assert s.evaluate(batch).tolist() == s.expand().evaluate(batch).tolist() == [True, False, False]


def test_the_canonical_key_is_the_slices_not_their_order():
    boxes = _boxes(40)
    s = _carrier(boxes)
    order = np.random.default_rng(1).permutation(40)
    assert canonical_key(s) == canonical_key(_carrier([boxes[i] for i in order]))
    assert canonical_key(s) == canonical_key(_carrier(boxes))
    assert canonical_key(s) != canonical_key(_carrier(boxes[:39]))
    assert canonical_key(s) != canonical_key(Slices("geom2", "dtg", s.boxes, s.windows))
    assert canonical_key(And((s, Cmp("mmsi", "<", 5)))) == canonical_key(
        And((Cmp("mmsi", "<", 5), s)))


@pytest.mark.parametrize("column", range(6))
def test_the_canonical_key_moves_with_one_ulp_or_one_millisecond(column):
    s = _carrier(_boxes(40))
    boxes, windows = s.boxes.copy(), s.windows.copy()
    if column < 4:
        boxes[17, column] = np.nextafter(boxes[17, column], np.inf if column >= 2 else -np.inf)
    else:
        windows[17, column - 4] += 1 if column == 5 else -1
    assert canonical_key(Slices("geom", "dtg", boxes, windows)) != canonical_key(s)


def test_it_names_its_two_fields_to_the_visibility_check():
    from geomesa_tpu.planning.planner import _referenced_props

    s = _carrier(_boxes(3))
    assert _referenced_props(s) == {"geom", "dtg"}
    assert _referenced_props(And((s, Cmp("mmsi", "<", 5)))) == {"geom", "dtg", "mmsi"}


def test_its_text_is_ecql_that_parses_back_to_the_expansion():
    s = Slices("geom", "dtg", [[-122.5, 36.0, -122.25, 36.125], [1e-7, -1e-7, 0.3, 0.1 + 0.2]],
               [[T0, T0 + 60_000], [T0 + 1, T0 + 60_001]])
    assert str(s) == repr(s) and str(s).count("DURING") == 2
    assert ecql.parse(str(s)) == s.expand()
    one = s.take([1])
    assert "OR" not in str(one) and ecql.parse(str(one)) == one.expand()


# ------------------------------------------------------------ the antimeridian

SEAM_BOXES = [
    (179.5, -0.5, 180.5, 0.5),  # across, from the east
    (-180.75, -0.5, -179.25, 0.5),  # across, from the west
    (181.0, -0.5, 181.5, 0.5),  # wholly beyond: shifted in
    (-541.0, -95.0, -539.5, 0.25),  # two turns beyond, latitude clamped
    (-190.0, -0.25, 200.0, 0.25),  # wider than the world
    (-122.0, 36.0, -121.0, 37.0),  # in range: untouched
]


def test_wrapping_is_wrap_boxs_as_array_arithmetic():
    windows = [[T0 + i * 1000, T0 + (i + 1) * 1000] for i in range(len(SEAM_BOXES))]
    s = Slices("geom", "dtg", SEAM_BOXES, windows)
    w = normalize_antimeridian(s)
    assert w is not s and isinstance(w, Slices)
    want = normalize_antimeridian(s.expand())  # a slice across the seam: two boxes, one window
    got_rows = sorted(map(tuple, np.concatenate([w.boxes, w.windows], axis=1).tolist()))
    want_rows = []
    for d in want.filters:
        boxes = [c for c in d.filters if not isinstance(c, During)]
        (during,) = [c for c in d.filters if isinstance(c, During)]
        for b in (boxes[0].filters if isinstance(boxes[0], Or) else boxes):
            want_rows.append((*b.bounds, during.lo_ms, during.hi_ms))
    assert got_rows == sorted(want_rows) and len(got_rows) == 9
    assert (w.boxes[:, 0] >= -180.0).all() and (w.boxes[:, 2] <= 180.0).all()
    # nothing to wrap: the filter itself, no copy
    inside = _carrier(_boxes(20))
    assert normalize_antimeridian(inside) is inside
    both = And((inside, Cmp("mmsi", "<", 5)))
    assert normalize_antimeridian(both) is both


def test_a_slice_across_the_antimeridian_answers_as_the_object_form(store, rows):
    """Equal answers; the plans may differ (the object form holds two boxes
    in one disjunct, the carrier two rows)."""
    boxes = [(179.5 + 0.001 * i, -0.6, 180.5 + 0.001 * i, 0.6,
              T0 + i * 8 * STEP_MS, T0 + (i + 1) * 8 * STEP_MS) for i in range(32)]
    want = _brute(rows, boxes)
    east = rows[0][want] > 0
    assert east.any() and (~east).any()
    for f in (_carrier(boxes), _objects(boxes)):
        plan = store.planner.plan(TYPE, f)
        assert plan.union is not None
        assert np.array_equal(_ids(store.query(TYPE, f)), want)


# ------------------------------------------------------------- the extraction


@pytest.mark.parametrize("shape", ["alone", "under-and", "z2-no-date"])
def test_the_extraction_is_the_arrays(shape):
    boxes = _boxes(20, overlap_ms=5_000) + [(-200.0, -95.0, 200.0, 95.0, T0 - 10**7, T0 - 10**6)]
    s = normalize_antimeridian(_carrier(boxes))
    f, obj = s, normalize_antimeridian(_objects(boxes))
    if shape == "under-and":
        f, obj = And((Cmp("mmsi", "<", 5), s)), And((Cmp("mmsi", "<", 5), obj))
    dtg = None if shape == "z2-no-date" else "dtg"
    got, want = extract_filter(f, "geom", dtg), extract_filter(obj, "geom", dtg)
    assert isinstance(got.bounds, np.ndarray) and got.bounds.dtype == np.float64
    assert got.bounds.tolist() == [list(b) for b in want.bounds]  # clipped to the world alike
    assert got.boxes_exact and want.boxes_exact and got.geoms.precise and len(got.geoms.values) == 21
    if dtg is None:
        assert got.intervals is None and want.intervals is None
    else:
        assert got.intervals == want.intervals and len(got.intervals.values) == 2  # merged


def test_any_other_composition_extracts_as_the_expansion():
    s, obj = _carrier(_boxes(6)), _objects(_boxes(6))
    for make in (lambda f: And((f, BBox("geom", -122.0, 36.0, -121.9, 36.1))),
                 lambda f: And((f, During("dtg", T0, T0 + 90_000))),
                 lambda f: Or((f, BBox("geom", 0, 0, 1, 1))),
                 lambda f: And((f, _carrier(_boxes(3)) if f is s else _objects(_boxes(3))))):
        got, want = extract_filter(make(s), "geom", "dtg"), extract_filter(make(obj), "geom", "dtg")
        assert [g.bounds() for g in got.geoms.values] == [g.bounds() for g in want.geoms.values]
        assert list(got.bounds) == list(want.bounds) and got.intervals == want.intervals
        assert (got.boxes_exact, got.geoms.precise) == (want.boxes_exact, want.geoms.precise)
    # another field's name: the carrier says nothing of this type's fields
    assert extract_geometries(s, "other").empty and extract_intervals(s, "other").empty
    assert not isinstance(extract_filter(Slices("other", "dtg", s.boxes, s.windows),
                                         "geom", "dtg").bounds, np.ndarray)


# ------------------------------------------------------- the corner ordinals


@pytest.mark.parametrize("seed", range(8))
def test_the_vector_route_gives_the_scalar_loops_corner_ordinals(seed, monkeypatch):
    """Past ``SCALAR_CORNERS`` boxes a batch's corner ordinals are one
    ``normalize`` a dimension: the same floor and clamp as ``normalize_one``,
    to the last bit, out-of-world and seam values among them; lists of
    tuples and arrays of rows alike."""
    import geomesa_tpu.curve.z2sfc as m2
    import geomesa_tpu.curve.z3sfc as m3

    rng = np.random.default_rng(seed)
    z3, z2 = m3.Z3SFC.for_period("week"), m2.Z2SFC()
    bounds, times = [], []
    for _ in range(int(rng.integers(1, 24))):
        n = int(rng.integers(1, 18))
        x0, y0 = rng.uniform(-200, 190, n), rng.uniform(-100, 95, n)
        b = np.stack([x0, y0, x0 + rng.uniform(0, 20, n), y0 + rng.uniform(0, 10, n)], 1)
        b[rng.random(n) < 0.2] = [-180.0, -90.0, 180.0, 90.0]
        bounds.append(b)
        t0 = float(rng.uniform(-10, 604800))
        times.append((t0, t0 + float(rng.uniform(0, 1e5))))
    as_lists = [[tuple(r) for r in b.tolist()] for b in bounds]
    monkeypatch.setattr(m3, "SCALAR_CORNERS", 10**9)
    monkeypatch.setattr(m2, "SCALAR_CORNERS", 10**9)
    want = z3._corners_each(as_lists, times) + z2._corners(as_lists)
    monkeypatch.setattr(m3, "SCALAR_CORNERS", 0)
    monkeypatch.setattr(m2, "SCALAR_CORNERS", 0)
    for given in (as_lists, bounds):
        got = z3._corners_each(given, times) + z2._corners(given)
        for a, b in zip(want, got):
            assert a.dtype == b.dtype == np.uint64 and a.shape == b.shape and np.array_equal(a, b)


@pytest.mark.parametrize("route", ["scalar", "vector"])
def test_an_inverted_box_or_window_is_refused_on_both_routes(route, monkeypatch):
    import geomesa_tpu.curve.z2sfc as m2
    import geomesa_tpu.curve.z3sfc as m3

    limit = 10**9 if route == "scalar" else 0
    monkeypatch.setattr(m3, "SCALAR_CORNERS", limit)
    monkeypatch.setattr(m2, "SCALAR_CORNERS", limit)
    z3, z2 = m3.Z3SFC.for_period("week"), m2.Z2SFC()
    good, bad = [(0.0, 0.0, 1.0, 1.0)], [(0.0, 0.0, 1.0, 1.0), (3.0, 0.0, 2.0, 1.0)]
    with pytest.raises(ValueError, match="inverted bbox"):
        z2._corners([good, bad])
    with pytest.raises(ValueError, match="inverted bbox"):
        z3._corners_each([good, bad], [(0.0, 1.0), (0.0, 1.0)])
    with pytest.raises(ValueError, match="inverted time window"):
        z3._corners_each([good, good], [(0.0, 1.0), (5.0, 1.0)])


# ------------------------------------------------------------------- the rule

CANNOT = {
    "a-polygon": lambda p: Or((*p[:-1], And((Intersects("geom", geo.box(-121, 36, -120.9, 36.1)),
                                            p[-1].filters[1])))),
    "a-second-predicate": lambda p: Or((*p[:-1], And((*p[-1].filters, Cmp("mmsi", "<", 50))))),
    "two-intervals": lambda p: Or((*p[:-1], And((p[-1].filters[0], Or((
        During("dtg", T0, T0 + 1000), During("dtg", T0 + 5000, T0 + 6000))))))),
    "an-empty-window": lambda p: Or((*p[:-1], And((p[-1].filters[0], During("dtg", T0, T0))))),
    "another-date-field": lambda p: Or((*p[:-1], And((p[-1].filters[0],
                                                     During("seen", T0, T0 + 1000))))),
    "two-geometry-fields": lambda p: Or((*p[:-1], And((BBox("where", 0, 0, 1, 1),
                                                      p[-1].filters[1])))),
    "a-bare-box": lambda p: Or((*p[:-1], p[-1].filters[0])),
}


@pytest.mark.parametrize("case", sorted(CANNOT))
def test_an_or_it_cannot_express_exactly_is_not_converted(case):
    f = CANNOT[case](list(_objects(_boxes(20)).filters))
    assert Slices.of(f, "dtg") is None
    groups = time_slices(f, "dtg")  # as before PR 48: objects, or no slices at all
    assert groups is None or all(isinstance(g, Or) for g in groups)


def test_an_or_of_box_and_interval_slices_is_converted_once():
    boxes = _boxes(40)
    parts = list(_objects(boxes).filters)
    parts[7] = And(tuple(reversed(parts[7].filters)))  # During first: the same slice
    s = Slices.of(Or(tuple(parts)), "dtg")
    assert s == _carrier(boxes)
    assert Slices.of(_objects(boxes), "seen") is None  # not the type's date field


@pytest.mark.parametrize("n", [17, 33, 255, 256])
def test_the_carrier_is_cut_into_carriers_over_row_slices(n):
    boxes = _boxes(n)
    order = np.random.default_rng(n).permutation(n)
    s = _carrier([boxes[i] for i in order])
    groups = time_slices(s, "dtg")
    k = -(-n // MAX_DISJUNCTS)
    assert len(groups) == k and all(isinstance(g, Slices) for g in groups)
    sizes = [len(g) for g in groups]
    assert sum(sizes) == n and max(sizes) <= MAX_DISJUNCTS and max(sizes) - min(sizes) <= 1
    assert np.concatenate([g.windows for g in groups])[:, 0].tolist() == [b[4] for b in boxes]
    assert np.concatenate([g.boxes for g in groups]).tolist() == [list(b[:4]) for b in boxes]
    rest = Cmp("mmsi", "<", 50)
    under = time_slices(And((rest, s)), "dtg")
    assert [g.filters for g in under] == [(part, rest) for part in groups]


@pytest.mark.parametrize("why", ["sixteen", "no-date-field", "another-date-field", "open-ended",
                                 "two-carriers"])
def test_the_carrier_keeps_one_plan(why):
    s = _carrier(_boxes(40))
    f, dtg = s, "dtg"
    if why == "sixteen":
        f = s.take(slice(0, 16))
    elif why == "no-date-field":
        dtg = None
    elif why == "another-date-field":
        dtg = "seen"
    elif why == "open-ended":
        w = s.windows.copy()
        w[3, 0] = 0
        f = Slices("geom", "dtg", s.boxes, w)
    else:
        f = And((s, _carrier(_boxes(20, half=5.0))))
    assert time_slices(f, dtg) is None


# ------------------------------------------------------------------- the plan


def _same_plan(a, b, filters=True):
    assert (a.type_name, a.index, a.ids, a.limit, a.strategy) == (
        b.type_name, b.index, b.ids, b.limit, b.strategy)
    assert a.estimated_rows == b.estimated_rows and a.warnings == b.warnings
    assert (a.config is None) == (b.config is None)
    if a.config is not None:
        assert a.config.n_ranges == b.config.n_ranges
        for name in ("range_bins", "range_lo", "range_hi", "range_contained", "boxes",
                     "boxes_inner", "windows", "windows_inner"):
            x, y = getattr(a.config, name), getattr(b.config, name)
            assert (x is None) == (y is None), name
            assert x is None or (x.dtype == y.dtype and np.array_equal(x, y)), name
        for name in ("geom_precise", "time_precise", "contained_exact", "disjoint"):
            assert getattr(a.config, name) == getattr(b.config, name), name
    assert (a.union is None) == (b.union is None)
    assert len(a.union or []) == len(b.union or [])
    for x, y in zip(a.union or [], b.union or []):
        _same_plan(x, y)


@pytest.mark.parametrize("limit", [None, 7])
@pytest.mark.parametrize("case", ["8", "16", "17", "64", "256", "256-shuffled", "96-overlap",
                                  "64-residual"])
def test_the_plans_are_the_object_paths_field_for_field(case, limit, store, object_path):
    """The same slices as the carrier (arrays all the way) and as a
    hand-built ``Or`` on the path before PR 48 (``object_path``: nothing is
    converted): groups, index, ranges, boxes, windows, estimates."""
    n = int(case.split("-")[0])
    boxes = _boxes(n, overlap_ms=90_000 if "overlap" in case else 0)
    if "shuffled" in case:
        boxes = [boxes[i] for i in np.random.default_rng(5).permutation(n)]
    s, obj = _carrier(boxes), _objects(boxes)
    if "residual" in case:
        s, obj = And((s, Cmp("mmsi", "<", 50))), And((obj, Cmp("mmsi", "<", 50)))
    store.planner.invalidate_config_memo()
    a = store.planner.plan(TYPE, s, limit=limit)
    store.planner.invalidate_config_memo()
    b = store.planner.plan(TYPE, obj, limit=limit)
    assert (a.union is not None) == (n > MAX_DISJUNCTS)
    if n > MAX_DISJUNCTS:
        assert len(a.union) == -(-n // MAX_DISJUNCTS)
        assert all(isinstance(p.filter, (Or, And)) for p in b.union)  # the object path it is
    _same_plan(a, b)
    (many,) = store.planner.plan_many(TYPE, [s], limit=limit)
    _same_plan(many, a)


@pytest.mark.parametrize("n", [17, 256])
def test_a_hand_built_or_is_planned_through_the_carrier(n, store):
    boxes = _boxes(n)
    a, b = store.planner.plan(TYPE, _carrier(boxes)), store.planner.plan(TYPE, _objects(boxes))
    _same_plan(a, b)
    assert all(isinstance(p.filter, Slices) for p in b.union)
    assert isinstance(b.filter, Or)  # the caller's own filter names the query


def test_a_disjunct_it_cannot_express_keeps_the_object_path(store, rows):
    boxes = _boxes(64)
    parts = list(_objects(boxes).filters)
    parts[5] = And((*parts[5].filters, Cmp("mmsi", "<", 50)))
    f = Or(tuple(parts))
    plan = store.planner.plan(TYPE, f)
    assert len(plan.union) == 4 and all(isinstance(p.filter, Or) for p in plan.union)
    x, y, t, mmsi = rows
    want = np.union1d(_brute(rows, boxes[:5] + boxes[6:]), _brute(rows, boxes[5:6], mmsi < 50))
    assert np.array_equal(_ids(store.query(TYPE, f)), want)


# ---------------------------------------------------------------- the answers

ANSWERS = {
    # n, overlap_ms, half, residual, limit
    "16-one-scan": (16, 0, 0.05, False, None),
    "17": (17, 0, 0.05, False, None),
    "256": (256, 0, 0.05, False, None),
    "255-odd-groups": (255, 0, 0.05, False, None),
    "boundary-instants": (256, 0, 5.0, False, None),
    "overlap-dedup": (96, 90_000, 0.05, False, None),
    "and-residual": (64, 0, 0.05, True, None),
    "limit": (128, 0, 0.05, False, 25),
    "overlap-residual-limit": (200, 30_000, 0.05, True, 40),
}


@pytest.mark.parametrize("case", sorted(ANSWERS))
@pytest.mark.parametrize("through", ["query", "scheduler", "query_many"])
def test_the_answer_is_the_brute_forces(case, through, store, rows):
    n, overlap, half, residual, limit = ANSWERS[case]
    boxes = _boxes(n, overlap_ms=overlap, half=half)
    f, more = _carrier(boxes), None
    if residual:
        f, more = And((f, Cmp("mmsi", "<", 50))), rows[3] < 50
    want = _brute(rows, boxes, more)
    assert (store.planner.plan(TYPE, f).union is not None) == (n > MAX_DISJUNCTS)
    if through == "query":
        out = store.query(TYPE, f, limit=limit)
    elif through == "scheduler":
        with QueryScheduler(store, ServingConfig()) as sched:
            out = sched.query(TYPE, f, limit=limit)
    else:
        out, other = store.query_many(TYPE, [f, _carrier(_boxes(5))], limit=limit)
        beside = _brute(rows, _boxes(5))
        assert np.isin(_ids(other), beside).all()
        assert len(other) == (len(beside) if limit is None else min(limit, len(beside)))
    got = _ids(out)
    assert len(np.unique(got)) == len(got)  # no row twice
    if limit is None:
        assert np.array_equal(got, want) and len(want) > 50
    else:
        assert len(got) == limit < len(want) and np.isin(got, want).all()
    x, y, t, mmsi = rows
    ids = np.asarray(out.ids).astype(np.int64)
    assert np.array_equal(np.asarray(out.columns["dtg"], np.int64), t[ids])
    assert np.array_equal(np.asarray(out.columns["mmsi"]), mmsi[ids])


def test_rows_on_a_boundary_instant_and_the_last_instant(store, rows):
    """Half-open to the row: a report exactly at minute i belongs to slice
    i whichever groups the two fall in, and the last slice's end is out."""
    x, y, t, _ = rows
    got = _ids(store.query(TYPE, _carrier(_boxes(256, half=5.0))))
    corridor = np.abs(x) < 150  # the boxes hold all of it: time alone decides there
    inside = (t >= T0) & (t < T0 + 256 * STEP_MS) & corridor
    assert np.array_equal(got, np.flatnonzero(inside))
    edge = np.flatnonzero(((t - T0) % (MAX_DISJUNCTS * STEP_MS) == 0) & inside)
    assert len(edge) > 100 and np.isin(edge, got).all()
    last = np.flatnonzero((t == T0 + 256 * STEP_MS) & corridor)
    assert len(last) and not np.isin(last, got).any()


def test_a_full_scan_evaluates_the_carrier():
    """No index serves it (a type without a date field: z2 alone, and
    ``dtg`` a plain attribute no index reads): a union of full scans is one
    full scan, whose one pass is the carrier's own."""
    sft = FeatureType.from_spec("plain", "seen:Long,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    rng = np.random.default_rng(9)
    x, y = rng.uniform(-10, 10, 4000), rng.uniform(-5, 5, 4000)
    seen = rng.integers(0, 40, 4000) * 1000
    ds.write("plain", FeatureCollection.from_columns(
        sft, np.arange(4000, dtype=np.int64), {"seen": seen, "geom": (x, y)}), check_ids=False)
    lo = np.arange(20) * 2000
    s = Slices("geom", "seen", np.tile([-5.0, -5.0, 5.0, 5.0], (20, 1)), np.stack([lo, lo + 1000], 1))
    want = np.flatnonzero((abs(x) <= 5) & (seen % 2000 < 1000))
    assert np.array_equal(_ids(ds.query("plain", s)), want) and len(want) > 100


# ------------------------------------------------------------------ the trace


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


@pytest.mark.parametrize("case", ["carrier-8", "carrier-16", "carrier-17", "carrier-256",
                                  "or-256", "or-8", "inexpressible-64", "seam-20", "bbox"])
def test_the_plan_span_counts_the_slices_that_reached_the_indexes_as_rows(case, store, traced):
    kind, _, n = case.partition("-")
    boxes = _boxes(int(n or 1))
    if kind == "carrier":
        f, want = _carrier(boxes), len(boxes)
    elif kind == "or":  # converted past sixteen; sixteen or fewer stay objects in one scan
        f, want = _objects(boxes), len(boxes) if len(boxes) > MAX_DISJUNCTS else 0
    elif kind == "inexpressible":
        parts = list(_objects(boxes).filters)
        parts[5] = And((*parts[5].filters, Cmp("mmsi", "<", 50)))
        f, want = Or(tuple(parts)), 0
    elif kind == "seam":  # a slice across the seam is two rows
        f = Slices("geom", "dtg", [(179.5, -1, 180.5, 1)] * 20,
                   [[T0 + i * STEP_MS, T0 + (i + 1) * STEP_MS] for i in range(20)])
        want = 40
    else:
        f, want = BBox("geom", -122, 36, -121, 37), 0
    store.query(TYPE, f)
    (tr,) = traced.traces()
    (plan,) = [s for s in tr.spans if s.name == "plan"]
    assert plan.attrs["slice_rows"] == want
    assert plan.attrs["members"] == plan.attrs["batched"] == 1
