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
