"""Stats sketches + cost-based strategy selection."""

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.stats import Frequency, Histogram, MinMax, StatsStore, TopK, Z3Histogram


def test_minmax_merge():
    a, b = MinMax(), MinMax()
    a.observe(np.array([3, 7, 5]))
    b.observe(np.array([1, 9]))
    a += b
    assert a.bounds == (1, 9)
    assert a.count == 5


def test_histogram_estimate():
    h = Histogram(10, 0.0, 100.0)
    h.observe(np.random.default_rng(0).uniform(0, 100, 10000))
    est = h.estimate_range(20.0, 40.0)
    assert 1700 < est < 2300


def test_frequency_estimate():
    f = Frequency()
    col = np.array(["a"] * 500 + ["b"] * 50 + [f"x{i}" for i in range(100)])
    f.observe(col)
    assert f.estimate("a") >= 500
    assert f.estimate("a") < 700  # count-min overestimates but not wildly
    assert f.estimate("b") >= 50


def test_topk():
    t = TopK(k=2)
    t.observe(np.array(["a"] * 9 + ["b"] * 5 + ["c"]))
    assert [v for v, _ in t.top()] == ["a", "b"]
    other = TopK(k=2)
    other.observe(np.array(["c"] * 20))
    t += other
    assert t.top()[0][0] == "c"


def test_z3_histogram_estimate():
    rng = np.random.default_rng(1)
    n = 20000
    bins = rng.integers(0, 4, n).astype(np.int32)
    zs = rng.integers(0, 1 << 30, n).astype(np.uint64)
    h = Z3Histogram(30, prefix_bits=10)
    h.observe(bins, zs)
    # whole-space ranges per bin should estimate ~n
    est = h.estimate(
        np.array([0, 1, 2, 3]),
        np.zeros(4, np.uint64),
        np.full(4, (1 << 30) - 1, np.uint64),
    )
    assert 0.9 * n < est < 1.1 * n
    # half the z space ~ half the rows
    est_half = h.estimate(
        np.array([0, 1, 2, 3]),
        np.zeros(4, np.uint64),
        np.full(4, (1 << 29) - 1, np.uint64),
    )
    assert 0.4 * n < est_half < 0.6 * n


def _store(n=3000):
    sft = FeatureType.from_spec("t", "name:String,age:Int,dtg:Date,*geom:Point:srid=4326")
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    rng = np.random.default_rng(5)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(n)],
        {
            "name": np.array(["alice", "bob"] * (n // 2)),
            "age": rng.integers(0, 90, n),
            "dtg": t0 + rng.integers(0, 30 * 86400_000, n),
            "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)),
        },
    )
    ds.write("t", fc)
    return ds


def test_store_stats_built():
    ds = _store()
    st = ds.stats_for("t")
    assert isinstance(st, StatsStore)
    assert st.total_count() == 3000
    assert st.attribute_bounds("age") is not None
    assert st.estimate_equality("name", "alice") >= 1400
    lo, hi = st.attribute_bounds("age")
    assert st.estimate_range("age", float(lo), float(hi)) > 2500
    assert st.z3 is not None


def test_cost_prefers_selective_index():
    """The decider picks z3 over z2 for bbox+time (smaller span cost), and
    the explain trace records the costs (reference StrategyDecider)."""
    ds = _store()
    trace = ds.explain(
        "t",
        "bbox(geom, -10, -10, 10, 10) AND dtg DURING 2024-01-02T00:00:00Z/2024-01-04T00:00:00Z",
    )
    assert "Strategy: z3" in trace
    trace2 = ds.explain("t", "bbox(geom, -10, -10, 10, 10)")
    assert "Strategy: z2" in trace2


def test_histogram_rebin_merge():
    a = Histogram(10, 0.0, 10.0)
    a.observe(np.full(100, 5.0))
    b = Histogram(10, 50.0, 100.0)
    b.observe(np.full(50, 75.0))
    a += b
    assert a.lo == 0.0 and a.hi == 100.0
    assert a.counts.sum() == 150
    assert 90 < a.estimate_range(0.0, 10.0) < 110
    assert 40 < a.estimate_range(70.0, 80.0) < 60


def test_incremental_write_stats():
    """Stats accumulate across write batches (no full rebuild, no
    double-counted z3 sketch)."""
    sft = FeatureType.from_spec("inc", "name:String,dtg:Date,*geom:Point:srid=4326")
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    rng = np.random.default_rng(9)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)

    def batch(k, n):
        return FeatureCollection.from_columns(
            sft,
            [f"{k}-{i}" for i in range(n)],
            {
                "name": np.array([f"u{i % 5}" for i in range(n)]),
                "dtg": t0 + rng.integers(0, 86400_000, n),
                "geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)),
            },
        )

    ds.write("inc", batch(0, 500))
    ds.write("inc", batch(1, 700))
    st = ds.stats_for("inc")
    assert st.total_count() == 1200
    # sketch mass equals row count exactly once (delta feeding)
    assert sum(st.z3.cells.values()) == 1200


def test_estimate_count():
    ds = _store()
    q = "bbox(geom, -60, -40, 60, 40) AND dtg DURING 2024-01-05T00:00:00Z/2024-01-15T00:00:00Z"
    est = ds.estimate_count("t", q)
    exact = ds.count("t", q)
    assert exact > 0
    assert 0.5 * exact < est < 2.0 * exact


def test_cost_changes_with_distribution():
    """Cost reflects actual data distribution: a bbox covering the dense
    half of the data costs more than the empty half (VERDICT task 8)."""
    sft = FeatureType.from_spec("d", "dtg:Date,*geom:Point:srid=4326")
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    n = 4000
    rng = np.random.default_rng(6)
    # all points in the eastern hemisphere
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(n)],
        {
            "dtg": t0 + rng.integers(0, 86400_000, n),
            "geom": (rng.uniform(10, 170, n), rng.uniform(-80, 80, n)),
        },
    )
    ds.write("d", fc)
    from geomesa_tpu.filter import ecql

    dense = ecql.parse("bbox(geom, 10, -80, 170, 80)")
    empty = ecql.parse("bbox(geom, -170, -80, -10, 80)")
    idx = [i for i in ds.indexes("d") if i.name == "z2"][0]
    c_dense = ds.planner.cost("d", "z2", idx.scan_config(dense))
    c_empty = ds.planner.cost("d", "z2", idx.scan_config(empty))
    assert c_dense > 100 * c_empty


class TestMarginalEstimator:
    """Marginal-histogram selectivity (estimate_bbox / estimate_filter):
    the bbox-only and spatio-temporal estimate paths on a z3-keyed store
    (the z-prefix sketch alone underestimated clustered data ~17x)."""

    @pytest.fixture(scope="class")
    def st_store(self):
        from geomesa_tpu.datastore import DataStore
        from geomesa_tpu.features import FeatureCollection

        rng = np.random.default_rng(31)
        sft = FeatureType.from_spec("st", "dtg:Date,*geom:Point:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "z3,z2"
        ds = DataStore()
        ds.create_schema(sft)
        n = 30000
        x = rng.normal(0, 0.5, n)
        y = rng.normal(0, 0.5, n)
        t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
        t = t0 + rng.integers(0, 30 * 86400_000, n)
        ds.write(
            "st",
            FeatureCollection.from_columns(
                sft, np.arange(n), {"dtg": t, "geom": (x, y)}
            ),
            check_ids=False,
        )
        return ds, (x, y, t)

    def test_bbox_only_on_z3_store(self, st_store):
        ds, (x, y, t) = st_store
        est = ds.estimate_count("st", "bbox(geom, -1, -1, 1, 1)")
        true = int(((x >= -1) & (x <= 1) & (y >= -1) & (y <= 1)).sum())
        assert 0.3 * true < est < 3 * true

    def test_spatiotemporal_product(self, st_store):
        ds, (x, y, t) = st_store
        lo = np.datetime64("2024-01-05", "ms").astype(np.int64)
        hi = np.datetime64("2024-01-20", "ms").astype(np.int64)
        est = ds.estimate_count(
            "st",
            "bbox(geom, -1, -1, 1, 1) AND dtg DURING "
            "2024-01-05T00:00:00Z/2024-01-20T00:00:00Z",
        )
        m = (x >= -1) & (x <= 1) & (y >= -1) & (y <= 1) & (t >= lo) & (t < hi)
        true = int(m.sum())
        assert 0.3 * true < est < 3 * true

    def test_disjoint_estimates_zero(self, st_store):
        ds, _ = st_store
        assert ds.estimate_count(
            "st", "bbox(geom, 0, 0, 1, 1) AND bbox(geom, 5, 5, 6, 6)"
        ) == 0

    def test_sparse_region_radius_grows(self, st_store):
        from geomesa_tpu.process.knn import _estimate_radius_m

        ds, _ = st_store
        r_dense = _estimate_radius_m(ds, "st", 10, 0.0, 0.0, 5e6)
        r_sparse = _estimate_radius_m(ds, "st", 10, 40.0, 40.0, 5e6)
        assert r_sparse > 10 * r_dense


class TestTakeBoundsGuard:
    def test_out_of_range_raises_and_negative_works(self):
        from geomesa_tpu.features import FeatureCollection

        sft = FeatureType.from_spec("t", "v:Integer,*geom:Point:srid=4326")
        n = 100
        fc = FeatureCollection.from_columns(
            sft, np.arange(n),
            {"v": np.arange(n), "geom": (np.zeros(n), np.zeros(n))},
        )
        with pytest.raises(IndexError):
            fc.take(np.array([n]))
        assert int(np.asarray(fc.take(np.array([-1])).columns["v"])[0]) == n - 1


def test_string_column_with_nones_writes_and_queries():
    """None in a String column must not crash the write-path sketches
    (np.unique can't sort mixed None/str); IS NULL and equality still
    answer correctly."""
    sft = FeatureType.from_spec("s", "name:String,*geom:Point:srid=4326")
    ds = DataStore()
    ds.create_schema(sft)
    names = np.empty(4, dtype=object)
    names[:] = ["a", None, "b", None]
    ds.write("s", FeatureCollection.from_columns(
        sft, np.arange(4), {"name": names, "geom": (np.arange(4.0), np.zeros(4))}
    ))
    assert sorted(np.asarray(ds.query("s", "name IS NULL").ids, np.int64).tolist()) == [1, 3]
    assert np.asarray(ds.query("s", "name = 'a'").ids, np.int64).tolist() == [0]


class TestDescriptiveStats:
    """Mergeable moments sketch vs numpy ground truth (reference
    DescriptiveStats.scala)."""

    def test_univariate_vs_numpy(self):
        from geomesa_tpu.stats.sketches import DescriptiveStats

        rng = np.random.default_rng(3)
        x = rng.gamma(2.0, 3.0, 10_000)  # skewed so g1/g2 are non-trivial
        d = DescriptiveStats(1)
        d.observe(x)
        assert d.count == len(x)
        assert d.min[0] == x.min() and d.max[0] == x.max()
        assert d.mean[0] == pytest.approx(x.mean(), rel=1e-12)
        assert d.variance(sample=False)[0] == pytest.approx(x.var(), rel=1e-10)
        assert d.variance(sample=True)[0] == pytest.approx(x.var(ddof=1), rel=1e-10)
        m = x.mean()
        g1 = np.mean((x - m) ** 3) / np.var(x) ** 1.5
        g2 = np.mean((x - m) ** 4) / np.var(x) ** 2 - 3.0
        assert d.skewness()[0] == pytest.approx(g1, rel=1e-8)
        assert d.kurtosis()[0] == pytest.approx(g2, rel=1e-8)

    def test_merge_exact(self):
        from geomesa_tpu.stats.sketches import DescriptiveStats

        rng = np.random.default_rng(4)
        x = rng.normal(5, 2, 5000)
        y = 0.5 * x + rng.normal(0, 1, 5000)
        whole = DescriptiveStats(2)
        whole.observe(x, y)
        merged = DescriptiveStats(2)
        for lo, hi in ((0, 1234), (1234, 1235), (1235, 5000)):
            part = DescriptiveStats(2)
            part.observe(x[lo:hi], y[lo:hi])
            merged += part
        assert merged.count == whole.count
        np.testing.assert_allclose(merged.mean, whole.mean, rtol=1e-12)
        np.testing.assert_allclose(merged.m2, whole.m2, rtol=1e-9)
        np.testing.assert_allclose(merged.m3, whole.m3, rtol=1e-8)
        np.testing.assert_allclose(merged.m4, whole.m4, rtol=1e-8)
        np.testing.assert_allclose(merged.comoment, whole.comoment, rtol=1e-9)

    def test_covariance_correlation(self):
        from geomesa_tpu.stats.sketches import DescriptiveStats

        rng = np.random.default_rng(5)
        x = rng.normal(0, 1, 8000)
        y = 0.8 * x + rng.normal(0, 0.6, 8000)
        d = DescriptiveStats(2)
        d.observe(x, y)
        want = np.cov(np.stack([x, y]), ddof=1)
        np.testing.assert_allclose(d.covariance(True), want, rtol=1e-9)
        corr = np.corrcoef(x, y)
        np.testing.assert_allclose(d.correlation(), corr, rtol=1e-9)
        j = d.to_json()
        assert j["count"] == 8000 and len(j["correlation"]) == 2

    def test_empty_and_dsl(self):
        from geomesa_tpu.stats import stat_spec
        from geomesa_tpu.stats.sketches import DescriptiveStats

        assert DescriptiveStats(1).to_json() == {"count": 0}
        sft = FeatureType.from_spec("d", "a:Double,b:Double,*geom:Point:srid=4326")
        n = 100
        rng = np.random.default_rng(6)
        a, b = rng.normal(0, 1, n), rng.normal(0, 1, n)
        fc = FeatureCollection.from_columns(
            sft, np.arange(n).astype(str),
            {"a": a, "b": b, "geom": (np.zeros(n), np.zeros(n))},
        )
        (res,) = stat_spec.evaluate("DescriptiveStats(a,b)", fc)
        assert res.count == n
        assert res.mean[0] == pytest.approx(a.mean())
        # SeqStat: a ';' list yields one sketch per term
        seq = stat_spec.evaluate("Count();DescriptiveStats(a)", fc)
        assert len(seq) == 2 and seq[0].count == n


class TestZ3Frequency:
    def test_point_estimates(self):
        from geomesa_tpu.stats.sketches import Z3Frequency

        rng = np.random.default_rng(7)
        total_bits = 42
        zf = Z3Frequency(total_bits=total_bits, prefix_bits=12)
        # two hot cells + background noise
        hot_z = np.uint64(0x123) << np.uint64(30)
        bins = np.concatenate([
            np.full(5000, 10), np.full(3000, 11),
            rng.integers(0, 8, 2000),
        ]).astype(np.uint64)
        zs = np.concatenate([
            np.full(5000, hot_z),
            np.full(3000, hot_z),
            rng.integers(0, 1 << 42, 2000).astype(np.uint64),
        ])
        zf.observe(bins, zs)
        assert zf.count == 10000
        # count-min overestimates only
        assert zf.estimate(10, int(hot_z)) >= 5000
        assert zf.estimate(11, int(hot_z)) >= 3000
        assert zf.estimate(10, int(hot_z)) <= 5000 + 2000
        # a cold cell stays near zero
        assert zf.estimate(300, 0) < 500

    def test_merge(self):
        from geomesa_tpu.stats.sketches import Z3Frequency

        a = Z3Frequency(total_bits=42)
        b = Z3Frequency(total_bits=42)
        a.observe(np.full(100, 5), np.full(100, 1 << 20))
        b.observe(np.full(50, 5), np.full(50, 1 << 20))
        a += b
        assert a.estimate(5, 1 << 20) >= 150


class TestStatsReviewFixes:
    def test_nan_rows_skipped(self):
        from geomesa_tpu.stats.sketches import DescriptiveStats

        x = np.array([1.0, 2.0, np.nan, 4.0])
        y = np.array([10.0, 20.0, 30.0, 40.0])
        d = DescriptiveStats(2)
        d.observe(x, y)
        assert d.count == 3  # NaN row dropped entirely
        assert d.mean[0] == pytest.approx(np.mean([1, 2, 4]))
        assert d.mean[1] == pytest.approx(np.mean([10, 20, 40]))
        assert not np.isnan(d.variance()).any()

    def test_z3frequency_merge_mismatch_refused(self):
        from geomesa_tpu.stats.sketches import Z3Frequency

        a = Z3Frequency(total_bits=42, prefix_bits=12)
        b = Z3Frequency(total_bits=42, prefix_bits=16)
        with pytest.raises(ValueError):
            a += b
        with pytest.raises(ValueError):
            Z3Frequency(total_bits=42, prefix_bits=0)

    def test_z3frequency_no_bin_alias(self):
        from geomesa_tpu.stats.sketches import Z3Frequency

        # full-resolution prefix: z occupies 42 bits; bins must not bleed
        zf = Z3Frequency(total_bits=42, prefix_bits=42)
        z_big = (1 << 40) + 17
        zf.observe(np.full(1000, 0), np.full(1000, z_big))
        assert zf.estimate(0, z_big) >= 1000
        assert zf.estimate(1, z_big) < 500  # distinct bin, same z
        assert zf.estimate(1, z_big - (1 << 40)) < 500

    def test_empty_spec_rejected(self):
        from geomesa_tpu.stats import stat_spec

        with pytest.raises(ValueError, match="at least one attribute"):
            stat_spec.parse("DescriptiveStats()")
