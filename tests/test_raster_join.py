"""Raster-interval polygon approximations + adaptive join planning.

The contract under test (docs/joins.md): the raster tier MOVES work, it
never changes answers —

- raster-filtered query results are bit-identical to the exact
  (raster-disabled) path and to a shapely oracle, across concave
  polygons, holes, cells straddling boundaries, slivers thinner than a
  raster cell, and rasters with empty residue;
- interval classification never flips a definite-in/definite-out label
  (full => truly inside, out => truly outside) under fuzzing;
- every adaptive join strategy (exact / raster / fused probe /
  host-raster broad path) returns the same pairs.
"""

import numpy as np
import pytest

from geomesa_tpu import DataStore, FeatureCollection, FeatureType
from geomesa_tpu import geometry as geo
from geomesa_tpu.conf import (
    JOIN_BROAD_FRACTION, RASTER_ENABLED, RASTER_MIN_EDGES, RASTER_RESIDUE,
)
from geomesa_tpu.filter import raster as fr
from geomesa_tpu.filter.predicates import Intersects
from geomesa_tpu.scan import block_kernels as bk

shapely = pytest.importorskip("shapely")
from shapely.geometry import Point as SPoint  # noqa: E402
from shapely.geometry import Polygon as SPolygon  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_raster_conf():
    """Raster on (the default), caches clean, per test."""
    fr.clear_cache()
    yield
    for prop in (RASTER_ENABLED, RASTER_MIN_EDGES, RASTER_RESIDUE,
                 JOIN_BROAD_FRACTION):
        prop.clear()
    fr.clear_cache()


def jagged_star(cx, cy, r, n_arms, seed=0):
    rng = np.random.default_rng(seed)
    a = np.linspace(0, 2 * np.pi, 2 * n_arms + 1)[:-1]
    rad = np.where(
        np.arange(2 * n_arms) % 2 == 0, r, r * rng.uniform(0.3, 0.7, 2 * n_arms)
    )
    return geo.Polygon(
        [(cx + rr * np.cos(t), cy + rr * np.sin(t)) for t, rr in zip(a, rad)]
    )


def donut(cx, cy, r_out, r_in, n=24):
    a = np.linspace(0, 2 * np.pi, n + 1)
    shell = [(cx + r_out * np.cos(t), cy + r_out * np.sin(t)) for t in a]
    hole = [(cx + r_in * np.cos(t), cy + r_in * np.sin(t)) for t in a]
    return geo.Polygon(shell, [hole])


def to_shapely(p: geo.Polygon) -> SPolygon:
    return SPolygon(p.shell, [h for h in p.holes])


TEST_POLYGONS = [
    ("concave_star", jagged_star(10.0, 20.0, 3.0, 12, seed=1)),
    ("big_star_256e", jagged_star(-40.0, -10.0, 5.0, 127, seed=2)),
    ("donut_hole", donut(60.0, 30.0, 4.0, 2.0)),
    # a sliver thinner than any margin-safe raster cell: rasterization
    # must decline or stay all-partial — either way results stay exact
    ("thin_sliver", geo.Polygon(
        [(0.0, 0.0), (4.0, 1e-4), (4.0, 2e-4), (0.0, 1e-4), (0.0, 0.0)]
    )),
]


def make_point_store(n=120_000, seed=7, index="z2", lo=(-60, -40), hi=(80, 45)):
    rng = np.random.default_rng(seed)
    x = rng.uniform(lo[0], hi[0], n)
    y = rng.uniform(lo[1], hi[1], n)
    sft = FeatureType.from_spec("pts", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = index
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("pts", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (x, y)}), check_ids=False)
    return ds, x, y


def query_ids(ds, f):
    return np.sort(np.asarray(ds.query("pts", f).ids).astype(np.int64))


class TestRasterBuild:
    def test_classes_cover_and_margin(self):
        p = jagged_star(10.0, 20.0, 3.0, 12, seed=3)
        ap = fr.build_raster(p)
        assert ap is not None
        full, part, out = ap.cell_counts
        assert full > 0 and part > 0
        # full cells' centers AND corners (the margin guarantee's easy
        # checkable consequence) are inside the shapely polygon
        sp = to_shapely(p)
        jj, ii = np.nonzero(ap.classes == geo.RASTER_FULL)
        for j, i in list(zip(jj.tolist(), ii.tolist()))[::7][:64]:
            for dx in (0.0, 1.0):
                for dy in (0.0, 1.0):
                    px = ap.x0 + (i + dx) * ap.cell_w
                    py = ap.y0 + (j + dy) * ap.cell_h
                    assert sp.covers(SPoint(px, py)), (i, j)
        jj, ii = np.nonzero(ap.classes == geo.RASTER_OUT)
        for j, i in list(zip(jj.tolist(), ii.tolist()))[::17][:64]:
            px = ap.x0 + (i + 0.5) * ap.cell_w
            py = ap.y0 + (j + 0.5) * ap.cell_h
            assert not sp.intersects(SPoint(px, py)), (i, j)

    def test_sliver_declines_or_all_partial(self):
        p = dict(TEST_POLYGONS)["thin_sliver"]
        ap = fr.build_raster(p)
        # margin-safe cells are far wider than the sliver: no FULL cell
        # may exist (it would wrongly certify points near the boundary)
        if ap is not None:
            assert (ap.classes != geo.RASTER_FULL).all()

    def test_fuzz_labels_never_flip(self):
        """The acceptance fuzz case: for random points, a FULL label
        implies shapely-covered, an OUT label implies shapely-disjoint.
        PARTIAL carries no claim (the exact predicate decides)."""
        rng = np.random.default_rng(11)
        for seed in range(6):
            p = jagged_star(
                float(rng.uniform(-50, 50)), float(rng.uniform(-30, 30)),
                float(rng.uniform(0.5, 4.0)), int(rng.integers(5, 60)),
                seed=seed,
            )
            ap = fr.build_raster(p)
            if ap is None:
                continue
            sp = to_shapely(p)
            x0, y0, x1, y1 = p.bounds()
            px = rng.uniform(x0 - 0.5, x1 + 0.5, 500)
            py = rng.uniform(y0 - 0.5, y1 + 0.5, 500)
            cls = ap.classify_points(px, py)
            for k in np.flatnonzero(cls == geo.RASTER_FULL):
                assert sp.covers(SPoint(px[k], py[k]))
            for k in np.flatnonzero(cls == geo.RASTER_OUT):
                assert not sp.intersects(SPoint(px[k], py[k]))

    def test_zranges_partition_by_class(self):
        p = jagged_star(10.0, 20.0, 2.0, 8, seed=4)
        ap = fr.build_raster(p)
        lo, hi, cont = ap.zranges()
        assert len(lo) and (lo <= hi).all()
        assert (lo[1:] > hi[:-1]).all()  # disjoint ascending
        assert cont.any() and (~cont).any()
        # coalescing keeps coverage and never invents containment
        clo, chi, ccont = ap.zranges(max_ranges=max(4, len(lo) // 8))
        assert len(clo) <= max(4, len(lo) // 8)
        assert int(ccont.sum()) <= int(cont.sum())

    def test_pack_block_coalesces_to_bucket(self):
        p = jagged_star(10.0, 20.0, 3.0, 24, seed=5)
        ap = fr.build_raster(p)
        for bucket in (16, 64):
            blk = ap.pack_block(bucket)
            assert blk.shape == (1 + bucket, bk.LANES)
            # pad/used interval rows never claim full beyond the source
            assert (blk[1:, 0] <= blk[1:, 1]).sum() <= bucket


class TestRasterQueryDifferential:
    """Raster-filtered scan results bit-identical to the exact path and
    to the shapely oracle — the acceptance differential suite."""

    @pytest.mark.parametrize("name,poly", TEST_POLYGONS)
    def test_query_identical_and_oracle(self, name, poly):
        ds, x, y = make_point_store()
        f = Intersects("geom", poly)
        got_on = query_ids(ds, f)
        RASTER_ENABLED.set(False)
        fr.clear_cache()
        ds.planner.invalidate_config_memo()
        got_off = query_ids(ds, f)
        assert np.array_equal(got_on, got_off), name
        # shapely oracle over a sample (full oracle is O(n) shapely calls)
        sp = to_shapely(poly)
        mine = np.zeros(len(x), bool)
        mine[got_on] = True
        idx = np.random.default_rng(3).integers(0, len(x), 2000)
        want = np.array([
            sp.intersects(SPoint(float(x[k]), float(y[k]))) for k in idx
        ])
        assert np.array_equal(want, mine[idx]), name

    def test_empty_residue_polygon(self):
        """A cell-aligned rectangle-ish polygon large enough that some
        queries resolve with certain rows only — still exact. (Rectangles
        bypass the raster via the box path; a near-rectangular octagon
        exercises raster with a tiny residue.)"""
        p = geo.Polygon([
            (0, 0), (20, 0), (25, 5), (25, 25), (20, 30), (0, 30),
            (-5, 25), (-5, 5), (0, 0),
        ])
        ds, x, y = make_point_store(n=60_000, seed=9)
        f = Intersects("geom", p)
        got_on = query_ids(ds, f)
        RASTER_ENABLED.set(False)
        fr.clear_cache()
        ds.planner.invalidate_config_memo()
        assert np.array_equal(got_on, query_ids(ds, f))

    def test_device_residue_masks_bit_identical(self):
        """geomesa.raster.residue=device: the kernel's raster leg runs
        the exact _pip_unrolled/_pip_loop on the boundary residue, so
        final (ordinals, certain-refined) results equal the pre-raster
        path AND the raster-off masks agree post-refinement."""
        RASTER_RESIDUE.set("device")
        ds, x, y = make_point_store(n=60_000, seed=13)
        poly = jagged_star(10.0, 5.0, 4.0, 10, seed=6)
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        cfg = idx.scan_config(Intersects("geom", poly))
        assert cfg.rast is not None and cfg.poly is not None
        table = ds.table("pts", "z2")
        rows_on, cert_on = table.scan(cfg)
        RASTER_ENABLED.set(False)
        fr.clear_cache()
        ds.planner.invalidate_config_memo()
        cfg_off = idx.scan_config(Intersects("geom", poly))
        assert cfg_off.rast is None
        rows_off, cert_off = table.scan(cfg_off)
        # device residue reuses the PIP tier verbatim: refined hit sets
        # agree exactly
        def refined(rows, cert):
            unc = np.flatnonzero(~cert)
            keep = cert.copy()
            if len(unc):
                keep[unc] = geo.points_in_polygon(x[rows[unc]], y[rows[unc]], poly)
            return np.sort(rows[keep])

        assert np.array_equal(refined(rows_on, cert_on), refined(rows_off, cert_off))
        # and every row the raster path certifies IS a true hit
        sp = to_shapely(poly)
        sample = rows_on[cert_on][::37][:100]
        for r in sample:
            assert sp.covers(SPoint(float(x[r]), float(y[r])))

    def test_fused_batch_equals_per_query(self):
        ds, _, _ = make_point_store(n=60_000, seed=17)
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(23)
        cfgs = [
            idx.scan_config(Intersects("geom", jagged_star(
                float(rng.uniform(-40, 60)), float(rng.uniform(-30, 35)),
                float(rng.uniform(0.5, 3.0)), int(rng.integers(5, 40)),
                seed=k,
            )))
            for k in range(9)
        ]
        assert any(c.rast is not None for c in cfgs)
        table = ds.table("pts", "z2")
        fused = [f() for f in table.scan_submit_many(list(cfgs))]
        for cfg, (rows, cert) in zip(cfgs, fused):
            er, ec = table.scan(cfg)
            assert np.array_equal(rows, er)
            assert np.array_equal(cert, ec)

    def test_z3_raster_kernel_tier(self):
        """z3 keeps bbox-derived ranges but rides the kernel raster leg:
        results identical with raster on/off."""
        rng = np.random.default_rng(29)
        n = 50_000
        t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
        sft = FeatureType.from_spec("pts", "dtg:Date,*geom:Point:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "z3"
        ds = DataStore()
        ds.create_schema(sft)
        x = rng.uniform(-30, 30, n)
        y = rng.uniform(-25, 25, n)
        t = t0 + rng.integers(0, 20 * 86400_000, n)
        ds.write("pts", FeatureCollection.from_columns(
            sft, np.arange(n), {"dtg": t, "geom": (x, y)}), check_ids=False)
        from geomesa_tpu.filter.predicates import During

        poly = jagged_star(5.0, 3.0, 6.0, 14, seed=8)
        f = Intersects("geom", poly) & During(
            "dtg", t0, t0 + 12 * 86400_000
        )
        idx = next(i for i in ds.indexes("pts") if i.name == "z3")
        assert idx.scan_config(f).rast is not None
        on = query_ids(ds, f)
        RASTER_ENABLED.set(False)
        fr.clear_cache()
        ds.planner.invalidate_config_memo()
        assert np.array_equal(on, query_ids(ds, f))


class TestAdaptiveJoin:
    def _stores(self, n=40_000, n_poly=12, seed=31):
        from geomesa_tpu.sql.join import spatial_join  # noqa: F401

        rng = np.random.default_rng(seed)
        x = rng.uniform(-50, 50, n)
        y = rng.uniform(-40, 40, n)
        sft = FeatureType.from_spec("pts", "*geom:Point:srid=4326")
        right = FeatureCollection.from_columns(sft, np.arange(n), {"geom": (x, y)})
        polys = [
            jagged_star(
                float(rng.uniform(-40, 40)), float(rng.uniform(-30, 30)),
                float(rng.uniform(1.0, 8.0)), int(rng.integers(4, 50)), seed=k,
            )
            for k in range(n_poly)
        ]
        gsft = FeatureType.from_spec("polys", "*geom:Polygon:srid=4326")
        left = FeatureCollection.from_columns(
            gsft, np.arange(n_poly),
            {"geom": geo.PackedGeometryColumn.from_geometries(polys)},
        )
        return left, right, sft, x, y

    @pytest.mark.parametrize("predicate", ["intersects", "contains"])
    def test_strategies_identical(self, predicate):
        from geomesa_tpu.sql.join import spatial_join

        left, right, *_ = self._stores()
        exact = spatial_join(left, right, predicate, strategy="exact")
        rast = spatial_join(left, right, predicate, strategy="raster")
        auto = spatial_join(left, right, predicate, strategy="auto")
        for got in (rast, auto):
            assert np.array_equal(exact[0], got[0])
            assert np.array_equal(exact[1], got[1])

    def test_raster_strategy_counted(self):
        from geomesa_tpu.metrics import MetricsRegistry
        from geomesa_tpu.sql.join import spatial_join

        left, right, *_ = self._stores()
        m = MetricsRegistry()
        spatial_join(left, right, "intersects", strategy="raster", metrics=m)
        assert m.counter_value("geomesa.join.strategy.raster") > 0
        assert m.counter_value("geomesa.join.raster.decided") > 0

    def test_indexed_join_raster_on_off(self):
        from geomesa_tpu.sql.join import spatial_join_indexed

        left, right, sft, x, y = self._stores(n=60_000)
        ds = DataStore()
        ds.create_schema(sft)
        ds.write("pts", right, check_ids=False)
        on = spatial_join_indexed(ds, "pts", left, "contains")
        RASTER_ENABLED.set(False)
        fr.clear_cache()
        ds.planner.invalidate_config_memo()
        off = spatial_join_indexed(ds, "pts", left, "contains")
        assert np.array_equal(on[0], off[0])
        assert np.array_equal(on[1], off[1])

    def test_indexed_join_broad_host_path(self):
        """A polygon covering most of the store routes to the host-raster
        strategy (geomesa.join.strategy.host_raster) with identical
        pairs."""
        from geomesa_tpu.metrics import MetricsRegistry
        from geomesa_tpu.sql.join import spatial_join_indexed

        left, right, sft, x, y = self._stores(n=50_000, n_poly=3)
        # one near-world-sized polygon forces the broad path
        big = jagged_star(0.0, 0.0, 80.0, 20, seed=99)
        gsft = FeatureType.from_spec("polys", "*geom:Polygon:srid=4326")
        geoms = left.geom_column.geometries() + [big]
        left2 = FeatureCollection.from_columns(
            gsft, np.arange(len(geoms)),
            {"geom": geo.PackedGeometryColumn.from_geometries(geoms)},
        )
        ds = DataStore()
        ds.create_schema(sft)
        ds.write("pts", right, check_ids=False)
        JOIN_BROAD_FRACTION.set(0.2)
        m = MetricsRegistry()
        adaptive = spatial_join_indexed(ds, "pts", left2, "contains", metrics=m)
        assert m.counter_value("geomesa.join.strategy.host_raster") >= 1
        assert m.counter_value("geomesa.join.strategy.probe") >= 1
        JOIN_BROAD_FRACTION.set(2.0)  # probe-only: no broad routing
        plain = spatial_join_indexed(ds, "pts", left2, "contains")
        assert np.array_equal(adaptive[0], plain[0])
        assert np.array_equal(adaptive[1], plain[1])


class TestJoinProcessSelectivity:
    def test_in_cap_fallback_counted_and_traced(self):
        from geomesa_tpu.metrics import MetricsRegistry
        from geomesa_tpu.planning.explain import Explainer
        from geomesa_tpu.process import join_search

        rng = np.random.default_rng(41)
        n = 3000
        sft_a = FeatureType.from_spec(
            "tracks", "vessel:String,*geom:Point:srid=4326"
        )
        sft_b = FeatureType.from_spec(
            "vessels", "vessel:String,*geom:Point:srid=4326"
        )
        ds = DataStore()
        ds.create_schema(sft_a)
        ds.create_schema(sft_b)
        names = np.array([f"v{k}" for k in range(n)])
        for tname, sft in (("tracks", sft_a), ("vessels", sft_b)):
            ds.write(tname, FeatureCollection.from_columns(
                sft, np.arange(n),
                {"vessel": names,
                 "geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n))},
            ), check_ids=False)
        m = MetricsRegistry()
        exp = Explainer()
        out = join_search(
            ds, "tracks", "vessels", "vessel", max_values=100,
            explain=exp, metrics=m,
        )
        assert m.counter_value("geomesa.join.in_cap_fallback") == 1
        assert "in_cap_fallback" in exp.render()
        assert len(out) == n
        # below the cap but high selectivity: the sampled gate also
        # routes to the host mask, visibly
        m2 = MetricsRegistry()
        out2 = join_search(
            ds, "tracks", "vessels", "vessel", max_values=n + 10, metrics=m2,
        )
        assert m2.counter_value("geomesa.join.in_skipped_selectivity") == 1
        assert len(out2) == n


class TestValidators:
    def _sft(self):
        return FeatureType.from_spec(
            "obs", "name:String,dtg:Date,*geom:Point:srid=4326"
        )

    def test_z_bounds_and_reasons(self):
        from geomesa_tpu.io.converters import Converter, FieldSpec

        sft = self._sft()
        conv = Converter(
            sft=sft,
            fields=[
                FieldSpec("name", "$1"),
                FieldSpec("dtg", "datetime($2)"),
                FieldSpec("geom", "point($3, $4)"),
            ],
            validators="index",
        )
        data = (
            "a,2024-01-01T00:00:00Z,10,20\n"      # ok
            "b,2024-01-01T00:00:00Z,200,20\n"     # lon out of bounds
            "c,2024-01-01T00:00:00Z,10,-95\n"     # lat out of bounds
            "d,not-a-date,10,20\n"                # parse error
            "e,2024-01-01T00:00:00Z,11,21\n"      # ok
        )
        fc = conv.convert(data)
        assert len(fc) == 2
        assert conv.errors == 3
        assert conv.error_reasons.get("parse") == 1
        zb = [k for k in conv.error_reasons if k.startswith("z-bounds")]
        assert sum(conv.error_reasons[k] for k in zb) == 2

    def test_raise_mode(self):
        from geomesa_tpu.io.converters import Converter, FieldSpec

        conv = Converter(
            sft=self._sft(),
            fields=[
                FieldSpec("name", "$1"),
                FieldSpec("dtg", "datetime($2)"),
                FieldSpec("geom", "point($3, $4)"),
            ],
            validators="z-bounds",
            drop_errors=False,
        )
        with pytest.raises(ValueError, match="z-bounds"):
            conv.convert("a,2024-01-01T00:00:00Z,500,20\n")

    def test_custom_validator_objects_in_process(self, tmp_path):
        """Custom Validator OBJECTS (unpicklable closures) work through
        the documented workers<=1 escape hatch, and a pool attempt fails
        with the clear error instead of a raw pickle traceback."""
        import pickle

        from geomesa_tpu.ingest.splits import ConverterConfig
        from geomesa_tpu.io.converters import Converter, FieldSpec
        from geomesa_tpu.io.ingest import ingest_files
        from geomesa_tpu.io.validators import Validator

        sft = self._sft()
        odd = Validator("odd-lon", lambda row: (
            None if int(row["geom"].x) % 2 == 1 else "even longitude"
        ))
        conv = Converter(
            sft=sft,
            fields=[
                FieldSpec("name", "$1"),
                FieldSpec("dtg", "datetime($2)"),
                FieldSpec("geom", "point($3, $4)"),
            ],
            validators=[odd],
        )
        path = tmp_path / "obs.csv"
        path.write_text(
            "a,2024-01-01T00:00:00Z,11,20\n"
            "b,2024-01-01T00:00:00Z,10,20\n"
        )
        ds = DataStore()
        ds.create_schema(sft)
        res = ingest_files(ds, conv, [str(path)], workers=1)
        assert res.written == 1 and res.errors == 1
        assert any(k.startswith("odd-lon") for k in res.error_reasons)
        with pytest.raises(ValueError, match="not picklable"):
            pickle.dumps(ConverterConfig.of(conv))

    def test_ingest_result_reasons(self, tmp_path):
        from geomesa_tpu.io.converters import Converter, FieldSpec
        from geomesa_tpu.io.ingest import ingest_files

        sft = self._sft()
        conv = Converter(
            sft=sft,
            fields=[
                FieldSpec("name", "$1"),
                FieldSpec("dtg", "datetime($2)"),
                FieldSpec("geom", "point($3, $4)"),
            ],
            validators="index",
        )
        path = tmp_path / "obs.csv"
        path.write_text(
            "a,2024-01-01T00:00:00Z,10,20\n"
            "b,2024-01-01T00:00:00Z,400,20\n"
            "c,2024-01-01T00:00:00Z,12,22\n"
        )
        ds = DataStore()
        ds.create_schema(sft)
        res = ingest_files(ds, conv, [str(path)], workers=1)
        assert res.written == 2
        assert res.errors == 1
        assert sum(res.error_reasons.values()) == 1
        assert any(k.startswith("z-bounds") for k in res.error_reasons)
