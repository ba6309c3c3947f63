"""Fused multi-query scan (round 5): scan_submit_many == per-query scans.

One kernel dispatch covers many queries' candidate blocks — slot i scans
block bids[i] under query qids[i]'s packed params (block_kernels.
block_scan_multi). The contract under test: for EVERY config mix, the
fused path returns exactly what per-query IndexTable.scan would."""

import numpy as np
import pytest

from geomesa_tpu import DataStore, FeatureCollection, FeatureType
from geomesa_tpu import geometry as geo
from geomesa_tpu.filter.predicates import BBox, During, Intersects
from geomesa_tpu.scan import block_kernels as bk


def bucket_q(q: int) -> int:
    """Static Q bucket: power of two >= q, floor 8. TEST-ONLY — production
    fused dispatches pad their param stacks to the canonical FUSED_CHUNK_Q
    (storage.table._submit_fused_chunk); this helper sizes hand-built
    stacks in kernel-level tests. Pad query rows are all-zero params no
    slot references (pad slots carry qid 0 and are ignored at decode)."""
    m = 8
    while m < q:
        m *= 2
    return m


def make_store(n=60_000, seed=11, index="z3", mesh=None):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-60, 60, n)
    y = rng.uniform(-45, 45, n)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    t = t0 + rng.integers(0, 28 * 86400_000, n)
    sft = FeatureType.from_spec("pts", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = index
    ds = DataStore(mesh=mesh)
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(sft, np.arange(n), {"dtg": t, "geom": (x, y)})
    ds.write("pts", fc, check_ids=False)
    return ds, t0


def assert_batched_equals_sequential(ds, type_name, queries):
    batched = ds.query_many(type_name, queries)
    for q, got in zip(queries, batched):
        want = ds.query(type_name, q)
        assert np.array_equal(
            np.sort(np.asarray(want.ids)), np.sort(np.asarray(got.ids))
        ), q
    assert sum(len(b) for b in batched) > 0


def rand_bbox(rng, span=25.0):
    x0 = rng.uniform(-60, 35)
    y0 = rng.uniform(-45, 20)
    return BBox("geom", x0, y0, x0 + rng.uniform(0.5, span), y0 + rng.uniform(0.5, span))


def assert_matches(table, cfgs):
    got = [f() for f in table.scan_submit_many(list(cfgs))]
    assert len(got) == len(cfgs)
    for cfg, (rows, certain) in zip(cfgs, got):
        er, ec = table.scan(cfg)
        assert np.array_equal(rows, er)
        assert np.array_equal(certain, ec)


class TestFusedScan:
    def test_z3_boxes_and_windows(self):
        ds, t0 = make_store()
        idx = next(i for i in ds.indexes("pts") if i.name == "z3")
        table = ds.table("pts", "z3")
        rng = np.random.default_rng(5)
        cfgs = []
        for _ in range(23):
            f = rand_bbox(rng)
            if rng.random() < 0.7:
                lo = t0 + rng.integers(0, 20 * 86400_000)
                f = f & During("dtg", lo, lo + rng.integers(3600_000, 7 * 86400_000))
            else:
                # whole-period window (z3 needs a time constraint at all)
                f = f & During("dtg", t0 - 86400_000, t0 + 40 * 86400_000)
            cfgs.append(idx.scan_config(f))
        assert_matches(table, cfgs)

    def test_z2_boxes(self):
        ds, _ = make_store(index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(6)
        assert_matches(ds.table("pts", "z2"), [idx.scan_config(rand_bbox(rng)) for _ in range(17)])

    def test_mixed_eligibility(self):
        """Disjoint, empty-candidate, PIP-edge polygon and plain box
        configs in one batch: each routes correctly and results stay in
        input order."""
        ds, _ = make_store(index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        table = ds.table("pts", "z2")
        rng = np.random.default_rng(7)
        tri = geo.from_wkt("POLYGON ((0 0, 24 4, 6 21, 0 0))")
        cfgs = [
            idx.scan_config(rand_bbox(rng)),
            idx.scan_config(BBox("geom", 120.0, 60.0, 130.0, 70.0)),  # empty region: no blocks
            idx.scan_config(Intersects("geom", tri)),  # PIP tier: per-query path
            idx.scan_config(rand_bbox(rng)),
            idx.scan_config(rand_bbox(rng)),
        ]
        assert_matches(table, [c for c in cfgs if c is not None])

    def test_single_member_group_falls_back(self):
        ds, _ = make_store(n=20_000, index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(8)
        assert_matches(ds.table("pts", "z2"), [idx.scan_config(rand_bbox(rng))])

    def test_delta_tier(self):
        """Un-compacted writes wrap the table in TieredTable: fused main
        scan + per-query host delta hits."""
        ds, t0 = make_store(n=30_000, index="z3")
        rng = np.random.default_rng(9)
        sft = ds.get_schema("pts")
        m = 4_000
        t = t0 + rng.integers(0, 28 * 86400_000, m)
        fc = FeatureCollection.from_columns(
            sft, 30_000 + np.arange(m),
            {"dtg": t, "geom": (rng.uniform(-60, 60, m), rng.uniform(-45, 45, m))},
        )
        ds.write("pts", fc, check_ids=False)
        idx = next(i for i in ds.indexes("pts") if i.name == "z3")
        table = ds.table("pts", "z3")
        from geomesa_tpu.storage.delta import TieredTable

        assert isinstance(table, TieredTable)
        cfgs = []
        for _ in range(9):
            lo = int(t0 + rng.integers(0, 20 * 86400_000))
            cfgs.append(idx.scan_config(
                rand_bbox(rng) & During("dtg", lo, lo + 3 * 86400_000)
            ))
        assert_matches(table, cfgs)

    def test_packed_time_store(self):
        from geomesa_tpu.index.z3 import PACKED_KEY

        t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
        ds2 = DataStore()
        sft2 = FeatureType.from_spec("pts", "dtg:Date,*geom:Point:srid=4326")
        sft2.user_data["geomesa.indices.enabled"] = "z3"
        sft2.user_data[PACKED_KEY] = "true"
        ds2.create_schema(sft2)
        rng = np.random.default_rng(10)
        n = 30_000
        t = t0 + rng.integers(0, 28 * 86400_000, n)
        ds2.write("pts", FeatureCollection.from_columns(
            sft2, np.arange(n),
            {"dtg": t, "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n))},
        ), check_ids=False)
        idx = next(i for i in ds2.indexes("pts") if i.name == "z3")
        cfgs = []
        for _ in range(11):
            f = rand_bbox(rng)
            lo = int(t0 + rng.integers(0, 20 * 86400_000))
            cfgs.append(idx.scan_config(f & During("dtg", lo, lo + 2 * 86400_000)))
        assert_matches(ds2.table("pts", "z3"), cfgs)

    def test_xz2_extent_store(self):
        """Fused scans on an EXTENT table: the inner plane is skipped
        (bbox-intersects can never certify), so the multi kernel's
        single-output variant must match per-query scans — including
        polygon INTERSECTS configs (extent kernels ignore poly edges in
        both paths)."""
        rng = np.random.default_rng(41)
        n = 20_000
        x0 = rng.uniform(-60, 59, n)
        y0 = rng.uniform(-45, 44, n)
        polys = geo.PackedGeometryColumn.from_boxes(
            x0, y0, x0 + rng.uniform(0.01, 0.8, n), y0 + rng.uniform(0.01, 0.6, n)
        )
        sft = FeatureType.from_spec("bld", "*geom:Polygon:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "xz2"
        ds = DataStore()
        ds.create_schema(sft)
        ds.write("bld", FeatureCollection.from_columns(
            sft, np.arange(n), {"geom": polys}), check_ids=False)
        idx = next(i for i in ds.indexes("bld") if i.name == "xz2")
        tri = geo.from_wkt("POLYGON ((-20 -15, 25 -10, 0 30, -20 -15))")
        cfgs = [idx.scan_config(rand_bbox(rng)) for _ in range(9)]
        cfgs.append(idx.scan_config(Intersects("geom", tri)))
        cfgs.extend(idx.scan_config(rand_bbox(rng)) for _ in range(4))
        assert all(c is not None for c in cfgs)  # esp. the INTERSECTS case
        assert_matches(ds.table("bld", "xz2"), cfgs)

    def test_chunking_cap(self, monkeypatch):
        """With a tiny chunk shape the batch must split into many fused
        chunks (and broad members dispatch alone) — results unchanged."""
        from geomesa_tpu.storage import table as tbl

        monkeypatch.setattr(tbl, "FUSED_CHUNK_SLOTS", 8)
        monkeypatch.setattr(tbl, "FUSED_CHUNK_Q", 4)
        ds, _ = make_store(n=40_000, index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(31)
        cfgs = [idx.scan_config(rand_bbox(rng)) for _ in range(13)]
        # a broad query: nearly the whole extent -> blocks > cap/2
        cfgs.append(idx.scan_config(BBox("geom", -59.0, -44.0, 59.0, 44.0)))
        assert_matches(ds.table("pts", "z2"), cfgs)

    def test_host_adapter_passthrough(self):
        from geomesa_tpu.storage.adapter import HostAdapter

        ds, _ = make_store(n=20_000, index="z2")
        hs = DataStore(adapter=HostAdapter())
        sft = FeatureType.from_spec("pts", "dtg:Date,*geom:Point:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "z2"
        hs.create_schema(sft)
        hs.write("pts", ds.features("pts"), check_ids=False)
        idx = next(i for i in hs.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(12)
        assert_matches(hs.table("pts", "z2"), [idx.scan_config(rand_bbox(rng)) for _ in range(7)])


def _poly(kind, cx, cy, r, rng=None, holes=False):
    """Concave / convex / holed polygons (the PIP fuzz shapes of
    test_pip_kernel, round 6: now exercised through the FUSED path)."""
    if kind == "triangle":
        pts = [(cx - r, cy - r), (cx + r, cy - r), (cx, cy + r)]
    elif kind == "hex":
        a = np.linspace(0, 2 * np.pi, 7)[:-1] + (rng.uniform(0, 1) if rng else 0.3)
        pts = [(cx + r * np.cos(t), cy + 0.7 * r * np.sin(t)) for t in a]
    elif kind == "lshape":
        pts = [
            (cx - r, cy - r), (cx + r, cy - r), (cx + r, cy),
            (cx, cy), (cx, cy + r), (cx - r, cy + r),
        ]
    else:  # star-ish concave
        a = np.linspace(0, 2 * np.pi, 11)[:-1]
        rad = np.where(np.arange(10) % 2 == 0, r, 0.4 * r)
        pts = [(cx + rr * np.cos(t), cy + rr * np.sin(t)) for t, rr in zip(a, rad)]
    hh = (
        [[(cx - 0.3 * r, cy - 0.3 * r), (cx + 0.3 * r, cy - 0.3 * r),
          (cx, cy + 0.2 * r)]]
        if holes else None
    )
    return geo.Polygon(pts, holes=hh)


class TestFusedPip:
    """Round 6: polygon-INTERSECTS (device PIP) members fuse — the chunk
    carries a [Q, E, 128] edge stack and a per-slot selector. Contract:
    fused == per-query scan, bit-identical, for every polygon shape mix,
    and polygon batches actually take the fused dispatch."""

    def _spy(self, monkeypatch):
        calls = {"fused": 0, "edged": 0}
        orig = bk.block_scan_multi

        def spy(*a, **kw):
            calls["fused"] += 1
            if kw.get("n_edges", 0):
                calls["edged"] += 1
            return orig(*a, **kw)

        monkeypatch.setattr(bk, "block_scan_multi", spy)
        return calls

    @pytest.mark.parametrize("seed", range(8))
    def test_z2_polygon_batches(self, seed, monkeypatch):
        ds, _ = make_store(n=40_000, seed=60 + seed, index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        table = ds.table("pts", "z2")
        calls = self._spy(monkeypatch)
        rng = np.random.default_rng(6000 + seed)
        kinds = ["triangle", "hex", "lshape", "star"]
        cfgs = []
        for k in range(12):
            cx, cy = rng.uniform(-40, 40), rng.uniform(-30, 30)
            if k % 3 == 2:  # mixed chunk: boxes ride zero-edge slots
                cfgs.append(idx.scan_config(rand_bbox(rng, span=10)))
            else:
                p = _poly(kinds[(seed + k) % 4], cx, cy, rng.uniform(3, 8),
                          rng, holes=(k % 4 == 1))
                cfgs.append(idx.scan_config(Intersects("geom", p)))
        assert any(c.poly is not None for c in cfgs)
        assert_matches(table, cfgs)
        assert calls["edged"] >= 1, "polygon batch never took the fused PIP path"

    def test_z3_polygon_time_batches(self, monkeypatch):
        ds, t0 = make_store(n=40_000, seed=71, index="z3")
        idx = next(i for i in ds.indexes("pts") if i.name == "z3")
        calls = self._spy(monkeypatch)
        rng = np.random.default_rng(6100)
        cfgs = []
        for k in range(10):
            cx, cy = rng.uniform(-40, 40), rng.uniform(-30, 30)
            p = _poly(["star", "lshape"][k % 2], cx, cy, rng.uniform(3, 7), rng)
            lo = t0 + rng.integers(0, 20 * 86400_000)
            f = Intersects("geom", p) & During("dtg", lo, lo + 5 * 86400_000)
            cfgs.append(idx.scan_config(f))
        assert_matches(ds.table("pts", "z3"), cfgs)
        assert calls["edged"] >= 1

    def test_e_bucket_ladder(self):
        assert bk.fused_e_bucket(0) == 0
        assert bk.fused_e_bucket(1) == 16
        assert bk.fused_e_bucket(16) == 16
        assert bk.fused_e_bucket(17) == 64
        assert bk.fused_e_bucket(200) == 256
        # every pack_edges output fits a fused bucket
        assert bk.FUSED_E_BUCKETS[-1] == bk.E_BUCKETS[-1]

    def test_mixed_edge_sizes_and_bucket_grouping(self, monkeypatch):
        """Polygons with different edge counts in the SAME fused bucket
        zero-pad into one chunk; a bigger-bucket ring and the box members
        group separately (the E bucket is part of the variant key, so box
        slots never pay edge work) — results exact throughout. Raster
        approximations are disabled: this test pins the PIP edge-ladder
        grouping specifically (the raster tier has its own suite,
        test_raster_join.py)."""
        from geomesa_tpu.conf import RASTER_ENABLED
        from geomesa_tpu.filter import raster as fr

        monkeypatch.setattr(RASTER_ENABLED, "_override", False)
        fr.clear_cache()
        ds, _ = make_store(n=30_000, seed=75, index="z2")
        idx = next(i for i in ds.indexes("pts") if i.name == "z2")
        e_seen = []
        orig = bk.block_scan_multi

        def spy(*a, **kw):
            e_seen.append(kw.get("n_edges", 0))
            return orig(*a, **kw)

        monkeypatch.setattr(bk, "block_scan_multi", spy)
        rng = np.random.default_rng(6200)
        a = np.linspace(0, 2 * np.pi, 41)[:-1]
        ring = geo.Polygon([(10 * np.cos(t), 8 * np.sin(t)) for t in a])
        # 3-, 6- and 10-edge polygons all bucket to FUSED_E_BUCKETS[0]
        small = [
            _poly(k, rng.uniform(-30, 30), rng.uniform(-20, 20), 6.0, rng)
            for k in ("triangle", "lshape", "star", "triangle", "star", "lshape")
        ]
        cfgs = (
            [idx.scan_config(Intersects("geom", p)) for p in small]
            + [idx.scan_config(Intersects("geom", ring))]
            + [idx.scan_config(rand_bbox(rng, span=8)) for _ in range(6)]
        )
        assert bk.n_edges_of(cfgs[len(small)].poly) > bk.FUSED_E_BUCKETS[0]
        assert_matches(ds.table("pts", "z2"), cfgs)
        # the small polygons fused at the smallest bucket; no box chunk
        # ever dispatched with edge work
        assert bk.FUSED_E_BUCKETS[0] in e_seen
        assert all(e in (0,) + bk.FUSED_E_BUCKETS for e in e_seen)


class TestFusedExtentXZ3:
    """XZ3 (extent + time) batches fuse on the wide-only plane layout
    (skip_inner_plane): fused == per-query, including polygon-INTERSECTS
    configs, whose edges extent kernels ignore in both paths."""

    def test_xz3_box_time_batch(self, monkeypatch):
        rng = np.random.default_rng(81)
        n = 15_000
        t0 = np.datetime64("2024-03-01T00:00:00", "ms").astype(np.int64)
        sft = FeatureType.from_spec("tx", "dtg:Date,*geom:Polygon:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "xz3"
        ds = DataStore()
        ds.create_schema(sft)
        x0 = rng.uniform(-60, 58, n)
        y0 = rng.uniform(-45, 43, n)
        col = geo.PackedGeometryColumn.from_boxes(
            x0, y0, x0 + rng.uniform(0.01, 1.0, n), y0 + rng.uniform(0.01, 0.8, n)
        )
        t = t0 + rng.integers(0, 30 * 86400_000, n)
        ds.write("tx", FeatureCollection.from_columns(
            sft, np.arange(n), {"dtg": t, "geom": col}), check_ids=False)
        idx = next(i for i in ds.indexes("tx") if i.name == "xz3")
        calls = {"fused": 0}
        orig = bk.block_scan_multi

        def spy(*a, **kw):
            calls["fused"] += 1
            assert kw.get("n_edges", 0) == 0  # extent chunks ride E = 0
            return orig(*a, **kw)

        monkeypatch.setattr(bk, "block_scan_multi", spy)
        tri = geo.from_wkt("POLYGON ((-20 -15, 25 -10, 0 30, -20 -15))")
        cfgs = []
        for k in range(11):
            f = rand_bbox(rng, span=15) if k % 4 else Intersects("geom", tri)
            lo = t0 + rng.integers(0, 20 * 86400_000)
            cfgs.append(idx.scan_config(
                f & During("dtg", int(lo), int(lo) + 6 * 86400_000)
            ))
        assert all(c is not None for c in cfgs)
        assert_matches(ds.table("tx", "xz3"), cfgs)
        assert calls["fused"] >= 1, "xz3 batch never fused"


class TestPlannerSubmitMany:
    def test_mixed_types_and_indexes(self):
        """submit_many groups per (type, index) and falls back for
        non-simple plans; results equal sequential execution."""
        ds, t0 = make_store(n=25_000, index="z3,z2")
        sft2 = FeatureType.from_spec("aux", "dtg:Date,*geom:Point:srid=4326")
        sft2.user_data["geomesa.indices.enabled"] = "z2"
        ds.create_schema(sft2)
        rng = np.random.default_rng(21)
        m = 8_000
        ds.write("aux", FeatureCollection.from_columns(
            sft2, np.arange(m),
            {"dtg": t0 + rng.integers(0, 86400_000, m),
             "geom": (rng.uniform(-60, 60, m), rng.uniform(-45, 45, m))},
        ), check_ids=False)
        queries = [
            ("pts", "bbox(geom, -20, -20, 10, 10)"),
            ("aux", "bbox(geom, -10, -30, 30, 0)"),
            ("pts", "bbox(geom, 0, 0, 25, 25) AND dtg DURING 2024-01-02T00:00:00Z/2024-01-06T00:00:00Z"),
            ("aux", "bbox(geom, -50, -40, -20, -10)"),
            ("pts", "IN ('3', '99')"),
            ("pts", "bbox(geom, 5, -40, 45, 5)"),
        ]
        plans = [ds.planner.plan(t, q) for t, q in queries]
        batched = [f() for f in ds.planner.submit_many(plans)]
        for (t, q), got in zip(queries, batched):
            want = ds.query(t, q)
            assert np.array_equal(
                np.sort(np.asarray(want.ids)), np.sort(np.asarray(got.ids))
            )
        assert sum(len(b) for b in batched) > 0


class TestMeshFused:
    def test_query_many_on_mesh_store(self):
        """A mesh-sharded store's batches dispatch through the shard_map
        FUSED kernel (round 6: one mesh-wide dispatch per chunk, one
        batched plane pull) — batched results equal sequential ones."""
        from geomesa_tpu.parallel import dtable, make_mesh

        ds, _ = make_store(n=30_000, seed=51, index="z2", mesh=make_mesh(8))
        calls = {"n": 0}
        orig = dtable._dist_scan_multi

        def spy(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        dtable._dist_scan_multi = spy
        try:
            rng = np.random.default_rng(52)
            qs = []
            for _ in range(12):
                qx, qy = rng.uniform(-55, 30), rng.uniform(-40, 15)
                w, h = rng.uniform(1, 15), rng.uniform(1, 10)
                qs.append(f"bbox(geom, {qx}, {qy}, {qx + w}, {qy + h})")
            assert_batched_equals_sequential(ds, "pts", qs)
        finally:
            dtable._dist_scan_multi = orig
        assert calls["n"] >= 1, "mesh batch never took the fused dispatch"

    def test_mesh_fused_matches_single_device(self):
        """mesh4 fused == single-device fused == sequential, on a batch
        mixing boxes and polygon-PIP members (the differential the round-6
        acceptance pins)."""
        from geomesa_tpu.parallel import make_mesh

        ds_m, _ = make_store(n=25_000, seed=55, index="z2", mesh=make_mesh(4))
        ds_s, _ = make_store(n=25_000, seed=55, index="z2")
        idx_m = next(i for i in ds_m.indexes("pts") if i.name == "z2")
        idx_s = next(i for i in ds_s.indexes("pts") if i.name == "z2")
        rng = np.random.default_rng(56)
        filters = []
        for k in range(10):
            cx, cy = rng.uniform(-40, 40), rng.uniform(-30, 30)
            if k % 3 == 0:
                filters.append(Intersects("geom", _poly(
                    ["star", "lshape", "hex"][k % 3], cx, cy, 6.0, rng
                )))
            else:
                filters.append(rand_bbox(rng, span=10))
        cfg_m = [idx_m.scan_config(f) for f in filters]
        cfg_s = [idx_s.scan_config(f) for f in filters]
        got_m = [f() for f in ds_m.table("pts", "z2").scan_submit_many(cfg_m)]
        got_s = [f() for f in ds_s.table("pts", "z2").scan_submit_many(cfg_s)]
        for cm, cs, (rm, km), (rs, ks) in zip(cfg_m, cfg_s, got_m, got_s):
            er, ec = ds_m.table("pts", "z2").scan(cm)
            assert np.array_equal(rm, er) and np.array_equal(km, ec)
            # same seed -> same data -> identical ordinal sets and
            # certainty across the two layouts
            assert np.array_equal(rm, rs)
            assert np.array_equal(km, ks)

    def test_mesh_zero_recompiles_warm_fused_batch(self):
        """After ONE fused batch (the warmup dispatch for its chunk
        variants), re-running the same mixed batch triggers NO new XLA
        compiles — the round-6 mesh-fusion acceptance bar (the compile
        key is the static (slots, Q, columns, flags, E) tuple)."""
        import logging

        import jax

        from geomesa_tpu.parallel import make_mesh

        ds, _ = make_store(n=30_000, seed=57, index="z2", mesh=make_mesh(4))
        rng = np.random.default_rng(58)
        qs = []
        for k in range(10):
            if k % 3 == 0:
                cx, cy = rng.uniform(-40, 40), rng.uniform(-30, 30)
                p = _poly("star", cx, cy, 6.0, rng)
                qs.append(f"INTERSECTS(geom, {p.wkt})")
            else:
                qx, qy = rng.uniform(-55, 30), rng.uniform(-40, 15)
                qs.append(f"bbox(geom, {qx}, {qy}, {qx + 9}, {qy + 7})")
        ds.query_many("pts", qs)  # warm: compiles the fused chunk variants
        jax.config.update("jax_log_compiles", True)
        records: list = []
        handler = logging.Handler()
        handler.emit = lambda r: records.append(r.getMessage())
        loggers = [logging.getLogger(n) for n in (
            "jax._src.dispatch", "jax._src.interpreters.pxla", "jax._src.compiler"
        )]
        prior = [lg.level for lg in loggers]
        for lg in loggers:
            lg.addHandler(handler)
            lg.setLevel(logging.DEBUG)
        try:
            ds.query_many("pts", qs)
        finally:
            jax.config.update("jax_log_compiles", False)
            for lg, lvl in zip(loggers, prior):
                lg.removeHandler(handler)
                lg.setLevel(lvl)
        compiles = [m for m in records if "Compiling" in m]
        assert compiles == [], f"unexpected recompiles: {compiles}"

    def test_indexed_join_on_mesh_store(self):
        """spatial_join_indexed against a mesh-sharded point store (the
        shard_map scan fallback) must produce exactly the host grid
        join's pairs."""
        from geomesa_tpu.parallel import make_mesh
        from geomesa_tpu.sql import spatial_join, spatial_join_indexed

        ds, _ = make_store(n=25_000, seed=53, index="z2", mesh=make_mesh(8))
        rng = np.random.default_rng(54)
        npoly = 24
        px0 = rng.uniform(-55, 35, npoly)
        py0 = rng.uniform(-40, 25, npoly)
        pw = rng.uniform(1, 14, npoly)
        ph = rng.uniform(1, 9, npoly)
        polys = geo.PackedGeometryColumn.from_boxes(px0, py0, px0 + pw, py0 + ph)
        gsft = FeatureType.from_spec("adm", "*geom:Polygon:srid=4326")
        pfc = FeatureCollection.from_columns(gsft, np.arange(npoly), {"geom": polys})
        li, ri = spatial_join_indexed(ds, "pts", pfc, "contains")
        hl, hr = spatial_join(pfc, ds.features("pts"), "contains")
        assert set(zip(li.tolist(), ri.tolist())) == set(zip(hl.tolist(), hr.tolist()))
        assert len(li) > 0


class TestMultiKernelParity:
    """Pallas-interpret vs XLA parity for the fused kernel itself."""

    SUB = 256

    def _cols(self, nb=4, seed=13):
        rng = np.random.default_rng(seed)
        import jax.numpy as jnp

        x = rng.uniform(-50, 50, (nb, self.SUB, 128)).astype(np.float32)
        y = rng.uniform(-50, 50, (nb, self.SUB, 128)).astype(np.float32)
        return tuple(jnp.asarray(a) for a in (x, y))

    def test_interpret_parity_boxes(self):
        cols3 = self._cols()
        q = 3
        boxes = np.zeros((bucket_q(q), 8, bk.LANES), np.float32)
        wins = np.zeros((bucket_q(q), 8, bk.LANES), np.int32)
        rng = np.random.default_rng(14)
        for k in range(q):
            x0, y0 = rng.uniform(-40, 20, 2)
            wide = np.array([[x0, y0, x0 + 25, y0 + 25]])
            inner = wide + np.array([[1.0, 1.0, -1.0, -1.0]])
            boxes[k] = bk.pack_boxes(wide, inner)
            wins[k] = bk.pack_windows(None, None)
        bids = np.array([0, 1, 2, 3, 0, 2, 1, 3], np.int32)
        qids = np.array([0, 0, 0, 1, 1, 2, 2, 2], np.int32)
        kw = dict(col_names=("x", "y"), has_boxes=True, has_windows=False, extent=False)
        w_ref, i_ref = bk._xla_block_scan_multi(cols3, bids, qids, boxes, wins, **kw)
        w_got, i_got = bk._pallas_block_scan_multi(
            cols3, bids, qids, boxes, wins, interpret=True, **kw
        )
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_got))
        assert np.array_equal(np.asarray(i_ref), np.asarray(i_got))

    def test_interpret_parity_extent_skip_inner(self):
        """Extent mode: the fused kernel emits ONE plane (skip_inner);
        Pallas-interpret must match the vmapped XLA fallback."""
        import jax.numpy as jnp

        rng = np.random.default_rng(16)
        nb = 3
        cols3 = tuple(
            jnp.asarray(rng.uniform(-50, 50, (nb, self.SUB, 128)).astype(np.float32))
            for _ in range(4)
        )
        q = 2
        boxes = np.zeros((bucket_q(q), 8, bk.LANES), np.float32)
        wins = np.zeros((bucket_q(q), 8, bk.LANES), np.int32)
        for k in range(q):
            xx, yy = rng.uniform(-40, 10, 2)
            boxes[k] = bk.pack_boxes(np.array([[xx, yy, xx + 30, yy + 25]]), None)
            wins[k] = bk.pack_windows(None, None)
        bids = np.array([0, 1, 2, 2, 1], np.int32)
        qids = np.array([0, 0, 1, 0, 1], np.int32)
        kw = dict(
            col_names=("gxmax", "gxmin", "gymax", "gymin"),
            has_boxes=True, has_windows=False, extent=True,
        )
        w_ref, i_ref = bk._xla_block_scan_multi(cols3, bids, qids, boxes, wins, **kw)
        w_got, i_got = bk._pallas_block_scan_multi(
            cols3, bids, qids, boxes, wins, interpret=True, **kw
        )
        assert i_ref is None and i_got is None
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_got))

    def test_interpret_parity_pip_fused(self):
        """PIP-fused multi kernel: Pallas-interpret == XLA, with a mixed
        chunk (polygon slots + box slots selected by spip)."""
        cols3 = self._cols(seed=17)
        q = 3
        E = 16
        boxes = np.zeros((bucket_q(q), 8, bk.LANES), np.float32)
        wins = np.zeros((bucket_q(q), 8, bk.LANES), np.int32)
        edges = np.zeros((bucket_q(q), E, bk.LANES), np.float32)
        rng = np.random.default_rng(18)
        tri = geo.from_wkt("POLYGON ((-30 -20, 20 -25, 5 30, -30 -20))")
        packed = bk.pack_edges(tri)
        assert packed is not None and packed.shape[0] == E
        for k in range(q):
            x0, y0 = rng.uniform(-40, 10, 2)
            boxes[k] = bk.pack_boxes(np.array([[x0, y0, x0 + 25, y0 + 20]]), None)
            wins[k] = bk.pack_windows(None, None)
        edges[1] = packed  # query 1 is the polygon; 0 and 2 stay boxes
        bids = np.array([0, 1, 2, 3, 0, 2, 1, 3], np.int32)
        qids = np.array([0, 0, 1, 1, 1, 2, 2, 2], np.int32)
        spip = (qids == 1).astype(np.int32)
        kw = dict(
            col_names=("x", "y"), has_boxes=True, has_windows=False,
            extent=False, n_edges=E,
        )
        w_ref, i_ref = bk._xla_block_scan_multi(
            cols3, bids, qids, boxes, wins, edges, spip, **kw
        )
        w_got, i_got = bk._pallas_block_scan_multi(
            cols3, bids, qids, boxes, wins, edges, spip, interpret=True, **kw
        )
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_got))
        assert np.array_equal(np.asarray(i_ref), np.asarray(i_got))
        # and the polygon slots equal the single-query PIP kernel
        sl = qids == 1
        w_s, i_s = bk._xla_block_scan(
            cols3, bids[sl], boxes[1], wins[1], edges[1],
            col_names=("x", "y"), has_boxes=True, has_windows=False,
            extent=False, n_edges=E,
        )
        assert np.array_equal(np.asarray(w_ref)[sl], np.asarray(w_s))
        assert np.array_equal(np.asarray(i_ref)[sl], np.asarray(i_s))

    def test_slotwise_equals_single_kernel(self):
        """Each fused slot must equal the single-query kernel run with that
        slot's params — the fused grid is just a re-indexed batch."""
        cols3 = self._cols()
        rng = np.random.default_rng(15)
        x0, y0 = -10.0, -5.0
        b0 = bk.pack_boxes(np.array([[x0, y0, x0 + 30, y0 + 20]]), None)
        b1 = bk.pack_boxes(np.array([[-40.0, -40.0, 0.0, 0.0]]), None)
        wins = bk.pack_windows(None, None)
        boxes_m = np.zeros((8, 8, bk.LANES), np.float32)
        wins_m = np.zeros((8, 8, bk.LANES), np.int32)
        boxes_m[0], boxes_m[1] = b0, b1
        wins_m[0] = wins_m[1] = wins
        bids = np.array([0, 1, 2, 3, 1, 2], np.int32)
        qids = np.array([0, 0, 0, 1, 1, 1], np.int32)
        kw = dict(col_names=("x", "y"), has_boxes=True, has_windows=False, extent=False)
        w_m, i_m = bk._xla_block_scan_multi(cols3, bids, qids, boxes_m, wins_m, **kw)
        for q, params in ((0, b0), (1, b1)):
            sl = qids == q
            w_s, i_s = bk._xla_block_scan(
                cols3, bids[sl], params, wins, **kw
            )
            assert np.array_equal(np.asarray(w_m)[sl], np.asarray(w_s))
            assert np.array_equal(np.asarray(i_m)[sl], np.asarray(i_s))
