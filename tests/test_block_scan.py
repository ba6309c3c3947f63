"""Round-3 scan path: block kernels, contained ranges, boundary exactness.

Covers VERDICT r2 items 1-2: the one-call bitmask scan, automatic
refinement skipping (certain rows), and contained-range propagation."""

import numpy as np
import pytest

from geomesa_tpu import DataStore, FeatureCollection, FeatureType
from geomesa_tpu.filter import ecql
from geomesa_tpu.scan import block_kernels as bk

N = 40_000


def make_store(n=N, seed=3):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-20, 20, n)
    y = rng.uniform(-20, 20, n)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    t = t0 + rng.integers(0, 28 * 86400_000, n)
    sft = FeatureType.from_spec("pts", "dtg:Date,*geom:Point:srid=4326")
    ds = DataStore()
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(sft, np.arange(n), {"dtg": t, "geom": (x, y)})
    ds.write("pts", fc, check_ids=False)
    return ds, (x, y, t, t0)


def brute(data, x0, y0, x1, y1, tlo, thi):
    x, y, t, _ = data
    return np.flatnonzero(
        (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1) & (t >= tlo) & (t < thi)
    )


class TestBlockScanExactness:
    def setup_method(self):
        self.ds, self.data = make_store()

    def q(self, x0, y0, x1, y1, d0, d1):
        return (
            f"bbox(geom, {x0}, {y0}, {x1}, {y1}) AND "
            f"dtg DURING 2024-01-{d0:02d}T00:00:00Z/2024-01-{d1:02d}T00:00:00Z"
        )

    def test_matches_brute_force(self):
        t0 = self.data[3]
        for (x0, y0, x1, y1, d0, d1) in [
            (-5, -5, 5, 5, 3, 10),
            (-19.7, -3.3, 8.1, 0.2, 1, 28),
            (0.001, 0.001, 0.002, 0.002, 5, 6),
        ]:
            out = self.ds.query("pts", self.q(x0, y0, x1, y1, d0, d1))
            tlo = t0 + (d0 - 1) * 86400_000
            thi = t0 + (d1 - 1) * 86400_000
            expect = brute(self.data, x0, y0, x1, y1, tlo, thi)
            got = np.sort(np.asarray(out.ids, dtype=np.int64))
            assert np.array_equal(got, expect)

    def test_unaligned_ms_endpoints_exact(self):
        # endpoints not aligned to the week-bin second granularity: the
        # boundary-second rows must be refined exactly at ms precision
        t0 = self.data[3]
        tlo = int(t0 + 5 * 86400_000 + 123)  # +123 ms
        thi = int(t0 + 9 * 86400_000 + 777)
        lo = np.datetime64(tlo, "ms")
        hi = np.datetime64(thi, "ms")
        q = f"bbox(geom, -8, -8, 8, 8) AND dtg DURING {lo}Z/{hi}Z"
        out = self.ds.query("pts", q)
        expect = brute(self.data, -8, -8, 8, 8, tlo, thi)
        assert np.array_equal(np.sort(np.asarray(out.ids, dtype=np.int64)), expect)

    def test_refinement_skipped_for_decided_filter(self, monkeypatch):
        """A bbox+time filter decided by the index must refine only the
        uncertain boundary rows, not all candidates (VERDICT r2 item 2)."""
        from geomesa_tpu.filter.predicates import And

        calls = {"rows": 0}
        orig = And.evaluate

        def spy(self, batch):
            calls["rows"] += batch.n
            return orig(self, batch)

        monkeypatch.setattr(And, "evaluate", spy)
        out = self.ds.query("pts", self.q(-5, -5, 5, 5, 3, 10))
        assert len(out) > 100
        # full refinement would evaluate every candidate (= every hit and
        # then some); the boundary tier must touch well under 5% of them
        assert calls["rows"] < max(50, 0.05 * len(out))

    def test_contained_spans_certain(self):
        """Contained ranges' rows bypass the kernel and refinement."""
        ds, data = self.ds, self.data
        table = ds.table("pts", "z3")
        idx = [i for i in ds.indexes("pts") if i.name == "z3"][0]
        f = ecql.parse(self.q(-15, -15, 15, 15, 1, 22))
        cfg = idx.scan_config(f)
        assert cfg.range_contained is not None and cfg.contained_exact
        overlap, contained = table.candidate_spans_split(cfg)
        assert len(contained), "a large query should produce contained ranges"
        rows, certain = table.scan(cfg)
        assert certain.any()
        # every contained-span row is marked certain
        from geomesa_tpu.storage.table import _rows_in_spans

        table_rows = np.argsort(table.perm, kind="stable")  # ordinal -> row
        # sanity: certainty is consistent with brute-force membership
        t0 = data[3]
        expect = set(
            brute(data, -15, -15, 15, 15, t0, t0 + 21 * 86400_000).tolist()
        )
        assert set(rows[certain].tolist()) <= expect

    def test_attribute_clip_rows(self):
        """Attribute-index kernel hits clip back to exact value spans."""
        rng = np.random.default_rng(7)
        n = 5000
        sft = FeatureType.from_spec(
            "t2", "name:String:index=true,dtg:Date,*geom:Point:srid=4326"
        )
        ds = DataStore()
        ds.create_schema(sft)
        names = np.array(["alpha", "beta", "gamma"])[rng.integers(0, 3, n)]
        t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
        fc = FeatureCollection.from_columns(
            sft,
            np.arange(n),
            {
                "name": names,
                "dtg": t0 + rng.integers(0, 86400_000, n),
                "geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n)),
            },
        )
        ds.write("t2", fc, check_ids=False)
        out = ds.query("t2", "name = 'beta' AND bbox(geom, -5, -5, 5, 5)")
        x, y = fc.columns["geom"].x, fc.columns["geom"].y
        expect = np.flatnonzero(
            (names == "beta") & (x >= -5) & (x <= 5) & (y >= -5) & (y <= 5)
        )
        assert np.array_equal(np.sort(np.asarray(out.ids, dtype=np.int64)), expect)


class TestBitPacking:
    def test_pack_decode_roundtrip(self):
        rng = np.random.default_rng(0)
        for block in (4096, 16384):
            sub, pack = block // 128, block // 128 // 32
            m = rng.uniform(size=(3, sub, 128)) < 0.1
            import jax.numpy as jnp

            from geomesa_tpu.scan.block_kernels import _pack_bits

            planes = np.stack(
                [np.asarray(_pack_bits(jnp.asarray(m[i]), pack)) for i in range(3)]
            )
            bids = np.array([5, 9, 11], np.int32)
            rows = bk.decode_bits(planes, bids, 3)
            flat = m.reshape(3, -1)
            # _pack_bits bit order: local row = (j*32 + b)*128 + lane; the
            # VMEM mask layout is row-major (sublane*128 + lane) — identical
            expect = np.concatenate(
                [np.flatnonzero(flat[i]) + bids[i] * block for i in range(3)]
            )
            assert np.array_equal(np.sort(rows), np.sort(expect))

    def test_window_slot_merge(self):
        w = np.array(
            [[3, 100, 604799], [4, 0, 604799], [5, 0, 604799], [6, 0, 42]], np.int32
        )
        slots = bk.merge_window_slots(w)
        assert slots.tolist() == [
            [3, 3, 100, 604799],
            [4, 5, 0, 604799],
            [6, 6, 0, 42],
        ]

    def test_window_slot_overflow_widens(self):
        # 12 disjoint single-bin windows -> merged down to 8 conservative slots
        w = np.array([[b * 3, 10, 20] for b in range(12)], np.int32)
        slots = bk.merge_window_slots(w)
        assert len(slots) <= 8
        # superset: every original window is covered by some slot
        for b, lo, hi in w.tolist():
            assert any(
                s[0] <= b <= s[1] and s[2] <= lo and s[3] >= hi for s in slots.tolist()
            )

    def test_window_slot_overflow_inner_drops(self):
        # the inner (certainty) plane must never widen: overflow drops slots,
        # so every surviving slot is one of the originals (subset semantics)
        w = np.array([[b * 3, 10, 20] for b in range(12)], np.int32)
        slots = bk.merge_window_slots(w, overflow="drop")
        assert len(slots) <= 8
        originals = {(b, b, lo, hi) for b, lo, hi in w.tolist()}
        assert all(tuple(s) in originals for s in slots.tolist())

    def test_many_interval_or_query_exact(self):
        """OR of >8 disjoint intervals: wide widens, inner drops — results
        must still be exact (code-review r3 regression)."""
        ds, data = make_store(n=20_000)
        x, y, t, t0 = data
        day = 86_400_000
        parts, m = [], np.zeros(len(t), bool)
        for k in range(10):
            lo = int(t0 + (2 * k) * day + 500)  # unaligned endpoints
            hi = int(t0 + (2 * k + 1) * day + 500)
            parts.append(
                f"dtg DURING {np.datetime64(lo, 'ms')}Z/{np.datetime64(hi, 'ms')}Z"
            )
            m |= (t >= lo) & (t < hi)
        q = f"bbox(geom, -10, -10, 10, 10) AND ({' OR '.join(parts)})"
        out = ds.query("pts", q)
        expect = np.flatnonzero(m & (x >= -10) & (x <= 10) & (y >= -10) & (y <= 10))
        assert np.array_equal(np.sort(np.asarray(out.ids, dtype=np.int64)), expect)

    def test_many_box_or_query_exact(self):
        """OR of >8 bboxes: wide collapses to a union, inner keeps subsets —
        results must still be exact (code-review r3 regression)."""
        ds, data = make_store(n=20_000)
        x, y, t, t0 = data
        boxes = [(-19 + 4 * k, -15 + k, -17.5 + 4 * k, -12 + k) for k in range(10)]
        q = " OR ".join(f"bbox(geom, {a}, {b}, {c}, {d})" for a, b, c, d in boxes)
        out = ds.query("pts", q)
        m = np.zeros(len(x), bool)
        for a, b, c, d in boxes:
            m |= (x >= a) & (x <= c) & (y >= b) & (y <= d)
        expect = np.flatnonzero(m)
        assert np.array_equal(np.sort(np.asarray(out.ids, dtype=np.int64)), expect)


class TestNativeZRanges:
    def test_native_matches_python(self):
        from geomesa_tpu import native

        if not native.available():
            pytest.skip("native lib unavailable")
        import os

        from geomesa_tpu.curve.z2sfc import Z2SFC

        sfc = Z2SFC()
        rng = np.random.default_rng(1)
        for _ in range(10):
            x0, y0 = rng.uniform(-170, 150), rng.uniform(-80, 70)
            w, h = rng.uniform(0.1, 30), rng.uniform(0.1, 15)
            bounds = [(x0, y0, x0 + w, y0 + h)]
            got = sfc.ranges(bounds)
            os.environ["GEOMESA_TPU_NO_NATIVE"] = "1"
            try:
                import geomesa_tpu.native as nat

                saved, nat._lib = nat._lib, False
                want = sfc.ranges(bounds)
            finally:
                nat._lib = saved
                del os.environ["GEOMESA_TPU_NO_NATIVE"]
            assert [(r.lower, r.upper, r.contained) for r in got] == [
                (r.lower, r.upper, r.contained) for r in want
            ]


class TestExtentModeKernel:
    """Direct extent=True kernel cases (XZ tables): bbox-INTERSECTS wide
    plane, all-false inner plane (bbox intersection can never certify the
    actual geometry predicate), and never-matching pad sentinels."""

    NAMES = ("gxmax", "gxmin", "gymax", "gymin")
    SUB = 32
    NB = 4

    def _cols(self):
        rng = np.random.default_rng(11)
        n = self.NB * self.SUB * 128
        x0 = rng.uniform(-170, 160, n).astype(np.float32)
        y0 = rng.uniform(-80, 70, n).astype(np.float32)
        w = rng.uniform(0.1, 10, n).astype(np.float32)
        h = rng.uniform(0.1, 8, n).astype(np.float32)
        cols = {"gxmin": x0, "gymin": y0, "gxmax": x0 + w, "gymax": y0 + h}
        # sentinel-pad the tail exactly like the table does
        from geomesa_tpu.storage.table import _SENTINELS

        for k in cols:
            cols[k][-700:] = _SENTINELS[k]
        import jax.numpy as jnp

        shape = (self.NB, self.SUB, 128)
        return cols, tuple(jnp.asarray(cols[k].reshape(shape)) for k in self.NAMES)

    def test_wide_intersects_inner_empty(self):
        host, cols3 = self._cols()
        boxes = bk.pack_boxes(
            np.array([[-30.0, -20.0, 40.0, 25.0]]),
            np.array([[-29.0, -19.0, 39.0, 24.0]]),  # inner MUST be ignored
        )
        wins = bk.pack_windows(None, None)
        bids, n_real = bk.pad_bids(np.arange(self.NB), self.NB)
        wide, inner = bk._xla_block_scan(
            cols3, bids, boxes, wins,
            col_names=self.NAMES, has_boxes=True, has_windows=False, extent=True,
        )
        # extent box scans skip the inner plane entirely (it would be
        # identically false: bbox intersection can never certify the
        # true geometry predicate)
        assert inner is None
        rows, certain = bk.decode_bits_pair(np.asarray(wide), None, bids, n_real)
        assert not certain.any()
        expect = np.flatnonzero(
            (host["gxmin"] <= 40) & (host["gxmax"] >= -30)
            & (host["gymin"] <= 25) & (host["gymax"] >= -20)
        )
        assert np.array_equal(rows, expect)
        assert len(rows) > 0

    def test_pad_sentinels_never_match(self):
        host, cols3 = self._cols()
        # a box covering the whole world still must not match sentinel rows
        boxes = bk.pack_boxes(np.array([[-180.0, -90.0, 180.0, 90.0]]), None)
        wins = bk.pack_windows(None, None)
        bids, n_real = bk.pad_bids(np.arange(self.NB), self.NB)
        wide, inner = bk._xla_block_scan(
            cols3, bids, boxes, wins,
            col_names=self.NAMES, has_boxes=True, has_windows=False, extent=True,
        )
        rows, _ = bk.decode_bits_pair(np.asarray(wide), inner, bids, n_real)
        n = self.NB * self.SUB * 128
        assert len(rows) == n - 700
        assert rows.max() < n - 700

    def test_interpret_parity_extent(self):
        _, cols3 = self._cols()
        boxes = bk.pack_boxes(np.array([[-30.0, -20.0, 40.0, 25.0]]), None)
        wins = bk.pack_windows(None, None)
        bids, _ = bk.pad_bids(np.array([0, 2]), self.NB)
        kw = dict(col_names=self.NAMES, has_boxes=True, has_windows=False, extent=True)
        w_ref, i_ref = bk._xla_block_scan(cols3, bids, boxes, wins, **kw)
        w_got, i_got = bk._pallas_block_scan(cols3, bids, boxes, wins, interpret=True, **kw)
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_got))
        assert i_ref is None and i_got is None


class TestColumnProjection:
    """ColumnGroups analogue (reference index/conf/ColumnGroups.scala):
    scan variants DMA only the device columns the predicate reads."""

    def setup_method(self):
        self.ds, self.data = make_store(n=20000)

    def _cfg(self, q):
        idx = [i for i in self.ds.indexes("pts") if i.name == "z3"][0]
        return idx.scan_config(ecql.parse(q))

    def test_time_only_query_ships_no_xy(self):
        table = self.ds.table("pts", "z3")
        cfg = self._cfg("dtg DURING 2024-01-03T00:00:00Z/2024-01-07T00:00:00Z")
        assert cfg is not None and cfg.boxes is None and cfg.windows is not None
        rows, _ = table.scan(cfg)
        assert table.last_scan_cols == ("tbin", "toff")
        t0 = self.data[3]
        expect = brute(
            self.data, -1e9, -1e9, 1e9, 1e9, t0 + 2 * 86400_000, t0 + 6 * 86400_000
        )
        assert np.array_equal(np.sort(np.asarray(rows)), expect)

    def test_spatial_only_query_ships_no_time(self):
        table = self.ds.table("pts", "z3")
        cfg = self._cfg("bbox(geom, -5, -5, 5, 5)")
        if cfg is None:
            return  # z3 may decline bbox-only; z2 serves it
        table.scan(cfg)
        assert table.last_scan_cols == ("x", "y")

    def test_full_query_ships_all(self):
        table = self.ds.table("pts", "z3")
        cfg = self._cfg(
            "bbox(geom, -5, -5, 5, 5) AND dtg DURING 2024-01-03T00:00:00Z/2024-01-07T00:00:00Z"
        )
        table.scan(cfg)
        assert table.last_scan_cols == ("tbin", "toff", "x", "y")
        bytes_full = table.last_scan_bytes
        # measured bytes-scanned drop for the projected variant
        cfg2 = self._cfg("dtg DURING 2024-01-03T00:00:00Z/2024-01-07T00:00:00Z")
        table.scan(cfg2)
        assert table.last_scan_cols == ("tbin", "toff")
        assert table.last_scan_bytes < bytes_full


@pytest.mark.parametrize("n_blocks", [1, 33, 128, 3000])
def test_fused_shape_is_a_function_of_block_count(n_blocks):
    """The canonical fused-dispatch shape is decided in one place from
    one input: the table's block count against FUSED_CHUNK_SLOTS. No
    link profile, knob or per-table stamp can move it."""
    from geomesa_tpu.storage.table import FUSED_CHUNK_SLOTS, IndexTable

    table = IndexTable.__new__(IndexTable)
    table.n_blocks = n_blocks
    assert FUSED_CHUNK_SLOTS == 2048
    assert table.fused_slots == min(2048, bk.bucket_of(n_blocks))
    assert table.fused_pack_capacity == table.fused_slots
    # the single-query ladder is bucket_of and nothing else
    assert len(bk.pad_bids(np.arange(n_blocks), n_blocks)[0]) == bk.bucket_of(n_blocks)
    # and the kernel module holds no link profile, slot cap or bucket
    # floor beside its ladders: these are all the names that size a grid
    sizing = {n for n in vars(bk)
              if any(w in n.lower() for w in ("link", "cap", "floor", "bucket"))}
    assert sizing == {
        "M_BUCKETS", "E_BUCKETS", "R_BUCKETS", "FUSED_E_BUCKETS",
        "FUSED_R_BUCKETS", "bucket_of", "r_bucket_of", "fused_e_bucket",
        "fused_r_bucket",
    }, sizing
