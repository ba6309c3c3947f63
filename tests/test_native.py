"""Native C++ encoder: bit-exact parity with the numpy curve path."""

import numpy as np
import pytest

from geomesa_tpu import native
from geomesa_tpu.curve.binnedtime import BinnedTime, MAX_BIN, MAX_OFFSET, TimePeriod
from geomesa_tpu.curve.z2sfc import Z2SFC
from geomesa_tpu.curve.z3sfc import Z3SFC
from geomesa_tpu.curve.zorder import Z2, Z3

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library unavailable (no g++?)"
)


def test_morton2_parity():
    rng = np.random.default_rng(0)
    x = rng.integers(0, 1 << 31, 10_000).astype(np.uint64)
    y = rng.integers(0, 1 << 31, 10_000).astype(np.uint64)
    np.testing.assert_array_equal(native.morton2(x, y), Z2.index(x, y))


def test_morton3_parity_and_decode():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    y = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    t = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    z = native.morton3(x, y, t)
    np.testing.assert_array_equal(z, Z3.index(x, y, t))
    dx, dy, dt = native.morton3_decode(z)
    np.testing.assert_array_equal(dx, x)
    np.testing.assert_array_equal(dy, y)
    np.testing.assert_array_equal(dt, t)


@pytest.mark.parametrize("period", ["day", "week"])
def test_z3_write_keys_parity(period):
    rng = np.random.default_rng(2)
    n = 20_000
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    # include exact boundary values where float rounding bites
    x[:4] = [-180.0, 180.0, 0.0, -0.0]
    y[:4] = [-90.0, 90.0, 0.0, 179.9999 % 90]
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    millis = t0 + rng.integers(0, 400 * 86400_000, n)
    millis[:2] = [0, t0]

    out = native.z3_write_keys(x, y, millis, period, MAX_OFFSET[TimePeriod(period)], MAX_BIN)
    assert out is not None
    bins, zs, cols = out

    sfc = Z3SFC.for_period(period)
    binner = BinnedTime(period)
    binned = binner.to_binned(millis)
    want_z = sfc.index(x, y, binned.offset.astype(np.float64))
    np.testing.assert_array_equal(zs, want_z.astype(np.uint64))
    np.testing.assert_array_equal(bins, binned.bin.astype(np.int32))
    np.testing.assert_array_equal(cols["toff"], binned.offset.astype(np.int32))
    np.testing.assert_array_equal(cols["x"], x.astype(np.float32))


def test_z3_write_keys_rejects_bad_dates():
    with pytest.raises(ValueError):
        native.z3_write_keys(
            np.zeros(1), np.zeros(1), np.array([-5]), "week",
            MAX_OFFSET[TimePeriod.WEEK], MAX_BIN,
        )
    far = np.array([(MAX_BIN + 10) * 7 * 86_400_000], dtype=np.int64)
    with pytest.raises(ValueError):
        native.z3_write_keys(
            np.zeros(1), np.zeros(1), far, "week",
            MAX_OFFSET[TimePeriod.WEEK], MAX_BIN,
        )


def test_z3_calendar_period_falls_back():
    assert (
        native.z3_write_keys(np.zeros(1), np.zeros(1), np.array([0]), "month", 1, 1)
        is None
    )


def test_z2_write_keys_parity():
    rng = np.random.default_rng(3)
    n = 20_000
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    x[:2] = [-180.0, 180.0]
    y[:2] = [-90.0, 90.0]
    z, cols = native.z2_write_keys(x, y)
    want = Z2SFC().index(x, y)
    np.testing.assert_array_equal(z, want.astype(np.uint64))
    np.testing.assert_array_equal(cols["y"], y.astype(np.float32))


def test_store_query_identical_with_and_without_native(monkeypatch):
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    def build():
        sft = FeatureType.from_spec("n", "dtg:Date,*geom:Point:srid=4326")
        ds = DataStore(tile=64)
        ds.create_schema(sft)
        rng = np.random.default_rng(4)
        n = 2000
        t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
        ds.write("n", FeatureCollection.from_columns(
            sft, [str(i) for i in range(n)],
            {"dtg": t0 + rng.integers(0, 20 * 86400_000, n),
             "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))},
        ))
        return ds

    q = "bbox(geom, -30, -20, 40, 35) AND dtg DURING 2024-01-03T00:00:00Z/2024-01-12T00:00:00Z"
    with_native = sorted(build().query("n", q).ids.tolist())
    monkeypatch.setattr(native, "_lib", False)
    without = sorted(build().query("n", q).ids.tolist())
    assert with_native == without and len(with_native) > 0


class TestBitmaskDecode:
    """Native bitmask decode + span merge vs the numpy reference paths."""

    def _planes(self, seed, n_real=6, pack=4):
        rng = np.random.default_rng(seed)
        # full u32 range: bit 31 (the int32 sign bit) must be exercised —
        # a signed shift/compare regression in the C++ would only show there
        wide = (
            rng.integers(0, 1 << 32, (n_real, pack, 128), dtype=np.uint64)
            .astype(np.uint32)
            .view(np.int32)
        )
        wide[rng.uniform(size=wide.shape) < 0.7] = 0  # sparse-ish
        inner = wide & (
            rng.integers(0, 1 << 32, wide.shape, dtype=np.uint64)
            .astype(np.uint32)
            .view(np.int32)
        )
        bids = np.sort(rng.choice(50, n_real, replace=False)).astype(np.int64)
        return wide, inner, bids

    def test_decode_matches_numpy(self):
        from geomesa_tpu import native
        from geomesa_tpu.scan import block_kernels as bk

        if not native.available():
            import pytest

            pytest.skip("native unavailable")
        for seed in range(5):
            wide, inner, bids = self._planes(seed)
            block = wide.shape[1] * 32 * 128
            got = native.bitmask_decode_pair(wide, inner, bids, len(bids), block)
            assert got is not None
            assert (np.asarray(wide) < 0).any()  # sign bit really exercised
            # numpy reference
            wb = bk._unpack_plane(wide, len(bids))
            blk, local = np.nonzero(wb)
            rows = bids[blk] * block + local
            cert = bk._unpack_plane(inner, len(bids))[blk, local].astype(bool)
            assert np.array_equal(got[0], rows)
            assert np.array_equal(got[1], cert)

    def test_decode_unsorted_bids_resorted(self):
        from geomesa_tpu import native
        from geomesa_tpu.scan import block_kernels as bk

        if not native.available():
            import pytest

            pytest.skip("native unavailable")
        wide, inner, _ = self._planes(11, n_real=4)
        bids = np.array([9, 2, 30, 5], dtype=np.int64)  # deliberately unsorted
        rows, cert = bk.decode_bits_pair(wide, inner, bids, 4)
        assert np.all(rows[1:] > rows[:-1])  # globally ascending after resort
        # membership matches the numpy reference
        wb = bk._unpack_plane(wide, 4)
        blk, local = np.nonzero(wb)
        block = wide.shape[1] * 32 * 128
        want = np.sort(bids[blk] * block + local)
        assert np.array_equal(rows, want)

    def test_merge_rows_spans_matches_numpy(self):
        from geomesa_tpu import native
        from geomesa_tpu.storage.table import (
            RowSpans, _merge_sorted_rows, _rows_in_spans, _span_rows,
        )

        if not native.available():
            import pytest

            pytest.skip("native unavailable")
        rng = np.random.default_rng(3)
        for _ in range(10):
            spans = []
            pos = 0
            for _ in range(rng.integers(1, 6)):
                pos += int(rng.integers(5, 40))
                end = pos + int(rng.integers(1, 30))
                spans.append((pos, end))
                pos = end
            spans = RowSpans(*np.array(spans, np.int64).T)
            rows = np.unique(rng.integers(0, pos + 50, 60)).astype(np.int64)
            cert = rng.uniform(size=len(rows)) < 0.5
            got = native.merge_rows_spans(spans.lo, spans.hi, rows, cert)
            assert got is not None
            dup = _rows_in_spans(rows, spans)
            want_rows, want_cert = _merge_sorted_rows(
                _span_rows(spans), rows[~dup], cert[~dup]
            )
            assert np.array_equal(got[0], want_rows)
            assert np.array_equal(got[1], want_cert)


def test_counting_argsort_matches_stable_argsort():
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 1024, 100_000).astype(np.int64)
    perm = native.counting_argsort(keys, 1024)
    np.testing.assert_array_equal(
        np.asarray(perm, dtype=np.int64), np.argsort(keys, kind="stable")
    )


def test_xz_index_parity():
    from geomesa_tpu.curve.xzsfc import XZSFC

    rng = np.random.default_rng(8)
    for dims in (2, 3):
        sfc = XZSFC(12 if dims == 2 else 10, dims)
        lo = rng.uniform(0, 0.98, (20_000, dims))
        hi = lo + rng.uniform(0, 0.02, (20_000, dims)) ** 2
        # include degenerate (point-like) and full-extent elements
        lo[:5] = 0.0
        hi[:5] = 1.0
        hi[5:10] = lo[5:10]
        got = native.xz_index(lo, hi, dims, sfc.g, sfc.subtree_size)
        want = sfc.sequence_code(lo, sfc.length_at(lo, hi))
        np.testing.assert_array_equal(got, want)


def test_bitmask_decode_wide_only():
    from geomesa_tpu.scan import block_kernels as bk

    rng = np.random.default_rng(9)
    wide = (
        rng.integers(0, 1 << 32, (5, 4, 128), dtype=np.uint64)
        .astype(np.uint32)
        .view(np.int32)
    )
    wide[rng.uniform(size=wide.shape) < 0.6] = 0
    bids = np.sort(rng.choice(40, 5, replace=False)).astype(np.int64)
    block = 4 * 32 * 128
    got = native.bitmask_decode(wide, bids, 5, block)
    flat = bk._unpack_plane(wide, 5)
    blk, local = np.nonzero(flat)
    want = bids[blk].astype(np.int64) * block + local
    np.testing.assert_array_equal(got, want)


def test_xz_ranges_parity():
    """Native XZ BFS vs the python pass: exact match uncapped; covering
    superset when the range budget caps (gap-close tie-breaks differ)."""
    from geomesa_tpu.curve.xzsfc import XElement, XZSFC

    rng = np.random.default_rng(12)
    for dims in (2, 3):
        sfc = XZSFC(12 if dims == 2 else 10, dims)
        for trial in range(12):
            k = rng.integers(1, 3)
            qs = []
            for _ in range(k):
                lo = rng.uniform(0, 0.9, dims)
                hi = lo + rng.uniform(0.001, 0.1, dims) ** (1 + trial % 2)
                qs.append(XElement(tuple(lo), tuple(np.minimum(hi, 1.0))))
            got = sfc.ranges(qs, max_ranges=200_000)  # large: no capping
            native._lib, saved = False, native._lib
            try:
                want = sfc.ranges(qs, max_ranges=200_000)
            finally:
                native._lib = saved
            assert [(r.lower, r.upper, r.contained) for r in got] == [
                (r.lower, r.upper, r.contained) for r in want
            ]

    # capped: both produce <= max_ranges ranges covering the uncapped set
    sfc = XZSFC(12, 2)
    qs = [XElement((0.1, 0.1), (0.6, 0.55))]
    full = sfc.ranges(qs, max_ranges=100_000)
    capped = sfc.ranges(qs, max_ranges=50)
    assert len(capped) <= 50
    # coverage: the union of capped intervals contains every full range
    # (merge kind-insensitively first: containment flags may differ)
    ivals = sorted((r.lower, r.upper) for r in capped)
    merged = [list(ivals[0])]
    for lo, hi in ivals[1:]:
        if lo <= merged[-1][1] + 1:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    lows = np.array([m[0] for m in merged])
    highs = np.array([m[1] for m in merged])
    for r in full:
        i = np.searchsorted(lows, r.lower, side="right") - 1
        assert i >= 0 and highs[i] >= r.upper  # covered


class TestNativePointsInPolygon:
    def test_parity_vs_numpy(self):
        from geomesa_tpu import geometry as geo
        from geomesa_tpu import native

        if not native.available():
            pytest.skip("native unavailable")
        rng = np.random.default_rng(0)
        n = 50_000
        px = rng.uniform(-5, 15, n)
        py = rng.uniform(-5, 15, n)
        # concave polygon with a hole + a second disjoint part
        shell = np.array(
            [[0, 0], [10, 0], [10, 10], [6, 10], [6, 4], [4, 4], [4, 10],
             [0, 10], [0, 0]], float)
        hole = np.array([[1, 1], [3, 1], [3, 3], [1, 3], [1, 1]], float)
        part2 = geo.Polygon(np.array(
            [[12, 12], [14, 12], [14, 14], [12, 14], [12, 12]], float))
        mp = geo.MultiPolygon([geo.Polygon(shell, [hole]), part2])
        got = native.points_in_polygon(
            px, py,
            [shell, hole, np.asarray(part2.shell)], [0, 0, 1],
        )
        # numpy truth via the per-ring path (force below native threshold)
        want = np.zeros(n, dtype=bool)
        for pi, p in enumerate([geo.Polygon(shell, [hole]), part2]):
            parity = geo.points_in_ring(px, py, p.shell)
            for h in p.holes:
                parity ^= geo.points_in_ring(px, py, h)
            want |= parity
        np.testing.assert_array_equal(got, want)
        # and the public entry point routes identically above threshold
        via_public = geo.points_in_polygon(px, py, mp)
        np.testing.assert_array_equal(via_public, want)

    def test_boundary_grid_cases(self):
        from geomesa_tpu import geometry as geo
        from geomesa_tpu import native

        if not native.available():
            pytest.skip("native unavailable")
        # points exactly on integer grid lines of a unit-square lattice:
        # parity semantics must match numpy bit-for-bit
        xs, ys = np.meshgrid(np.linspace(-1, 3, 41), np.linspace(-1, 3, 41))
        px, py = xs.ravel(), ys.ravel()
        sq = geo.box(0, 0, 2, 2)
        got = native.points_in_polygon(px, py, [np.asarray(sq.shell)], [0])
        want = geo.points_in_ring(px, py, np.asarray(sq.shell))
        np.testing.assert_array_equal(got, want)

    def test_slanted_edge_points(self):
        """Points exactly ON slanted edges: native must match numpy even
        where FMA contraction could flip the strict x comparison."""
        from geomesa_tpu import geometry as geo
        from geomesa_tpu import native

        if not native.available():
            pytest.skip("native unavailable")
        tri = np.array([[0, 0], [7, 3], [2, 9], [0, 0]], float)
        # sample points ON each edge at irrational-ish parameters
        ts = np.linspace(0.01, 0.99, 997)
        pts = []
        for a, b in zip(tri[:-1], tri[1:]):
            pts.append(a + ts[:, None] * (b - a))
        p = np.concatenate(pts)
        got = native.points_in_polygon(p[:, 0], p[:, 1], [tri], [0])
        want = geo.points_in_ring(p[:, 0], p[:, 1], tri)
        np.testing.assert_array_equal(got, want)


_GATHER_COLUMNS_SCRIPT = """
import numpy as np
from geomesa_tpu import native

rng = np.random.default_rng(29)
n = 1 << 16
cols = [
    rng.integers(0, 255, n).astype(np.uint8),                 # 1 byte an item
    rng.integers(0, 1 << 15, n).astype(np.int16),             # 2
    rng.integers(0, 99, n).astype("S3"),                      # 3: no word fits
    rng.integers(-9, 9, n).astype(np.int32),                  # 4: word loads
    rng.normal(size=n).astype(np.float32),
    rng.normal(size=n),                                       # 8
    rng.integers(0, 1 << 60, n),
    rng.integers(0, 999, n).astype("<U3"),                    # 12
    rng.integers(0, 10 ** 9, n).astype("<U24"),               # 96
    rng.normal(size=(n, 5)),                                  # 40: a row of five
    rng.integers(0, 9, (n, 2, 3)).astype(np.int16),           # 12: two trailing axes
    np.zeros((n, 0)),                                         # 0
]
assert all(native.ColumnTable.fits(c) for c in cols)
assert not native.ColumnTable.fits(cols[5][::2]) and not native.ColumnTable.fits(cols[5].astype(object))
table = native.ColumnTable(cols)
assert table.row_bytes == 1 + 2 + 3 + 4 + 4 + 8 + 8 + 12 + 96 + 40 + 12
for m in (0, 1, 7, 2048, 2049, 50_000, 300_000):  # 300,000 rows: 57 MB, a full team
    for dtype in (np.int64, np.uint32, np.int32, np.uint64, np.int16):
        top = min(n, np.iinfo(dtype).max)
        idx = rng.integers(0, top, m).astype(dtype)
        out = native.gather_columns(table, idx)
        assert out.widths is table.widths and len(out.arrays) == len(cols)
        for got, col in zip(out.arrays, cols):
            want = col[idx]
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.flags.c_contiguous and got.flags.owndata
            assert got.tobytes() == want.tobytes()
again = native.gather_columns(out, np.arange(len(idx))[::-1].copy())  # an answer's own table
assert all(a.tobytes() == b[::-1].tobytes() for a, b in zip(again.arrays, out.arrays))
print("ok")
"""


@pytest.mark.parametrize("threads", ["1", "3", None], ids=["omp1", "omp3", "default"])
def test_gather_columns_matches_numpy_for_mixed_item_sizes(threads):
    """One call for columns of 0 to 96 bytes an item, against NumPy's
    indexing; OMP_NUM_THREADS is read when libgomp loads, so each setting
    is a process of its own."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("OMP_NUM_THREADS", None)
    if threads is not None:
        env["OMP_NUM_THREADS"] = threads
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _GATHER_COLUMNS_SCRIPT], env=env, cwd=root,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]


class TestBuildArtefact:
    """PR 21: the library that loads is the one built from the committed
    source, and a failed build says so."""

    def test_artefact_is_named_by_source_hash(self):
        import hashlib

        digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
        assert native._lib_path().name == f"libgeomesa_native-{digest}.so"
        lib = native._load()
        if lib is not None:
            assert digest in lib._name

    def test_failed_build_is_logged_with_compiler_stderr(self, tmp_path, monkeypatch, caplog):
        import logging
        import subprocess
        import types

        def fake_run(cmd, **kw):
            return types.SimpleNamespace(returncode=1, stderr=b"fatal error: no such header")

        monkeypatch.setattr(subprocess, "run", fake_run)
        with caplog.at_level(logging.WARNING, logger="geomesa_tpu.native"):
            assert native._build(tmp_path / "build" / "libgeomesa_native-x.so") is False
        assert "no such header" in caplog.text and "numpy fallbacks" in caplog.text
        assert not list((tmp_path / "build").glob("*.so"))
