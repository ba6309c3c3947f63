"""Processes (kNN, proximity, tube select, unique) against brute force."""

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.process import knn_search, proximity_search, tube_select, unique_values
from geomesa_tpu.process.knn import haversine_m
from geomesa_tpu.sft import FeatureType

SPEC = "kind:String,dtg:Date,*geom:Point:srid=4326"
DAY = 86400_000


@pytest.fixture(scope="module")
def ds():
    sft = FeatureType.from_spec("p", SPEC)
    store = DataStore(tile=64)
    store.create_schema(sft)
    n = 4000
    rng = np.random.default_rng(7)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    x = rng.uniform(-10, 10, n)
    y = rng.uniform(-10, 10, n)
    t = t0 + rng.integers(0, 10 * DAY, n)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(n)],
        {
            "kind": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            "dtg": t,
            "geom": (x, y),
        },
    )
    store.write("p", fc)
    return store, fc, (x, y, t, t0)


class TestKnn:
    def test_matches_brute_force(self, ds):
        store, fc, (x, y, _, _) = ds
        out = knn_search(store, "p", 1.0, 2.0, k=15, estimated_distance_m=5_000)
        d = haversine_m(1.0, 2.0, x, y)
        want = np.argsort(d, kind="stable")[:15]
        got = sorted(out.ids.tolist())
        assert got == sorted(fc.ids[want].tolist())
        # ordered nearest-first
        dx, dy = out.representative_xy()
        dists = haversine_m(1.0, 2.0, dx, dy)
        assert (np.diff(dists) >= 0).all()

    def test_k_larger_than_data(self, ds):
        store, fc, _ = ds
        out = knn_search(
            store, "p", 0.0, 0.0, k=10**6, max_distance_m=5_000_000
        )
        assert len(out) == len(fc)

    def test_with_filter(self, ds):
        store, fc, (x, y, _, _) = ds
        from geomesa_tpu.filter import ecql

        out = knn_search(store, "p", 0.0, 0.0, k=5, filter=ecql.parse("kind = 'a'"))
        assert set(np.asarray(out.columns["kind"])) == {"a"}
        kinds = np.asarray(fc.columns["kind"])
        d = haversine_m(0.0, 0.0, x, y)
        d[kinds != "a"] = np.inf
        want = np.argsort(d, kind="stable")[:5]
        assert sorted(out.ids.tolist()) == sorted(fc.ids[want].tolist())


class TestProximity:
    def test_matches_brute_force(self, ds):
        store, fc, (x, y, _, _) = ds
        pts = [(0.0, 0.0), (5.0, 5.0)]
        out = proximity_search(store, "p", pts, distance_m=100_000)
        d = np.minimum(
            haversine_m(0.0, 0.0, x, y), haversine_m(5.0, 5.0, x, y)
        )
        truth = d <= 100_000
        assert sorted(out.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_empty_inputs(self, ds):
        store, _, _ = ds
        assert len(proximity_search(store, "p", [], 1000)) == 0


class TestTube:
    def test_corridor(self, ds):
        store, fc, (x, y, t, t0) = ds
        track_xy = [(-5.0, -5.0), (0.0, 0.0), (5.0, 5.0)]
        track_t = [t0, t0 + 5 * DAY, t0 + 10 * DAY]
        out = tube_select(store, "p", track_xy, track_t, buffer_m=150_000)
        # brute force: distance to interpolated position at each row's time
        px = np.interp(t, np.array(track_t), np.array([p[0] for p in track_xy]))
        py = np.interp(t, np.array(track_t), np.array([p[1] for p in track_xy]))
        truth = haversine_m(x, y, px, py) <= 150_000
        assert sorted(out.ids.tolist()) == sorted(fc.ids[truth].tolist())

    def test_bad_track(self, ds):
        store, _, _ = ds
        with pytest.raises(ValueError):
            tube_select(store, "p", [(0, 0)], [0], buffer_m=100)


def _slices_loop(xy, ts, buffer_m, bin_ms, max_bins):
    """``process/tube.py`` ``_slices`` as it was before PR 48, a bin at a
    time (the reference the vectorised one is held to, to the last bit):
    ``(boxes [n, 4], windows [n, 2])`` of the slices that hold any time."""
    from geomesa_tpu.process.knn import _meters_to_degrees

    span = int(ts[-1] - ts[0])
    if bin_ms is None:
        bin_ms = max(1, span // max(1, len(xy)))
    n_bins = min(max_bins, max(1, -(-span // bin_ms)))
    bin_ms = -(-span // n_bins)
    mids = ts[0] + bin_ms * np.arange(n_bins) + bin_ms // 2
    cx = np.interp(mids, ts, xy[:, 0])
    cy = np.interp(mids, ts, xy[:, 1])
    boxes, windows = [], []
    for i in range(n_bins):
        lo = int(ts[0] + i * bin_ms)
        hi = int(ts[-1] + 1 if i == n_bins - 1 else min(ts[0] + (i + 1) * bin_ms, ts[-1] + 1))
        j0, j1 = np.searchsorted(ts, [lo, hi])
        seg_x = np.concatenate([[cx[i]], xy[max(0, j0 - 1) : j1 + 1, 0]])
        seg_y = np.concatenate([[cy[i]], xy[max(0, j0 - 1) : j1 + 1, 1]])
        deg = _meters_to_degrees(buffer_m, float(np.abs(seg_y).max()))
        if lo < hi:  # an empty DURING held no row: the carrier leaves it out
            boxes.append((float(seg_x.min()) - deg, max(float(seg_y.min()) - deg, -90.0),
                          float(seg_x.max()) + deg, min(float(seg_y.max()) + deg, 90.0)))
            windows.append((lo, hi))
    return np.array(boxes, np.float64), np.array(windows, np.int64)


def _seeded_track(seed):
    """One of 1,000 tracks: the AIS cell's two classes (360 waypoints over
    6 h, 288 over 24 h), duplicate timestamps, a span that is a whole
    multiple of the bins, a span of a few milliseconds, high latitudes, a
    track across the antimeridian, explicit ``bin_ms`` and ``max_bins``."""
    rng = np.random.default_rng(seed)
    kind = seed % 10
    n = {0: 360, 1: 288}.get(kind, int(rng.integers(2, 500)))
    hours = {0: 6, 1: 24}.get(kind, float(rng.uniform(0.01, 48)))
    t0 = 1_700_000_000_000 + int(rng.integers(0, 10**9))
    ts = t0 + np.sort(rng.integers(0, int(hours * 3_600_000), n)) // 1000 * 1000
    if kind == 2:  # dwell: many reports at one instant
        ts[rng.integers(0, n, n // 3)] = ts[n // 2]
        ts = np.sort(ts)
    if kind == 3:  # a whole multiple of the bins: the last edge IS the last instant
        ts = t0 + np.arange(n, dtype=np.int64) * 256_000
    if kind == 4:  # shorter than its bins' steps: some bins hold no time
        ts = t0 + np.sort(rng.integers(0, int(rng.integers(1, 40)), n))
    lat0 = {5: 84.0, 6: -88.5}.get(kind, float(rng.uniform(-60, 60)))
    lon0 = 179.2 if kind == 7 else float(rng.uniform(-170, 170))
    xy = np.stack([lon0 + np.cumsum(rng.normal(0.004, 0.01, n)),
                   np.clip(lat0 + np.cumsum(rng.normal(0.002, 0.01, n)), -90, 90)], 1)
    bin_ms = None if kind != 8 else int(rng.integers(1, 3_600_000))
    max_bins = 256 if kind != 9 else int(rng.integers(1, 300))
    buffer_m = float(rng.choice([500.0, 2000.0, 10_000.0, 250_000.0]))
    return xy, ts.astype(np.int64), buffer_m, bin_ms, max_bins


class TestTubeSlices:
    """PR 48: all bins at once, the same f64 boxes and [lo, hi) windows as
    the loop, to the last bit."""

    @pytest.mark.parametrize("block", range(10))
    def test_the_arrays_are_the_loops_to_the_last_bit(self, block):
        from geomesa_tpu.filter.predicates import And, Slices
        from geomesa_tpu.process.tube import _slices

        shapes = set()
        for seed in range(block * 100, block * 100 + 100):
            xy, ts, buffer_m, bin_ms, max_bins = _seeded_track(seed)
            got = _slices("geom", "dtg", xy, ts, buffer_m, bin_ms, max_bins)
            boxes, windows = _slices_loop(xy, ts, buffer_m, bin_ms, max_bins)
            if len(boxes) == 1:  # one slice stays And(BBox, During)
                assert isinstance(got, And)
                bbox, during = got.filters
                assert bbox.bounds == tuple(boxes[0]) and (during.lo_ms, during.hi_ms) == tuple(windows[0])
                assert (bbox.prop, during.prop) == ("geom", "dtg")
            else:
                assert isinstance(got, Slices) and (got.geom, got.dtg) == ("geom", "dtg")
                assert got.boxes.tobytes() == boxes.tobytes(), seed
                assert got.windows.tobytes() == windows.tobytes(), seed
                assert got.windows[-1, 1] == ts[-1] + 1  # the last instant is inside
            shapes.add(len(boxes))
        assert len(shapes) > 3 and max(shapes) <= 300

    def test_the_cells_two_classes_are_256_slices(self):
        from geomesa_tpu.process.tube import _slices

        for seed in (0, 1, 10, 11):
            xy, ts, buffer_m, _, _ = _seeded_track(seed)
            assert len(_slices("geom", "dtg", xy, ts, buffer_m, None, 256)) == 256

    def test_a_bin_that_holds_no_time_is_left_out(self):
        from geomesa_tpu.filter.predicates import Slices
        from geomesa_tpu.process.tube import _slices

        # 10 ms over 8 bins of 2 ms: bins 5 to 7 start past the last instant
        ts = 1_700_000_000_000 + np.arange(10, dtype=np.int64)
        xy = np.stack([np.linspace(0, 1, 10), np.linspace(0, 1, 10)], 1)
        got = _slices("geom", "dtg", xy, ts, 500.0, None, 8)
        assert isinstance(got, Slices) and len(got) == 5
        assert got.windows[0, 0] == ts[0] and got.windows[-1, 1] == ts[-1] + 1
        assert (np.diff(got.windows, axis=1) > 0).all()

    def test_degrees_each_is_the_scalars_at_every_latitude(self):
        from geomesa_tpu.process.knn import _meters_to_degrees, _meters_to_degrees_each

        rng = np.random.default_rng(48)
        lats = np.concatenate([rng.uniform(-90, 90, 5000), [0.0, -0.0, 89.0, -89.0, 90.0, 89.999]])
        for m in (1.0, 500.0, 2000.0, 10_000.0, 250_000.0, 5e6, 2.1e7):
            want = np.array([_meters_to_degrees(m, float(v)) for v in lats])
            assert _meters_to_degrees_each(m, lats).tobytes() == want.tobytes(), m


class TestUnique:
    def test_counts(self, ds):
        store, fc, _ = ds
        pairs = unique_values(store, "p", "kind", sort_by_count=True)
        vals, cnts = np.unique(np.asarray(fc.columns["kind"]), return_counts=True)
        assert dict(pairs) == dict(zip(vals.tolist(), cnts.tolist()))
        assert pairs[0][1] == max(cnts)


class TestJoinProcess:
    """JoinProcess analogue: correlate two types by attribute value."""

    def _stores(self):
        rng = np.random.default_rng(9)
        ds = DataStore()
        tracks = FeatureType.from_spec(
            "tracks", "vessel:String:index=true,dtg:Date,*geom:Point:srid=4326"
        )
        info = FeatureType.from_spec(
            "vessels", "vessel:String:index=true,flag:String,*geom:Point:srid=4326"
        )
        ds.create_schema(tracks)
        ds.create_schema(info)
        n = 2000
        t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
        ds.write("tracks", FeatureCollection.from_columns(
            tracks, [str(i) for i in range(n)],
            {"vessel": np.array([f"v{i % 40}" for i in range(n)]),
             "dtg": t0 + rng.integers(0, 86400_000, n),
             "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n))},
        ))
        ds.write("vessels", FeatureCollection.from_columns(
            info, [f"m{i}" for i in range(60)],
            {"vessel": np.array([f"v{i}" for i in range(60)]),
             "flag": np.array([f"f{i % 5}" for i in range(60)]),
             "geom": (rng.uniform(-60, 60, 60), rng.uniform(-45, 45, 60))},
        ))
        return ds

    def test_join_by_attribute(self):
        from geomesa_tpu.process import join_search

        ds = self._stores()
        out = join_search(
            ds, "tracks", "vessels", "vessel",
            primary_filter="bbox(geom, -20, -15, 20, 15)",
        )
        # expected: vessels whose id appears among the primary hits
        hits = ds.query("tracks", "bbox(geom, -20, -15, 20, 15)")
        want = sorted(set(hits.columns["vessel"].tolist()))
        assert sorted(out.columns["vessel"].tolist()) == want
        assert len(out) > 0

    def test_join_with_secondary_filter(self):
        from geomesa_tpu.process import join_search

        ds = self._stores()
        out = join_search(
            ds, "tracks", "vessels", "vessel",
            primary_filter="bbox(geom, -60, -45, 60, 45)",
            secondary_filter="flag = 'f2'",
        )
        assert len(out) > 0
        assert set(out.columns["flag"].tolist()) == {"f2"}

    def test_join_value_cap_falls_back_to_mask(self):
        from geomesa_tpu.process import join_search

        ds = self._stores()
        small = join_search(ds, "tracks", "vessels", "vessel", max_values=3)
        full = join_search(ds, "tracks", "vessels", "vessel")
        assert sorted(small.ids.tolist()) == sorted(full.ids.tolist())

    def test_empty_primary(self):
        from geomesa_tpu.process import join_search

        ds = self._stores()
        out = join_search(
            ds, "tracks", "vessels", "vessel",
            primary_filter="vessel = 'nope'",
        )
        assert len(out) == 0 and out.sft.name == "vessels"

    def test_unknown_attribute_rejected(self):
        from geomesa_tpu.process import join_search

        ds = self._stores()
        with pytest.raises(ValueError):
            join_search(ds, "tracks", "vessels", "missing")


class TestKnnRadiusEstimate:
    def test_auto_radius_reduces_expansions(self):
        """Stats-based start radius: the first window should usually hold k
        neighbours, so the expansion loop runs once for uniform data."""
        rng = np.random.default_rng(14)
        n = 20000
        sft = FeatureType.from_spec("p", "*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        ds.write("p", FeatureCollection.from_columns(
            sft, np.arange(n), {"geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n))}
        ), check_ids=False)
        from geomesa_tpu.process.knn import _estimate_radius_m, knn_search

        r = _estimate_radius_m(ds, "p", 10, 0.0, 0.0, 1_000_000.0)
        # ~50 pts per sq-degree here: a sane estimate sits well under 100km
        assert 1000 < r < 200_000
        queries = 0
        orig = ds.query

        def counting(*a, **k):
            nonlocal queries
            queries += 1
            return orig(*a, **k)

        ds.query = counting
        out = knn_search(ds, "p", 0.0, 0.0, k=10)
        assert len(out) == 10
        assert queries <= 2  # estimate good enough to avoid radius doubling

    def test_fallback_without_stats(self):
        from geomesa_tpu.process.knn import _estimate_radius_m

        sft = FeatureType.from_spec("e", "*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        assert _estimate_radius_m(ds, "e", 10, 0.0, 0.0, 1_000_000.0) == 10_000.0


class TestRouteSearch:
    """route_search vs a brute-force numpy re-implementation (reference
    RouteSearchProcess: dwithin buffer + closest-segment heading match)."""

    @pytest.fixture(scope="class")
    def route_ds(self):
        from geomesa_tpu.process.knn import METERS_PER_DEGREE

        sft = FeatureType.from_spec(
            "trk", "heading:Double,*geom:Point:srid=4326"
        )
        store = DataStore(tile=64)
        store.create_schema(sft)
        rng = np.random.default_rng(11)
        n = 3000
        x = rng.uniform(-1, 3, n)
        y = rng.uniform(-1, 3, n)
        heading = rng.uniform(0, 360, n)
        fc = FeatureCollection.from_columns(
            sft, [str(i) for i in range(n)],
            {"heading": heading, "geom": (x, y)},
        )
        store.write("trk", fc)
        return store, (x, y, heading)

    # an L-shaped route: east along y=0 then north along x=2
    ROUTE = np.array([[0.0, 0.0], [2.0, 0.0], [2.0, 2.0]])

    def _brute(self, x, y, heading, buffer_m, thr, bidirectional):
        from geomesa_tpu.process.knn import METERS_PER_DEGREE
        from geomesa_tpu.process.route import (
            _point_segment_distances, heading_diff,
        )

        d, b = _point_segment_distances(
            x, y, self.ROUTE[:-1], self.ROUTE[1:]
        )
        k = np.argmin(d, axis=1)
        rng = np.arange(len(k))
        dist = d[rng, k]
        diff = heading_diff(b[rng, k], heading)
        m = diff <= thr
        if bidirectional:
            m |= np.abs(diff - 180.0) <= thr
        return (dist <= buffer_m) & m

    def test_matches_brute_force(self, route_ds):
        from geomesa_tpu.process import route_search

        store, (x, y, heading) = route_ds
        out = route_search(
            store, "trk", self.ROUTE, buffer_m=30_000,
            heading_threshold_deg=25.0, heading_field="heading",
        )
        want = np.flatnonzero(self._brute(x, y, heading, 30_000, 25.0, False))
        got = np.sort(np.asarray(out.ids, dtype=np.int64).astype(np.int64))
        np.testing.assert_array_equal(got, want)
        assert len(want) > 0

    def test_bidirectional_superset(self, route_ds):
        from geomesa_tpu.process import route_search

        store, (x, y, heading) = route_ds
        uni = route_search(
            store, "trk", self.ROUTE, 30_000, 25.0,
            heading_field="heading",
        )
        bi = route_search(
            store, "trk", self.ROUTE, 30_000, 25.0,
            heading_field="heading", bidirectional=True,
        )
        want = np.flatnonzero(self._brute(x, y, heading, 30_000, 25.0, True))
        np.testing.assert_array_equal(
            np.sort(np.asarray(bi.ids, dtype=np.int64)), want
        )
        assert len(bi) > len(uni)

    def test_heading_required_for_points(self, route_ds):
        from geomesa_tpu.process import route_search

        store, _ = route_ds
        with pytest.raises(ValueError, match="heading_field"):
            route_search(store, "trk", self.ROUTE, 1000, 10.0)

    def test_wkt_route_and_filter(self, route_ds):
        from geomesa_tpu.filter import ecql
        from geomesa_tpu.process import route_search

        store, (x, y, heading) = route_ds
        out = route_search(
            store, "trk", "LINESTRING(0 0, 2 0, 2 2)", 30_000, 25.0,
            heading_field="heading",
            filter=ecql.parse("bbox(geom, -1, -1, 1, 1)"),
        )
        brute = self._brute(x, y, heading, 30_000, 25.0, False)
        brute &= (x >= -1) & (x <= 1) & (y >= -1) & (y <= 1)
        np.testing.assert_array_equal(
            np.sort(np.asarray(out.ids, dtype=np.int64)),
            np.flatnonzero(brute),
        )


class TestTransformProcesses:
    """point2point / track_label / date_offset / bin+arrow conversion
    (reference geomesa-process transform tier)."""

    @pytest.fixture(scope="class")
    def tracks(self):
        sft = FeatureType.from_spec(
            "trk2", "track:String,dtg:Date,*geom:Point:srid=4326"
        )
        t0 = np.datetime64("2024-03-01T00:00:00", "ms").astype(np.int64)
        HOUR = 3600_000
        rows = [
            # track a: 3 points, crosses a day boundary between p1 and p2
            ("a", t0 + 22 * HOUR, 0.0, 0.0),
            ("a", t0 + 23 * HOUR, 1.0, 0.0),
            ("a", t0 + 25 * HOUR, 2.0, 0.0),
            # track b: 2 points, second is a duplicate position
            ("b", t0 + 1 * HOUR, 5.0, 5.0),
            ("b", t0 + 2 * HOUR, 5.0, 5.0),
            # track c: single point
            ("c", t0 + 3 * HOUR, 9.0, 9.0),
        ]
        fc = FeatureCollection.from_columns(
            sft,
            [str(i) for i in range(len(rows))],
            {
                "track": np.array([r[0] for r in rows]),
                "dtg": np.array([r[1] for r in rows], dtype=np.int64),
                "geom": (
                    np.array([r[2] for r in rows]),
                    np.array([r[3] for r in rows]),
                ),
            },
        )
        return fc, t0

    def test_point2point_segments(self, tracks):
        from geomesa_tpu.process import point2point

        fc, t0 = tracks
        out = point2point(fc, "track", "dtg", min_points=1)
        # a: 2 segments; b: its only segment is singular (dropped); c: too small
        assert len(out) == 2
        assert list(out.columns["track"]) == ["a", "a"]
        assert list(out.ids) == ["a-0", "a-1"]
        g0 = out.geom_column.geometry(0)
        assert [tuple(c) for c in g0.coords] == [(0.0, 0.0), (1.0, 0.0)]
        HOUR = 3600_000
        np.testing.assert_array_equal(
            out.columns["dtg_start"], [t0 + 22 * HOUR, t0 + 23 * HOUR]
        )
        np.testing.assert_array_equal(
            out.columns["dtg_end"], [t0 + 23 * HOUR, t0 + 25 * HOUR]
        )

    def test_point2point_break_on_day(self, tracks):
        from geomesa_tpu.process import point2point

        fc, _ = tracks
        out = point2point(fc, "track", "dtg", min_points=1, break_on_day=True)
        assert len(out) == 1  # a's day-crossing segment dropped
        assert list(out.ids) == ["a-0"]

    def test_point2point_keep_singular(self, tracks):
        from geomesa_tpu.process import point2point

        fc, _ = tracks
        out = point2point(
            fc, "track", "dtg", min_points=1, filter_singular=False
        )
        assert len(out) == 3  # b's zero-length segment kept

    def test_track_label(self, tracks):
        from geomesa_tpu.process import track_label

        fc, t0 = tracks
        out = track_label(fc, "track", "dtg")
        assert len(out) == 3
        got = dict(zip(out.columns["track"].tolist(), out.columns["dtg"].tolist()))
        HOUR = 3600_000
        assert got == {
            "a": t0 + 25 * HOUR, "b": t0 + 2 * HOUR, "c": t0 + 3 * HOUR
        }

    def test_date_offset(self, tracks):
        from geomesa_tpu.process import date_offset

        fc, _ = tracks
        out = date_offset(fc, "dtg", 60_000)
        np.testing.assert_array_equal(
            np.asarray(out.columns["dtg"]),
            np.asarray(fc.columns["dtg"]) + 60_000,
        )
        # input unchanged
        assert out.columns["dtg"] is not fc.columns["dtg"]

    def test_bin_conversion_roundtrip(self, tracks):
        from geomesa_tpu.process import bin_conversion
        from geomesa_tpu.utils import bin_format

        fc, _ = tracks
        data = bin_conversion(fc, "track", "dtg")
        dec = bin_format.decode(data)
        assert len(dec["lat"]) == len(fc)
        np.testing.assert_allclose(dec["lon"], fc.representative_xy()[0])

    def test_arrow_conversion(self, tracks):
        pytest.importorskip("pyarrow")
        from geomesa_tpu.io.arrow import read_arrow_table
        from geomesa_tpu.process import arrow_conversion

        fc, _ = tracks
        table = read_arrow_table(arrow_conversion(fc))
        assert table.num_rows == len(fc)


class TestKnnLocalRadius:
    """Sketch-refined start radius (z2 store): sparse query regions grow
    the window host-side instead of paying device-query doubling rounds."""

    @pytest.fixture(scope="class")
    def clustered(self):
        rng = np.random.default_rng(21)
        sft = FeatureType.from_spec("c", "*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        # dense cluster at (0, 0), nothing within ~10 degrees of (40, 40)
        n = 30000
        x = rng.normal(0, 0.5, n)
        y = rng.normal(0, 0.5, n)
        ds.write("c", FeatureCollection.from_columns(
            sft, np.arange(n), {"geom": (x, y)}
        ), check_ids=False)
        return ds

    def test_z2_sketch_feeds_estimate_count(self, clustered):
        ds = clustered
        est = ds.estimate_count("c", "bbox(geom, -1, -1, 1, 1)")
        # sketch-based (not exact): right order of magnitude is enough
        true = ds.count("c", "bbox(geom, -1, -1, 1, 1)")
        assert true > 0
        assert 0.2 * true < est < 5 * true

    def test_sparse_region_grows_radius_without_queries(self, clustered):
        from geomesa_tpu.process.knn import _estimate_radius_m, knn_search

        ds = clustered
        r_dense = _estimate_radius_m(ds, "c", 10, 0.0, 0.0, 5e6)
        r_sparse = _estimate_radius_m(ds, "c", 10, 40.0, 40.0, 5e6)
        assert r_sparse > 10 * r_dense  # local sketch sees the emptiness
        queries = 0
        orig = ds.query

        def counting(*a, **k):
            nonlocal queries
            queries += 1
            return orig(*a, **k)

        ds.query = counting
        try:
            out = knn_search(ds, "c", 40.0, 40.0, k=5, max_distance_m=2e7)
        finally:
            ds.query = orig
        assert len(out) == 5
        assert queries <= 3


class TestThinProcesses:
    def test_query_sampling_minmax(self, ds):
        from geomesa_tpu.process import (
            minmax_process, query_process, sampling_process,
        )

        store, fc, (x, y, t, t0) = ds
        out = query_process(store, "p", "bbox(geom, -5, -5, 5, 5)")
        want = np.flatnonzero((x >= -5) & (x <= 5) & (y >= -5) & (y <= 5))
        assert np.array_equal(np.sort(np.asarray(out.ids, np.int64)), want)
        s = sampling_process(fc, 0.25)
        assert 0 < len(s) < len(fc)
        mm = minmax_process(store, "p", "dtg")
        assert int(mm[0]) == int(t.min()) and int(mm[1]) == int(t.max())
        mm2 = minmax_process(store, "p", "dtg", "bbox(geom, -5, -5, 5, 5)")
        assert int(mm2[0]) == int(t[want].min())


class TestKnnMany:
    def test_matches_per_point_search(self, ds):
        from geomesa_tpu.process import knn_many, knn_search

        store, fc, (x, y, t, t0) = ds
        rng = np.random.default_rng(33)
        pts = [(float(rng.uniform(-9, 9)), float(rng.uniform(-9, 9)))
               for _ in range(8)]
        # one far-away point forces the expansion rounds
        pts.append((60.0, 60.0))
        batched = knn_many(store, "p", pts, k=6, max_distance_m=2e7)
        for (qx, qy), got in zip(pts, batched):
            want = knn_search(store, "p", qx, qy, k=6, max_distance_m=2e7)
            assert got.ids.tolist() == want.ids.tolist(), (qx, qy)
        assert all(len(b) == 6 for b in batched)

    def test_with_filter(self, ds):
        from geomesa_tpu.filter import ecql
        from geomesa_tpu.process import knn_many, knn_search

        store, fc, _ = ds
        f = ecql.parse("kind = 'b'")
        got = knn_many(store, "p", [(0.0, 0.0)], k=5, filter=f)[0]
        want = knn_search(store, "p", 0.0, 0.0, k=5, filter=f)
        assert got.ids.tolist() == want.ids.tolist()
        assert set(got.columns["kind"]) == {"b"}


class TestKnnAntimeridian:
    def test_wraps_across_seam(self):
        """Neighbours across +/-180 must win over farther same-side points
        (the window becomes two boxes at the seam)."""
        from geomesa_tpu.process import knn_many, knn_search
        from geomesa_tpu.process.knn import haversine_m

        sft = FeatureType.from_spec("s", "*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        x = np.array([-179.9, -179.5, 178.0, 170.0, 0.0])
        y = np.zeros(5)
        ds.write("s", FeatureCollection.from_columns(
            sft, np.arange(5), {"geom": (x, y)}
        ))
        got = knn_search(ds, "s", 179.8, 0.0, k=2, estimated_distance_m=30_000)
        d = haversine_m(x, y, 179.8, 0.0)
        want = np.argsort(d)[:2]
        assert set(np.asarray(got.ids, np.int64).tolist()) == set(want.tolist())
        many = knn_many(ds, "s", [(179.8, 0.0)], k=2, estimated_distance_m=30_000)
        assert many[0].ids.tolist() == got.ids.tolist()


class TestTubeBruteForce:
    def test_matches_continuous_interpolation(self):
        from geomesa_tpu.process import tube_select
        from geomesa_tpu.process.knn import haversine_m

        rng = np.random.default_rng(0)
        sft = FeatureType.from_spec("ev", "dtg:Date,*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        n = 20000
        t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
        x = rng.uniform(-5, 15, n)
        y = rng.uniform(-5, 15, n)
        t = t0 + rng.integers(0, 3600_000, n)
        ds.write("ev", FeatureCollection.from_columns(
            sft, np.arange(n), {"dtg": t, "geom": (x, y)}
        ), check_ids=False)
        track = np.stack([np.linspace(0, 10, 20), np.linspace(0, 10, 20)], axis=1)
        times = t0 + np.linspace(0, 3600_000, 20).astype(np.int64)
        out = tube_select(ds, "ev", track, times, buffer_m=100_000, bin_ms=60_000)
        cx = np.interp(t, times, track[:, 0])
        cy = np.interp(t, times, track[:, 1])
        exact = np.flatnonzero(haversine_m(x, y, cx, cy) <= 100_000)
        np.testing.assert_array_equal(
            np.sort(np.asarray(out.ids, np.int64)), exact
        )
        assert len(exact) > 50
