"""Query & aggregation cache tier (geomesa_tpu.cache; docs/caching.md).

Covers the ISSUE 2 tentpole: canonical fingerprints (``a AND b`` ==
``b AND a``), LRU/TTL/cost-aware admission, single-flight stampede
protection, generation-based invalidation, tile-aggregate composition
exactness, per-query bypass/pin hints and explain/metrics wiring."""

import json
import threading
import time

import numpy as np
import pytest

from geomesa_tpu.cache import (
    BUCKET_MS, CacheConfig, GenerationTracker, KeyRange, QueryCache,
    fingerprint, key_range_of, schema_signature,
)
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.filter.predicates import And, BBox, canonical_key
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.planning.hints import QueryHints
from geomesa_tpu.sft import FeatureType

T0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
DAY = 86_400_000
SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"


def _store(n=3000, seed=0, cache=True, metrics=None, indices="z3"):
    sft = FeatureType.from_spec("t", SPEC)
    sft.user_data["geomesa.indices.enabled"] = indices
    ds = DataStore(metrics=metrics or MetricsRegistry(), cache=cache)
    ds.create_schema(sft)
    rng = np.random.default_rng(seed)
    ds.write("t", FeatureCollection.from_columns(
        sft, [f"f{i}" for i in range(n)],
        {"name": np.array([f"n{i % 5}" for i in range(n)], dtype=object),
         "dtg": T0 + rng.integers(0, 60 * DAY, n),
         "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n))},
    ), check_ids=False)
    return ds


def _same_rows(a, b):
    """Byte-identical results up to row order."""
    ia = np.argsort(np.asarray(a.ids).astype(str))
    ib = np.argsort(np.asarray(b.ids).astype(str))
    assert np.array_equal(np.asarray(a.ids)[ia], np.asarray(b.ids)[ib])
    ax, ay = a.representative_xy()
    bx, by = b.representative_xy()
    assert np.array_equal(np.asarray(ax)[ia], np.asarray(bx)[ib])
    assert np.array_equal(np.asarray(ay)[ia], np.asarray(by)[ib])


Q = "bbox(geom, -10, -10, 40, 30)"


# -- fingerprints (satellite: deterministic conjunction ordering) ----------

class TestFingerprint:
    def test_and_order_collides(self):
        a = ecql.parse("bbox(geom, -10, -10, 40, 30) AND name = 'n1'")
        b = ecql.parse("name = 'n1' AND bbox(geom, -10, -10, 40, 30)")
        assert canonical_key(a) == canonical_key(b)

    def test_or_order_collides_nested(self):
        a = ecql.parse("(name = 'n1' OR name = 'n2') AND bbox(geom, 0, 0, 9, 9)")
        b = ecql.parse("bbox(geom, 0, 0, 9, 9) AND (name = 'n2' OR name = 'n1')")
        assert canonical_key(a) == canonical_key(b)

    def test_different_filters_do_not_collide(self):
        a = ecql.parse("bbox(geom, -10, -10, 40, 30)")
        b = ecql.parse("bbox(geom, -10, -10, 40, 31)")
        assert canonical_key(a) != canonical_key(b)

    def test_canonical_key_sorts_conjunction_children(self):
        f = ecql.parse("name = 'n1' AND bbox(geom, -10, -10, 40, 30)")
        g = ecql.parse("bbox(geom, -10, -10, 40, 30) AND name = 'n1'")
        ka, kb = canonical_key(f), canonical_key(g)
        assert ka == kb
        # the key renders children in sorted order regardless of input
        inner = ka[len("And("):-1]
        assert inner == ",".join(sorted(canonical_key(c) for c in f.filters))

    def test_store_level_collision(self):
        """Logically-equal conjunctions share ONE cache entry end-to-end."""
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        r1 = ds.query("t", "bbox(geom, -10, -10, 40, 30) AND name = 'n1'")
        r2 = ds.query("t", "name = 'n1' AND bbox(geom, -10, -10, 40, 30)")
        _same_rows(r1, r2)
        assert reg.counters["geomesa.cache.hit"] == 1
        assert reg.counters["geomesa.cache.miss"] == 1
        assert len(ds.cache.result) == 1

    def test_result_hints_change_key_timeout_does_not(self):
        sft = FeatureType.from_spec("t", SPEC)
        sig = schema_signature(sft)
        f = ecql.parse(Q)

        def fp(hints):
            return fingerprint("t", sig, 0, "z3", f, None, hints, None)

        base = fp(None)
        assert fp(QueryHints(timeout=5.0)) == base  # failure knob, not result
        assert fp(QueryHints(transforms=["name"])) != base
        assert fp(QueryHints(sort_by="name")) != base
        assert fp(QueryHints(loose=True)) != base

    def test_auths_change_key(self):
        sft = FeatureType.from_spec("t", SPEC)
        sig = schema_signature(sft)
        f = ecql.parse(Q)
        a = fingerprint("t", sig, 0, "z3", f, None, None, ("admin",))
        b = fingerprint("t", sig, 0, "z3", f, None, None, ("user",))
        c = fingerprint("t", sig, 0, "z3", f, None, None, None)
        assert len({a, b, c}) == 3


# -- generation tracker ----------------------------------------------------

class TestGenerations:
    def test_overlapping_bump_invalidates(self):
        g = GenerationTracker()
        tick = g.tick()
        kr = KeyRange(boxes=((0.0, 0.0, 10.0, 10.0),), interval=(T0, T0 + DAY))
        assert not g.stale("t", kr, tick)
        g.bump("t", bounds=(5.0, 5.0, 6.0, 6.0), time_range=(T0, T0 + DAY))
        assert g.stale("t", kr, tick)

    def test_disjoint_space_does_not_invalidate(self):
        g = GenerationTracker()
        tick = g.tick()
        kr = KeyRange(boxes=((0.0, 0.0, 10.0, 10.0),), interval=None)
        g.bump("t", bounds=(100.0, 50.0, 120.0, 60.0), time_range=None)
        assert not g.stale("t", kr, tick)

    def test_disjoint_time_does_not_invalidate(self):
        g = GenerationTracker()
        tick = g.tick()
        kr = KeyRange(boxes=None, interval=(T0, T0 + DAY))
        g.bump("t", bounds=None, time_range=(T0 + 200 * DAY, T0 + 201 * DAY))
        assert not g.stale("t", kr, tick)

    def test_unknown_range_covers_everything(self):
        g = GenerationTracker()
        tick = g.tick()
        kr = KeyRange(boxes=((0.0, 0.0, 1.0, 1.0),), interval=(T0, T0 + 1))
        g.bump("t")
        assert g.stale("t", kr, tick)

    def test_other_type_untouched(self):
        g = GenerationTracker()
        tick = g.tick()
        g.bump("other")
        assert not g.stale("t", KeyRange.everything(), tick)

    def test_bucket_width_matches_persistence_partitions(self):
        from geomesa_tpu.storage.persist import PARTITION_MS

        assert BUCKET_MS == PARTITION_MS


# -- result cache ----------------------------------------------------------

class TestResultCache:
    def test_hit_returns_identical_rows(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        r1 = ds.query("t", Q)
        r2 = ds.query("t", Q)
        _same_rows(r1, r2)
        assert reg.counters["geomesa.cache.hit"] == 1
        assert reg.counters["geomesa.cache.miss"] == 1
        assert reg.gauges["geomesa.cache.bytes"] > 0

    def test_write_invalidates(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        n0 = len(ds.query("t", Q))
        sft = ds.get_schema("t")
        ds.write("t", FeatureCollection.from_columns(
            sft, ["new0", "new1"],
            {"name": np.array(["z", "z"], dtype=object),
             "dtg": np.full(2, int(T0)),
             "geom": (np.array([5.0, 6.0]), np.array([5.0, 6.0]))},
        ), check_ids=False)
        assert len(ds.query("t", Q)) == n0 + 2
        assert reg.counters["geomesa.cache.invalidation"] >= 1

    def test_disjoint_write_keeps_entry_warm(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        ds.query("t", Q)  # populate: box is -10..40 x -10..30
        sft = ds.get_schema("t")
        ds.write("t", FeatureCollection.from_columns(
            sft, ["far0"],
            {"name": np.array(["z"], dtype=object),
             "dtg": np.full(1, int(T0)),
             "geom": (np.array([150.0]), np.array([70.0]))},
        ), check_ids=False)
        ds.query("t", Q)
        assert reg.counters["geomesa.cache.hit"] == 1  # still served warm

    def test_delete_and_upsert_invalidate(self):
        ds = _store()
        before = ds.query("t", "name = 'n1'")
        ds.delete_features("t", "name = 'n1'")
        assert len(ds.query("t", "name = 'n1'")) == 0
        sft = ds.get_schema("t")
        fid = str(np.asarray(before.ids)[0])
        ds.upsert("t", FeatureCollection.from_columns(
            sft, [fid],
            {"name": np.array(["n1"], dtype=object),
             "dtg": np.full(1, int(T0)),
             "geom": (np.array([0.0]), np.array([0.0]))},
        ))
        assert len(ds.query("t", "name = 'n1'")) == 1

    def test_bypass_hint_skips_probe_and_populate(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        ds.query("t", Q, hints=QueryHints(cache="bypass"))
        assert len(ds.cache.result) == 0
        assert reg.counters["geomesa.cache.hit"] == 0
        assert reg.counters["geomesa.cache.miss"] == 0

    def test_pin_hint_beats_admission_and_eviction(self):
        # admission threshold no real scan here will ever clear
        conf = CacheConfig(max_bytes=1 << 16, min_cost_s=1e9,
                           tile_max_entries=0)
        reg = MetricsRegistry()
        ds = _store(metrics=reg, cache=conf)
        ds.query("t", Q)  # unpinned: rejected by cost admission
        assert len(ds.cache.result) == 0
        assert reg.counters["geomesa.cache.reject"] >= 1
        ds.query("t", Q, hints=QueryHints(cache="pin"))
        assert len(ds.cache.result) == 1
        # eviction pressure: distinct PINNED queries exceed the byte
        # budget, yet the first pinned entry is never evicted
        for i in range(12):
            ds.query("t", f"bbox(geom, {-60 + i}, -40, {60 + i}, 40)",
                     hints=QueryHints(cache="pin"))
        ds.query("t", Q)
        assert reg.counters["geomesa.cache.hit"] >= 1

    def test_ttl_expires_entries(self):
        conf = CacheConfig(ttl_s=0.05, tile_max_entries=0)
        reg = MetricsRegistry()
        ds = _store(metrics=reg, cache=conf)
        ds.query("t", Q)
        ds.query("t", Q)
        assert reg.counters["geomesa.cache.hit"] == 1
        time.sleep(0.06)
        ds.query("t", Q)
        assert reg.counters["geomesa.cache.expired"] == 1
        assert reg.counters["geomesa.cache.miss"] == 2

    def test_lru_eviction_respects_byte_budget(self):
        # entries here run ~12-60 KB: a 96 KB budget admits each one but
        # holds only a few at a time, forcing LRU churn
        conf = CacheConfig(max_bytes=96_000, tile_max_entries=0)
        reg = MetricsRegistry()
        ds = _store(metrics=reg, cache=conf)
        for i in range(16):
            ds.query("t", f"bbox(geom, {-80 + i}, -60, {80 - i}, 60)")
        assert ds.cache.result.bytes_resident <= conf.max_bytes
        assert reg.counters["geomesa.cache.eviction"] >= 1

    def test_schema_drop_clears_entries(self):
        ds = _store()
        ds.query("t", Q)
        assert len(ds.cache.result) == 1
        ds.delete_schema("t")
        assert len(ds.cache.result) == 0

    def test_cache_disabled_by_zero_budget(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg, cache=CacheConfig(max_bytes=0))
        ds.query("t", Q)
        ds.query("t", Q)
        assert reg.counters["geomesa.cache.hit"] == 0

    def test_cache_on_vs_off_byte_identical(self):
        cached = _store(cache=True)
        plain = _store(cache=False)
        assert plain.cache is None
        for q in (Q, "name = 'n2'",
                  "bbox(geom, 0, 0, 90, 45) AND name = 'n3'"):
            for _ in range(2):  # second pass serves from cache
                _same_rows(cached.query("t", q), plain.query("t", q))


# -- single-flight (satellite: concurrency test) ---------------------------

class TestSingleFlight:
    def test_concurrent_identical_queries_share_one_scan(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        n_threads = 8
        scans = []
        orig = ds.planner._execute

        def counting_execute(plan, explain=None, hints=None, **kw):
            scans.append(1)
            time.sleep(0.15)  # hold the flight open so waiters pile up
            return orig(plan, explain, hints, **kw)

        ds.planner._execute = counting_execute
        barrier = threading.Barrier(n_threads)
        results, errors = [None] * n_threads, []

        def worker(i):
            try:
                barrier.wait()
                results[i] = ds.query("t", Q)
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert not errors
        assert len(scans) == 1, f"expected 1 underlying scan, got {len(scans)}"
        assert reg.counters["geomesa.cache.miss"] == 1
        assert reg.counters["geomesa.cache.stampede.coalesced"] >= 1
        # every thread was served: one scanned, the rest coalesced onto
        # its flight (or hit the freshly admitted entry if they lost the
        # race to the flight window)
        assert (reg.counters["geomesa.cache.stampede.coalesced"]
                + reg.counters["geomesa.cache.hit"]) == n_threads - 1
        for r in results[1:]:
            _same_rows(results[0], r)

    def test_waiter_recomputes_when_write_lands_mid_flight(self):
        """A mutation during the leader's scan must not let waiters adopt
        the pre-write snapshot."""
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        sft = ds.get_schema("t")
        orig = ds.planner._execute
        started = threading.Event()  # leader is inside its scan

        def slow_execute(plan, explain=None, hints=None, **kw):
            first = not started.is_set()
            started.set()
            out = orig(plan, explain, hints, **kw)
            if first:
                # a mutation lands AFTER the leader's snapshot but before
                # its flight completes
                ds.write("t", FeatureCollection.from_columns(
                    sft, ["mid0"],
                    {"name": np.array(["z"], dtype=object),
                     "dtg": np.full(1, int(T0)),
                     "geom": (np.array([5.0]), np.array([5.0]))},
                ), check_ids=False)
                time.sleep(0.08)  # hold the flight so the waiter joins it
            return out

        ds.planner._execute = slow_execute
        out = {}

        def leader():
            out["leader"] = ds.query("t", Q)

        def waiter():
            started.wait(timeout=5)
            out["waiter"] = ds.query("t", Q)

        t1 = threading.Thread(target=leader)
        t2 = threading.Thread(target=waiter)
        t1.start(); t2.start(); t1.join(); t2.join()
        # the waiter must see the mid-flight write (the leader's snapshot
        # predates it) — generation validation forces its own scan
        assert len(out["waiter"]) == len(out["leader"]) + 1
        assert reg.counters["geomesa.cache.stampede.coalesced"] == 0


class TestScanConfigMemo:
    def test_memo_dropped_on_write(self):
        """The planner's scan-config memo may not outlive a write: z3
        time bins clamp to the data's bin_range, which GROWS with writes
        — a stale memo entry would silently exclude the new bins (even
        on bypass queries; the memo sits under the result cache)."""
        sft = FeatureType.from_spec("t", SPEC)
        sft.user_data["geomesa.indices.enabled"] = "z3"
        ds = DataStore(cache=True)
        ds.create_schema(sft)

        def batch(ids, t):
            n = len(ids)
            return FeatureCollection.from_columns(
                sft, ids,
                {"name": np.array(["a"] * n, dtype=object),
                 "dtg": np.full(n, int(t)),
                 "geom": (np.zeros(n), np.zeros(n))})

        ds.write("t", batch(["a0"], T0), check_ids=False)
        q = ("bbox(geom, -1, -1, 1, 1) AND dtg DURING "
             "2024-01-01T00:00:00Z/2024-03-01T00:00:00Z")
        bypass = QueryHints(cache="bypass")
        assert len(ds.query("t", q, hints=bypass)) == 1  # memoizes config
        # 40 days later: a NEW z3 time bin, beyond the clamped range the
        # memoized decomposition saw
        ds.write("t", batch(["a1"], T0 + 40 * DAY), check_ids=False)
        assert len(ds.query("t", q, hints=bypass)) == 2
        assert len(ds.query("t", q)) == 2


# -- tile-aggregate cache --------------------------------------------------

class TestTileCache:
    def test_count_composition_exact_fuzz(self):
        reg = MetricsRegistry()
        ds = _store(n=4000, metrics=reg)
        plain = _store(n=4000, cache=False)
        rng = np.random.default_rng(7)
        for _ in range(12):
            x0 = float(rng.uniform(-170, 100))
            y0 = float(rng.uniform(-80, 40))
            w = float(rng.uniform(15, 70))
            q = f"bbox(geom, {x0}, {y0}, {x0 + w}, {y0 + w / 2})"
            assert ds.count("t", q) == len(plain.query("t", q)), q
        assert reg.counters.get("geomesa.cache.tile.reused", 0) > 0

    def test_bounds_composition_exact(self):
        ds = _store(n=4000)
        plain = _store(n=4000, cache=False)
        q = "bbox(geom, -60, -40, 60, 40)"
        got = ds.bounds("t", q)
        rows = plain.query("t", q)
        x, y = rows.representative_xy()
        want = (float(np.min(x)), float(np.min(y)),
                float(np.max(x)), float(np.max(y)))
        assert got == pytest.approx(want, abs=0)

    def test_tile_edge_rows_never_double_count(self):
        """Points exactly ON tile edges and query edges: half-open tile
        membership + closed query semantics must still compose exactly."""
        sft = FeatureType.from_spec("t", SPEC)
        sft.user_data["geomesa.indices.enabled"] = "z2"
        conf = CacheConfig(tile_bits=4)  # 22.5 x 11.25 degree tiles
        ds = DataStore(cache=conf)
        ds.create_schema(sft)
        step_x, step_y = 360.0 / 16, 180.0 / 16
        # a lattice of points sitting exactly on tile corners
        gx = -180.0 + np.arange(1, 15) * step_x
        gy = -90.0 + np.arange(1, 15) * step_y
        xx, yy = np.meshgrid(gx, gy)
        x, y = xx.ravel(), yy.ravel()
        n = len(x)
        ds.write("t", FeatureCollection.from_columns(
            sft, [f"e{i}" for i in range(n)],
            {"name": np.array(["e"] * n, dtype=object),
             "dtg": np.full(n, int(T0)), "geom": (x, y)},
        ), check_ids=False)
        plain = DataStore()
        plain.create_schema(FeatureType.from_spec("t", SPEC))
        plain.write("t", FeatureCollection.from_columns(
            plain.get_schema("t"), [f"e{i}" for i in range(n)],
            {"name": np.array(["e"] * n, dtype=object),
             "dtg": np.full(n, int(T0)), "geom": (x, y)},
        ), check_ids=False)
        # query boxes whose edges land exactly on tile edges, twice (the
        # second pass composes from cached tiles)
        for x0, y0, x1, y1 in (
            (-180.0 + step_x, -90.0 + step_y, step_x * 3, step_y * 2),
            (-step_x * 2, -step_y * 2, step_x * 2, step_y * 2),
            (0.0, 0.0, step_x * 4, step_y * 3),
        ):
            q = f"bbox(geom, {x0}, {y0}, {x1}, {y1})"
            want = len(plain.query("t", q))
            assert ds.count("t", q) == want, q
            assert ds.count("t", q) == want, q

    def test_shifted_bbox_reuses_interior(self):
        reg = MetricsRegistry()
        ds = _store(n=4000, metrics=reg)
        ds.count("t", "bbox(geom, -60, -40, 60, 40)")
        filled = reg.counters["geomesa.cache.tile.filled"]
        reused0 = reg.counters.get("geomesa.cache.tile.reused", 0)
        assert filled > 0
        # a 10%-shifted dashboard pan: most interior tiles come from cache
        ds.count("t", "bbox(geom, -48, -36, 72, 44)")
        assert reg.counters["geomesa.cache.tile.reused"] > reused0

    def test_write_invalidates_overlapping_tiles(self):
        ds = _store(n=4000)
        plain = _store(n=4000, cache=False)
        q = "bbox(geom, -60, -40, 60, 40)"
        assert ds.count("t", q) == len(plain.query("t", q))
        sft = ds.get_schema("t")
        batch = FeatureCollection.from_columns(
            sft, ["w0", "w1", "w2"],
            {"name": np.array(["w"] * 3, dtype=object),
             "dtg": np.full(3, int(T0)),
             "geom": (np.array([0.0, 1.0, 2.0]), np.array([0.0, 1.0, 2.0]))},
        )
        ds.write("t", batch, check_ids=False)
        plain.write("t", batch, check_ids=False)
        assert ds.count("t", q) == len(plain.query("t", q))

    def test_adaptive_cost_gate(self):
        """Composition that measures slower than the plain scan it
        replaces gates itself off — and re-probes periodically, reopening
        when the balance shifts back."""
        reg = MetricsRegistry()
        ds = _store(n=4000, metrics=reg)
        tc = ds.cache.tiles
        for _ in range(6):  # losing compositions vs a 10ms plain scan
            tc._note_compose("t", 0.050)
        tc.note_scan("t", 0.010)
        opened = [tc.worth_composing("t") for _ in range(16)]
        assert opened.count(False) >= 10          # mostly gated
        assert opened[1:].count(True) >= 1        # but re-probes
        assert reg.counters["geomesa.cache.tile.gated"] >= 10
        for _ in range(12):  # cheap composes reopen the gate for good
            tc._note_compose("t", 0.001)
        assert all(tc.worth_composing("t") for _ in range(8))
        # a composition's own union scan is not a plain-scan sample
        tc._scanning.active = True
        tc.note_scan("t", 99.0)
        tc._scanning.active = False
        assert tc._scan_s["t"] < 1.0

    def test_compose_duration_not_a_scan_sample(self):
        """A composition-served stats_query/bounds must not feed the
        adaptive gate's plain-scan baseline with its own duration (the
        gate would then compare composing against itself and never
        trip); the composition's inner union scan is excluded too."""
        ds = _store(n=2000)
        tc = ds.cache.tiles
        out = ds.stats_query("t", "Count()", "bbox(geom, -60, -40, 60, 40)")
        assert out[0].count > 0
        assert "t" not in tc._scan_s
        # a real row query IS a baseline sample
        ds.query("t", "bbox(geom, -60, -40, 60, 40)")
        assert "t" in tc._scan_s

    def test_tile_cache_disabled_for_visibility(self):
        """Row-level visibility changes per-row membership: the tile tier
        must decline, falling back to the (auth-fingerprinted) row path."""
        sft = FeatureType.from_spec(
            "t", "name:String,vis:String,dtg:Date,*geom:Point:srid=4326")
        sft.user_data["geomesa.vis.field"] = "vis"
        ds = DataStore(cache=True, auths=("a",))
        ds.create_schema(sft)
        n = 50
        ds.write("t", FeatureCollection.from_columns(
            sft, [f"v{i}" for i in range(n)],
            {"name": np.array(["x"] * n, dtype=object),
             "vis": np.array(["a" if i % 2 else "b" for i in range(n)],
                             dtype=object),
             "dtg": np.full(n, int(T0)),
             "geom": (np.linspace(-50, 50, n), np.linspace(-40, 40, n))},
        ), check_ids=False)
        assert ds._tile_compose("t", ecql.parse("bbox(geom, -60, -60, 60, 60)")) is None


# -- explain + metrics (satellite: attributable probe time) ----------------

class TestExplainAndMetrics:
    def test_explain_reports_status_and_probe_time(self):
        ds = _store()
        exp = Explainer()
        ds.query("t", Q, explain=exp)
        [line] = [l for l in exp.lines if l.strip().startswith("cache:")]
        assert "miss" in line and "probe" in line and "ms" in line
        exp = Explainer()
        ds.query("t", Q, explain=exp)
        [line] = [l for l in exp.lines if l.strip().startswith("cache:")]
        assert "hit" in line

    def test_probe_time_separate_from_scan_time(self):
        reg = MetricsRegistry()
        ds = _store(metrics=reg)
        ds.query("t", Q)
        ds.query("t", Q)
        probe = reg.timers["geomesa.query.cache_probe"]
        scan = reg.histograms["geomesa.query.scan"]
        assert probe.count == 2 and scan.count == 2
        # the probe is cache machinery only — it can never exceed the
        # whole execute the scan histogram covers
        assert probe.total_s <= scan.sum_s

    def test_plan_carries_cache_outcome(self):
        ds = _store()
        plan = ds.planner.plan("t", Q)
        ds.planner.execute(plan)
        assert plan.cache_status == "miss"
        assert plan.cache_probe_s >= 0.0
        plan2 = ds.planner.plan("t", Q)
        ds.planner.execute(plan2)
        assert plan2.cache_status == "hit"

    def test_tile_explain_reports_partial_then_hit(self):
        ds = _store(n=4000)
        exp = Explainer()
        ds.stats_query("t", "Count()", f="bbox(geom, -60, -40, 60, 40)",
                       explain=exp)
        [line] = [l for l in exp.lines if l.strip().startswith("cache:")]
        assert "tiles reused" in line
        exp = Explainer()
        ds.stats_query("t", "Count()", f="bbox(geom, -60, -40, 60, 40)",
                       explain=exp)
        [line] = [l for l in exp.lines if l.strip().startswith("cache:")]
        assert line.strip().startswith("cache: hit")

    def test_bad_cache_hint_rejected(self):
        with pytest.raises(ValueError):
            QueryHints(cache="nope").validate()


# -- streaming interplay ---------------------------------------------------

class TestStreamingInterplay:
    def test_lambda_hot_mutations_bump_generations(self):
        from geomesa_tpu.streaming import LambdaStore

        ds = _store(n=200)
        lam = LambdaStore(ds, "t", expiry_ms=10_000)
        assert lam.hot.generations is ds.cache.generations
        t0 = ds.cache.generations.tick()
        lam.write([{"name": "h", "dtg": int(T0), "geom": "POINT(1 1)"}],
                  ids=["h0"])
        assert ds.cache.generations.tick() > t0
        t1 = ds.cache.generations.tick()
        lam.hot.delete(["h0"])
        assert ds.cache.generations.tick() > t1

    def test_lambda_expiry_bumps(self):
        from geomesa_tpu.streaming import LambdaStore

        ds = _store(n=200)
        lam = LambdaStore(ds, "t", expiry_ms=1)
        lam.write([{"name": "h", "dtg": int(T0), "geom": "POINT(1 1)"}],
                  ids=["h0"])
        t0 = ds.cache.generations.tick()
        assert lam.hot.expire(now_ms=int(time.time() * 1000) + 10_000) == 1
        assert ds.cache.generations.tick() > t0

    def test_flush_invalidates_cold_cached_results(self):
        from geomesa_tpu.streaming import LambdaStore

        ds = _store(n=200)
        n0 = len(ds.query("t", Q))  # populate the cold result cache
        lam = LambdaStore(ds, "t")
        lam.write([{"name": "h", "dtg": int(T0), "geom": "POINT(5 5)"}],
                  ids=["hot0"])
        lam.persist_hot()
        assert len(ds.query("t", Q)) == n0 + 1
