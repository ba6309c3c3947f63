"""The dynamic lock witness (docs/concurrency.md): prove the static
lock model against reality, tier-1.

Layers:

- **the runtime has teeth**: wrapper passthrough when disarmed, edge
  recording / re-entrancy / aliasing semantics, Condition wait frame
  handling, blocking events via fault points, and the deterministic
  two-thread A->B / B->A inversion whose cycle the witness must report;
- **model vs reality, both directions**: a workload across every
  concurrent tier (DataStore writes + cached queries, BulkLoader
  ingest, LambdaStore + WAL + flush/fold + checkpoint, the serving
  scheduler, a chaos schedule) under an armed witness must (a) witness
  EVERY LOCKS-registry lock, (b) observe an acyclic acquisition graph
  that is (c) a subgraph of the static model's predicted edges, and
  (d) never reach a fault point while a HOT lock is held — the runtime
  twin of blocking-under-lock, pinning the WAL _rotate/close fix;
- **overhead**: the witnessed workload stays within 1.5x of the
  unwitnessed wall time (disarmed it is zero-cost by construction).

The observed graph is ALWAYS dumped to the
``geomesa.tpu.lock.witness.artifact`` path (default
``/tmp/lock_witness.json``) so CI failures are diagnosable from logs.
"""

import os
import threading
import time

import numpy as np
import pytest

from geomesa_tpu import fault, lockwitness
from geomesa_tpu import geometry as geo
from geomesa_tpu.analysis.core import Project
from geomesa_tpu.analysis.lockmodel import LOCKS, LockModel
from geomesa_tpu.cache import CacheConfig
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage import persist
from geomesa_tpu.streaming import (
    LambdaStore,
    PipeTransport,
    ReplicaStore,
    SegmentShipper,
    StreamConfig,
    WalConfig,
)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000


@pytest.fixture(autouse=True)
def _disarm():
    """Every test leaves the witness disarmed and the injector clean
    (objects built while armed keep their wrappers — they only feed the
    report, which the next enable() resets)."""
    yield
    lockwitness.disable()
    fault.injector().reset()


def _rows(n, seed=0, prefix="r"):
    rng = np.random.default_rng(seed)
    xs = rng.uniform(-50, 50, n)
    ys = rng.uniform(-50, 50, n)
    ts = T0 + rng.integers(0, 30 * DAY, n)
    return [
        {
            "__id__": f"{prefix}{i}",
            "name": "n",
            "dtg": np.datetime64(int(ts[i]), "ms"),
            "geom": f"POINT ({xs[i]:.6f} {ys[i]:.6f})",
        }
        for i in range(n)
    ]


def _fc(sft, n, seed=0, prefix="c"):
    rng = np.random.default_rng(seed)
    return FeatureCollection.from_columns(
        sft, [f"{prefix}{i}" for i in range(n)],
        {"name": np.array(["n"] * n),
         "dtg": T0 + rng.integers(0, 30 * DAY, n),
         "geom": (rng.uniform(-50, 50, n), rng.uniform(-50, 50, n))},
    )


# -- layer 1: the runtime has teeth ---------------------------------------


def test_disarmed_witness_is_passthrough():
    lockwitness.disable()
    lock = threading.Lock()
    assert lockwitness.witness(lock, "X._lock") is lock
    cond = threading.Condition()
    assert lockwitness.witness(cond, "X._cond") is cond


def test_edges_reentrancy_and_aliasing():
    lockwitness.enable()
    a = lockwitness.witness(threading.Lock(), "Fix._a")
    b = lockwitness.witness(threading.RLock(), "Fix._b")
    b2 = lockwitness.witness(threading.RLock(), "Fix._b")
    with a:
        assert lockwitness.held_locks() == ("Fix._a",)
        with b:
            with b:  # re-entrant same instance: NOT an edge, not aliased
                pass
            with b2:  # distinct instance, same name: aliased, not an edge
                pass
    assert lockwitness.held_locks() == ()
    snap = lockwitness.REPORT.snapshot()
    assert ("Fix._a", "Fix._b") in lockwitness.REPORT.edges
    assert ("Fix._b", "Fix._b") not in lockwitness.REPORT.edges
    assert snap["aliased"] == {"Fix._b ~ Fix._b": 1}
    assert {"Fix._a", "Fix._b"} <= set(snap["seen"])
    assert lockwitness.REPORT.cycle() is None


def test_two_thread_inversion_reports_cycle(tmp_path):
    """The deterministic A->B / B->A inversion: thread one nests A->B,
    thread two (strictly after) nests B->A; the witness must report the
    cycle even though the interleaving never actually deadlocked."""
    lockwitness.enable()
    a = lockwitness.witness(threading.Lock(), "Inv._a")
    b = lockwitness.witness(threading.Lock(), "Inv._b")
    first_done = threading.Event()

    def t1():
        with a:
            with b:
                pass
        first_done.set()

    def t2():
        first_done.wait()
        with b:
            with a:
                pass

    threads = [threading.Thread(target=t) for t in (t1, t2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cyc = lockwitness.REPORT.cycle()
    assert cyc is not None
    assert set(cyc) == {"Inv._a", "Inv._b"}
    # the artifact records the cycle for CI forensics
    out = lockwitness.dump(str(tmp_path / "w.json"))
    import json

    payload = json.load(open(out))
    assert payload["cycle"] is not None
    assert "Inv._a -> Inv._b" in payload["edge_counts"]


def test_condition_wait_releases_held_frame():
    lockwitness.enable()
    cond = lockwitness.witness(threading.Condition(), "Fix._cond")
    seen_during_wait = []

    def waker():
        with cond:
            cond.notify_all()

    with cond:
        assert lockwitness.held_locks() == ("Fix._cond",)
        t = threading.Thread(target=waker)
        # wait() pops the held frame (the lock is released) and
        # re-pushes it on wake; a timeout-less wait would hang here
        # without the waker
        t.start()
        cond.wait(timeout=5.0)
        seen_during_wait.append(lockwitness.held_locks())
        t.join()
    assert seen_during_wait == [("Fix._cond",)]
    assert lockwitness.held_locks() == ()


def test_fault_points_record_blocking_events():
    lockwitness.enable()
    lock = lockwitness.witness(threading.Lock(), "Fix._hot")
    fault.fault_point("persist.gc")  # no lock held: not an event
    with lock:
        fault.fault_point("persist.gc")
    blocking = lockwitness.REPORT.snapshot()["blocking"]
    assert blocking == {"Fix._hot @ persist.gc": 1}


# -- layer 2: model vs reality, both directions ---------------------------


def _workload(tmp_path, metrics=None):
    """One pass over every concurrent tier; returns nothing — the point
    is which locks it crosses (construction happens INSIDE, so an armed
    witness wraps everything). The observability tier runs armed too:
    a fresh Tracer (sampling every root) and an attached SLO tracker,
    so Tracer._lock and SloTracker._lock are witnessed under the same
    concurrent serving load as the store locks."""
    from geomesa_tpu import conf, obs
    from geomesa_tpu.ingest import BulkLoader, PipelineConfig
    from geomesa_tpu.metrics import MetricsRegistry

    conf.OBS_TRACE_SAMPLE.set(1)
    obs.install(obs.Tracer())  # constructed armed: its lock is wrapped
    ds = DataStore(cache=CacheConfig(max_bytes=1 << 22, tile_bits=4))
    # a store-level registry (constructed under the armed witness):
    # without one, record_query skips the tile tier's cost gate and
    # TileAggregateCache._lock would never be crossed
    ds.metrics = metrics if metrics is not None else MetricsRegistry()
    ds.attach_slo()  # SLO windows fed through the registry observer hook
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    ds.write("t", _fc(sft, 200, seed=0))
    ds.compact("t")
    # cached read path: miss then hit (ResultCache + generations), and
    # record_query feeds the tile tier's cost gate
    for _ in range(2):
        ds.query("t", "BBOX(geom, -20, -20, 20, 20)")
    # pipelined ingest (BulkLoader._cv / _stage_lock)
    loader = BulkLoader(ds, "t", config=PipelineConfig(workers=2))
    loader.put(_fc(sft, 64, seed=1, prefix="b"))
    loader.close()
    # serving tier: admitted queries cross the scheduler condition;
    # the ops plane mounts alongside (constructed armed) and scrapes
    # /metrics + /health + /debug/vars WHILE a query is in flight, so
    # TelemetryRecorder._lock is witnessed under concurrent
    # scrape+serve (EstimateAccuracy._lock is crossed by every query's
    # record path — the store has sketches from the write above)
    import urllib.request

    sched = ds.serve()
    srv = ds.serve_ops()
    # data plane (docs/serving.md "The data plane"), mounted on the
    # same scheduler: tenant-tagged HTTP query + ingest traffic crosses
    # TenantRegistry._lock under concurrent handler threads, alongside
    # the scheduler condition and the store write lock
    dsv = ds.serve(port=0)
    try:
        fut = sched.submit("t", "BBOX(geom, -10, -10, 10, 10)")
        srv.recorder.sample()
        for path in ("/metrics", "/health", "/debug/vars?window=60"):
            urllib.request.urlopen(srv.url + path, timeout=10).read()
        fut.result(30)
        from geomesa_tpu.serving import DataClient

        dsv.tenants.configure("wl", queue_max=8)
        client = DataClient(dsv.url, tenant="wl")
        client.query("t", cql="BBOX(geom, -10, -10, 10, 10)")
        # tile pyramid (docs/tiles.md): a leaf fetch crosses
        # TilePyramid._lock on the scan-EWMA path; the ingest below
        # then crosses it AGAIN under the store write lock (the
        # declared DataStore._write_lock -> TilePyramid._lock edge,
        # via on_mutation -> note_delta). One LEAF tile: a single scan.
        client.tile("t", "density", 3, 0, 0)
        client.ingest("t", {"type": "FeatureCollection", "features": [{
            "type": "Feature", "id": "wl-ingest-1",
            "geometry": {"type": "Point", "coordinates": [0.5, 0.5]},
            "properties": {"name": "wl", "dtg": 1704067200000},
        }]})
        client.tenants()
    finally:
        dsv.close()
        srv.close()
    # multi-host pod tier (docs/distributed.md), constructed armed:
    # id-less writes cross PodStore._route_lock on the auto-id counter
    # before the pod.wal.route hop fans the batch out to its owning hosts
    from geomesa_tpu.pod import PodStore, make_host_group

    pg = make_host_group(hosts=2, devices_per_host=1, driver="sim")
    pod = PodStore(FeatureType.from_spec("p", SPEC), pg)
    try:
        pod.write([
            {"name": "p", "dtg": np.datetime64(T0, "ms"),
             "geom": geo.Point(float(i), float(i))}
            for i in range(8)
        ])
        pod.query()
        pod.count()
    finally:
        pod.close()
    # streaming tier over a durably saved cold store, WAL attached,
    # tiny segments so rotation happens (the fixed seal-fsync path),
    # chaos armed at rate=0 so every stream.* fault point consults the
    # schedule (ChaosSpec._lock) without firing anything
    root = tmp_path / "w"
    persist.save(ds, root)
    lam = LambdaStore(
        ds, "t",
        config=StreamConfig(chunk_rows=64, fold_rows=8, workers=2),
        wal_dir=str(root / "_wal"),
        wal_config=WalConfig(sync="always", segment_bytes=4 << 10),
    )
    try:
        with fault.chaos(
            seed=3, rate=0.0,
            points="stream.*,streaming.*,standing.*,replica.*",
        ):
            # standing tier (docs/standing.md), constructed armed: the
            # subscription index, a continuous window and the alert
            # queue all cross their locks on every write below
            from geomesa_tpu.streaming.standing import (
                Subscription, WindowSpec,
            )

            lam.subscribe(Subscription("w", "geofence", geom=geo.Polygon(
                [(-30, -30), (30, -30), (30, 30), (-30, 30), (-30, -30)]
            )))
            # a non-rectangular geofence so matching crosses the host
            # ray cast and the _MatchGate cost EWMAs (the rect above
            # takes the box fast path, which touches neither)
            lam.subscribe(Subscription("t", "geofence", geom=geo.Polygon(
                [(-30, -30), (30, -30), (0.0, 30), (-30, -30)]
            )))
            lam.standing().add_window("m", WindowSpec(size_ms=60_000))
            lam.write(_rows(150, seed=2))
            lam.flush()
            lam.write(_rows(150, seed=3))          # updates: fold path
            lam.delete([f"r{i}" for i in range(10)])  # hot-lock WAL hook
            lam.flush()
            lam.query("BBOX(geom, -30, -30, 30, 30)")
            lam.standing().alerts.drain()
            # replication tier (docs/replication.md), constructed
            # armed: the shipper's bookkeeping lock crosses on
            # attach/pump, the follower's watermark lock on every
            # applied record
            end_a, end_b = PipeTransport.pair()
            ship = SegmentShipper(lam, chunk_bytes=4096)
            fid = ship.attach(end_a)
            fol = ReplicaStore(
                str(root), str(tmp_path / "fw" / "_wal"), end_b,
                type_name="t",
                config=StreamConfig(chunk_rows=64, fold_rows=4096),
            )
            try:
                ship.pump()
                fol.drain()
                fol.staleness_ms()
            finally:
                ship.detach(fid)
                fol.close()
            lam.checkpoint(str(root))
    finally:
        lam.close()
        sched.close()
        conf.OBS_TRACE_SAMPLE.clear()
        obs.install(obs.Tracer())  # drop the witness-wrapped tracer


def test_every_registry_lock_witnessed_graph_acyclic_and_subgraph(tmp_path):
    """THE model-vs-reality gate (docs/concurrency.md): drive the
    workload under an armed witness, then check both directions —
    every LOCKS lock witnessed, the observed graph acyclic and inside
    the static prediction, no fault point under a hot lock. The
    observed graph is dumped to the artifact path either way."""
    lockwitness.enable()
    try:
        _workload(tmp_path)
    finally:
        lockwitness.disable()
    report = lockwitness.REPORT
    artifact = lockwitness.dump()  # the CI artifact, pass or fail
    snap = report.snapshot()

    # (a) every registry lock actually witnessed — a LOCKS entry the
    # workload cannot reach is as suspect as an unregistered lock
    missing = set(LOCKS) - set(snap["seen"])
    assert not missing, (
        f"registry locks never witnessed: {sorted(missing)} "
        f"(see {artifact})"
    )

    # (b) observed acquisition order is acyclic
    assert report.cycle() is None, (
        f"observed lock-order cycle {report.cycle()} (see {artifact})"
    )

    # (c) observed edges are a subgraph of the static model's predicted
    # edges (AST-derived + declared callback edges)
    model = LockModel.of(Project.load(ROOT))
    predicted = model.predicted_edges()
    surprise = [
        e for e in report.edges
        if e not in predicted and e[0] != e[1]
    ]
    assert not surprise, (
        f"observed edges missing from the static model: {surprise} "
        f"(see {artifact}) — resolve them in lockmodel (derived or "
        "DECLARED_EDGES) so the model stays truthful"
    )

    # (d) no fault point (IO/latency step) fired while a HOT lock was
    # held — the runtime twin of blocking-under-lock, pinning the WAL
    # _rotate/close seal-fsync fix. DECLARED_BLOCKING pairs (the
    # apply-then-record delete hook) are the registry's accepted,
    # justified exceptions.
    import fnmatch

    from geomesa_tpu.analysis.lockmodel import DECLARED_BLOCKING

    def declared(lock, point):
        return any(
            lock == dl and fnmatch.fnmatch(point, pat)
            for dl, pat, _why in DECLARED_BLOCKING
        )

    hot_blocking = {
        k: n for k, n in snap["blocking"].items()
        if model.is_hot(k.split(" @ ")[0])
        and not declared(*k.split(" @ "))
    }
    assert not hot_blocking, (
        f"fault points reached under hot locks: {hot_blocking} "
        f"(see {artifact})"
    )

    # the load-bearing nesting was actually observed, not vacuously
    assert ("WriteAheadLog._sync_lock", "WriteAheadLog._lock") in report.edges
    assert (
        "StreamingFeatureCache._lock", "WriteAheadLog._lock"
    ) in report.edges, "the delete hook's WAL append was not observed"
    assert os.path.exists(artifact)


def test_wal_rotation_seals_outside_append_lock(tmp_path):
    """Regression pin for the blocking-under-lock fix: with the witness
    armed and tiny segments, rotations happen during sustained appends
    and the stream.wal.rotate fault point must fire under the SYNC lock
    only — never while the hot append lock is held — while recovery
    still sees every acknowledged row."""
    lockwitness.enable()
    try:
        ds = DataStore()
        sft = FeatureType.from_spec("t", SPEC)
        ds.create_schema(sft)
        root = tmp_path / "s"
        persist.save(ds, root)
        lam = LambdaStore(
            ds, "t", config=StreamConfig(chunk_rows=64),
            wal_dir=str(root / "_wal"),
            wal_config=WalConfig(sync="always", segment_bytes=2 << 10),
        )
        lam.write(_rows(200, seed=5))
        assert lam.wal.metrics.counter_value(
            "geomesa.stream.wal.rotations"
        ) >= 1, "workload never rotated — shrink segment_bytes"
        lam.checkpoint(str(root))
        lam.close()
    finally:
        lockwitness.disable()
    blocking = lockwitness.REPORT.snapshot()["blocking"]
    rotate_holders = {
        k for k in blocking if k.endswith("@ stream.wal.rotate")
    }
    assert all(
        k.startswith("WriteAheadLog._sync_lock") for k in rotate_holders
    ), rotate_holders
    assert not any(
        k.startswith("WriteAheadLog._lock ") for k in blocking
    ), blocking
    # durability held across the un-locked seal: recovery replays clean
    again = LambdaStore.recover(str(root))
    assert again.count() == 200
    again.close()


# -- layer 3: overhead ----------------------------------------------------


def _overhead_workload():
    """Lock-crossing-heavy but real work: hot-tier writes + flushes
    into a cold store (no WAL fsyncs — disk noise would swamp the
    measurement)."""
    ds = DataStore()
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    lam = LambdaStore(
        ds, "t", config=StreamConfig(chunk_rows=256, workers=2),
    )
    for batch in range(4):
        lam.write(_rows(1500, seed=batch, prefix=f"o{batch}_"))
        lam.flush()
    n = lam.count()
    lam.close()
    return n


def test_witness_overhead_smoke():
    """Witnessed wall time <= 1.5x unwitnessed (best-of-3 each; the
    disarmed path is passthrough so the baseline is the true cost)."""
    def measure():
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            n = _overhead_workload()
            best = min(best, time.perf_counter() - t0)
            assert n == 6000
        return best

    lockwitness.disable()
    base = measure()
    lockwitness.enable()
    try:
        witnessed = measure()
    finally:
        lockwitness.disable()
    assert witnessed <= 1.5 * base + 0.05, (
        f"witnessed {witnessed:.3f}s vs base {base:.3f}s "
        f"({witnessed / base:.2f}x, budget 1.5x)"
    )
