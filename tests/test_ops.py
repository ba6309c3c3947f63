"""The ops plane (docs/observability.md "The ops plane"): /metrics +
/health endpoints, telemetry history rings, estimate accountability.

Layers:

- **scrape correctness**: a REAL HTTP scrape of ``/metrics`` parses
  under the strict exposition mini-parser while serving load runs;
- **the health state machine**: ``/health`` flips
  healthy→degraded→unhealthy under injected faults (quarantined
  partition, WAL recovery debt, shed storm / saturated queue, hot-tier
  overrun) with exact machine-readable reasons;
- **estimate accountability**: every executed plan records estimated
  vs actual rows; a mutated-without-analyze store trips the
  "stats stale — re-analyze" reason and the auto-analyze hook clears
  it;
- **lifecycle**: the server binds/shuts down cleanly under
  ``DataStore.close()`` — no leaked thread or socket, the port
  immediately rebindable (the reuse-addr regression).
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from geomesa_tpu import conf, fault, obs
from geomesa_tpu.audit import AuditWriter
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.obs.ops import HealthMonitor, TelemetryRecorder, ops_report
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage import persist

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
Q = "BBOX(geom, -20, -20, 20, 20)"


@pytest.fixture(autouse=True)
def _fresh_obs():
    """Fresh tracer, an empty stall record (it is the process's, and
    ``/debug/trace`` exports it) + restored knobs around every test."""
    obs.install(obs.Tracer())
    obs.trace.clear_stalls()
    yield
    for knob in (conf.OBS_TRACE_SAMPLE, conf.OBS_SLOW_MS,
                 conf.OBS_SLOW_MAX, conf.PLAN_ESTIMATE,
                 conf.PLAN_ESTIMATE_STALE_P90, conf.PLAN_ESTIMATE_MIN_COUNT,
                 conf.PLAN_ESTIMATE_AUTO_ANALYZE, conf.OBS_OPS_SAMPLE_MS,
                 conf.OBS_OPS_HISTORY, conf.OBS_SLO_QUERY_P99_MS):
        knob.clear()
    obs.install(obs.Tracer())


def _fc(sft, n, seed=0, prefix="r", lo=-50.0, hi=50.0):
    rng = np.random.default_rng(seed)
    return FeatureCollection.from_columns(
        sft, [f"{prefix}{i}" for i in range(n)],
        {"name": np.array(["n"] * n),
         "dtg": T0 + rng.integers(0, 30 * DAY, n),
         "geom": (rng.uniform(lo, hi, n), rng.uniform(lo, hi, n))},
    )


def _store(n=3000, metrics=True, audit=False):
    ds = DataStore(
        metrics=MetricsRegistry() if metrics else None,
        audit=AuditWriter() if audit else None,
    )
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    if n:
        ds.write("t", _fc(sft, n))
    return ds


def _get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:  # non-2xx still carries a body
        with e:
            return e.code, e.read().decode()


def _reasons(report):
    return {r["reason"] for r in report["reasons"]}


# -- layer 1: the /metrics scrape under the strict parser ------------------


def test_metrics_scrape_parses_strict_under_serving_load():
    """A real HTTP scrape of /metrics, taken WHILE scheduler-admitted
    queries run, parses under the strict exposition mini-parser and
    carries the histogram families the doc promises."""
    from test_metrics import _parse_openmetrics

    ds = _store()
    ds.query("t", Q)  # warm the kernel variant
    sched = ds.serve()
    srv = ds.serve_ops()
    try:
        stop = threading.Event()
        errs = []

        def load():
            while not stop.is_set():
                try:
                    sched.submit("t", Q).result(30)
                except BaseException as e:  # pragma: no cover
                    errs.append(e)
                    return

        t = threading.Thread(target=load)
        t.start()
        try:
            bodies = []
            for _ in range(3):
                code, text = _get(srv.url + "/metrics")
                assert code == 200
                bodies.append(text)
        finally:
            stop.set()
            t.join()
        assert errs == []
        fams = _parse_openmetrics(bodies[-1])
        kind, _ = fams["geomesa_query_scan_seconds"]
        assert kind == "histogram"
        kind, _ = fams["geomesa_plan_estimate_error_seconds"]
        assert kind == "histogram"
        assert fams["geomesa_query_count"][0] == "counter"
        # the scrape counted itself
        assert ds.metrics.counter_value("geomesa.obs.ops.scrapes") >= 3
    finally:
        ds.close()
    assert ds.ops.closed and sched.closed


# -- layer 2: the health state machine -------------------------------------


def test_health_ready_then_quarantine_degraded_then_wal_unhealthy(tmp_path):
    """The composite verdict walks healthy→degraded→unhealthy: a clean
    store is ready; a bit-flipped partition quarantined at load is
    degraded with the exact store.quarantine reason; a WAL holding
    unreplayed mutation records flips unhealthy (HTTP 503) with
    wal.needs_recovery on top."""
    from geomesa_tpu.streaming import LambdaStore, StreamConfig, WalConfig
    from geomesa_tpu.streaming.wal import WriteAheadLog

    ds = _store(n=800)
    report = HealthMonitor(ds).evaluate()
    assert report["status"] == "ready" and report["reasons"] == []

    # degraded: save with an injected bit flip, reload -> quarantine
    root = tmp_path / "s"
    with fault.inject("persist.partition.commit", kind="bit_flip"):
        persist.save(ds, root)
    back = persist.load(root)
    assert back.store_health.status == "degraded"
    srv = back.serve_ops()
    try:
        code, body = _get(srv.url + "/health")
        assert code == 200  # degraded still serves
        report = json.loads(body)
        assert report["status"] == "degraded"
        assert _reasons(report) == {"store.quarantine"}
        [r] = report["reasons"]
        assert r["severity"] == "degraded" and "quarantined" in r["detail"]

        # unhealthy: a WAL with acknowledged-but-unreplayed records.
        # Build one by writing through a WAL'd LambdaStore and closing
        # WITHOUT a checkpoint, then reopening the log standalone (the
        # explicit wal= escape hatch the plain constructor refuses).
        wal_root = tmp_path / "w"
        clean = _store(n=0)
        persist.save(clean, wal_root)
        lam0 = LambdaStore(
            clean, "t", config=StreamConfig(chunk_rows=64),
            wal_dir=str(wal_root / "_wal"),
            wal_config=WalConfig(sync="always"),
        )
        lam0.write([{
            "__id__": "a", "name": "n",
            "dtg": np.datetime64(T0, "ms"), "geom": "POINT (1 1)",
        }])
        lam0.close()
        wal = WriteAheadLog(str(wal_root / "_wal"))
        assert wal.needs_recovery
        try:
            lam = LambdaStore(back, "t", wal=wal)
            srv.monitor.lam = lam
            code, body = _get(srv.url + "/health")
            assert code == 503  # unhealthy: stop routing
            report = json.loads(body)
            assert report["status"] == "unhealthy"
            assert _reasons(report) == {
                "store.quarantine", "wal.needs_recovery",
            }
            sev = {r["reason"]: r["severity"] for r in report["reasons"]}
            assert sev["wal.needs_recovery"] == "unhealthy"
        finally:
            wal.close()
    finally:
        back.close()


def test_health_shed_storm_and_saturated_queue():
    """The serving checks: shed-counter movement since the previous
    evaluation is degraded (scheduler.shedding); a FULL admission
    queue is unhealthy (scheduler.saturated); a half-full queue is
    degraded (scheduler.queue); draining restores ready."""
    from geomesa_tpu.serving import QueryScheduler, ServingConfig

    ds = _store(n=400)
    # an UNSTARTED scheduler stages a deterministic queue (no
    # dispatcher thread drains it)
    sched = QueryScheduler(ds, ServingConfig(queue_max=4))
    ds.scheduler = sched
    mon = HealthMonitor(ds)
    assert mon.evaluate()["status"] == "ready"

    futs = [sched.submit("t", Q) for _ in range(2)]  # half full
    report = mon.evaluate()
    assert _reasons(report) == {"scheduler.queue"}
    assert report["status"] == "degraded"
    assert report["scheduler"] == {"queue_depth": 2, "queue_max": 4}

    futs += [sched.submit("t", Q) for _ in range(2)]  # full
    # the shed storm: a full queue + block=False sheds immediately
    from geomesa_tpu.serving.scheduler import ServingRejected

    shed = sched.submit("t", Q, block=False)
    with pytest.raises(ServingRejected):
        shed.result(1)
    report = mon.evaluate()
    assert _reasons(report) == {
        "scheduler.saturated", "scheduler.shedding",
    }
    assert report["status"] == "unhealthy"

    # the shed delta was consumed; with the queue still full only the
    # saturation remains
    report = mon.evaluate()
    assert _reasons(report) == {"scheduler.saturated"}

    sched.close()  # fails the staged futures, drains the queue
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(1)
    ds.scheduler = None
    assert mon.evaluate()["status"] == "ready"


def test_health_hot_occupancy_and_standing_drops(tmp_path):
    """The streaming checks: a hot tier holding more than 2x the fold
    threshold is degraded (hot.occupancy) and clears after a flush;
    standing alert-queue drops since the previous evaluation are
    degraded (standing.drops)."""
    from geomesa_tpu.streaming import LambdaStore, StreamConfig

    ds = _store(n=0)
    lam = LambdaStore(ds, "t", config=StreamConfig(
        chunk_rows=64, fold_rows=8, workers=1,
    ))
    try:
        srv = lam.serve_ops()
        mon = srv.monitor
        assert mon.evaluate()["status"] == "ready"
        lam.write([{
            "__id__": f"h{i}", "name": "n",
            "dtg": np.datetime64(T0, "ms"),
            "geom": f"POINT ({i % 50} {i % 50})",
        } for i in range(100)])
        code, body = _get(srv.url + "/health")
        report = json.loads(body)
        assert code == 200 and report["status"] == "degraded"
        assert _reasons(report) == {"hot.occupancy"}
        assert report["hot"]["rows"] == 100 and report["hot"]["fold_rows"] == 8
        lam.flush(full=True)
        assert mon.evaluate()["status"] == "ready"
        # standing drops ride the counter-delta path
        ds.metrics.counter("geomesa.standing.dropped", 7)
        report = mon.evaluate()
        assert _reasons(report) == {"standing.drops"}
        assert "7" in report["reasons"][0]["detail"]
        assert mon.evaluate()["status"] == "ready"  # delta consumed
    finally:
        ds.close()
        lam.close()


def test_health_slo_breach_reason():
    """A breaching SLO objective surfaces as one slo.breach reason with
    the objective, quantile and burn rate in the detail."""
    conf.OBS_SLO_QUERY_P99_MS.set(0.0001)  # everything breaches
    ds = _store(n=500)
    ds.attach_slo()
    for _ in range(3):
        ds.query("t", Q)
    report = HealthMonitor(ds).evaluate()
    assert report["status"] == "degraded"
    assert _reasons(report) == {"slo.breach"}
    assert "query_p99" in report["reasons"][0]["detail"]


# -- layer 3: estimate accountability --------------------------------------


def test_estimates_recorded_on_every_scan():
    """Every executed index scan records the sketch estimate next to
    the rows actually scanned: plan fields set, explain lines present,
    the error histogram populated, the per-index accuracy reported."""
    from geomesa_tpu.planning.explain import Explainer

    ds = _store()
    exp = Explainer()
    plan = ds.planner.plan("t", Q, explain=exp)
    assert plan.estimated_rows is not None and plan.estimated_rows > 0
    out = ds.planner.execute(plan, explain=exp)
    assert plan.actual_rows is not None and plan.actual_rows >= len(out)
    lines = exp.lines
    assert any(l.startswith("Estimated rows:") for l in lines)
    assert any(l.startswith("Estimate vs actual:") for l in lines)
    snap = ds.metrics.snapshot()["histograms"]
    assert snap["geomesa.plan.estimate.error"]["count"] == 1
    rows = ds.accuracy.report()["indexes"]
    assert len(rows) == 1
    assert rows[0]["type"] == "t" and rows[0]["count"] == 1
    assert rows[0]["p90_error"] >= 1.0
    # a fresh store's estimate is honest: well under the stale bar
    assert rows[0]["worst_error"] < float(conf.PLAN_ESTIMATE_STALE_P90.get())
    # the knob disables the whole loop
    conf.PLAN_ESTIMATE.set(False)
    plan2 = ds.planner.plan("t", Q)
    assert plan2.estimated_rows is None
    ds.planner.execute(plan2)
    assert ds.accuracy.sample_count() == 1  # unchanged


def test_stale_stats_flag_health_and_manual_reanalyze():
    """The accountability loop end to end: mutate the store WITHOUT
    re-analyzing (the documented accumulate-only sketch drift), run
    queries whose estimates are now wild, and the health surface says
    'stats stale — re-analyze'; analyze_stats + reset clears it."""
    conf.PLAN_ESTIMATE_MIN_COUNT.set(8)
    ds = _store(n=2000)
    sft = ds.get_schema("t")
    # move EVERY point far away through the streaming fold path, whose
    # stats are accumulate-only (docs/streaming.md's documented drift):
    # the sketches still claim the old region is dense
    ds.fold_upsert("t", _fc(sft, 2000, seed=1, lo=100.0, hi=140.0))
    mon = HealthMonitor(ds)
    for _ in range(10):
        ds.query("t", Q)  # old region: estimate >> actual
    stale = ds.accuracy.stale()
    assert stale and stale[0][0] == "t"
    report = mon.evaluate()
    assert "stats.stale" in _reasons(report)
    detail = next(
        r["detail"] for r in report["reasons"]
        if r["reason"] == "stats.stale"
    )
    assert "stats stale" in detail and "analyze_stats" in detail
    # the operator follows the instruction: fresh sketches, reset window
    ds.analyze_stats("t")
    ds.accuracy.reset("t")
    for _ in range(10):
        ds.query("t", Q)
    assert ds.accuracy.stale() == []
    assert "stats.stale" not in _reasons(mon.evaluate())


def test_stale_stats_auto_analyze_hook():
    """With geomesa.plan.estimate.auto.analyze on, the stale trip runs
    analyze_stats itself — once (the window resets), counted by
    geomesa.plan.estimate.analyze — and estimates recover."""
    conf.PLAN_ESTIMATE_MIN_COUNT.set(8)
    conf.PLAN_ESTIMATE_AUTO_ANALYZE.set(True)
    ds = _store(n=2000)
    sft = ds.get_schema("t")
    ds.fold_upsert("t", _fc(sft, 2000, seed=1, lo=100.0, hi=140.0))
    for _ in range(12):
        ds.query("t", Q)
    assert ds.metrics.counter_value("geomesa.plan.estimate.analyze") == 1
    # post-analyze: the window restarted and the fresh sketches stay
    # accurate, so no second trip
    for _ in range(12):
        ds.query("t", Q)
    assert ds.metrics.counter_value("geomesa.plan.estimate.analyze") == 1
    assert ds.accuracy.stale() == []


def test_estimate_compares_post_refinement_not_candidates():
    """Review-pinned: the recorded 'actual' is the POST-refinement
    matched count, not the index's candidate count — a spatial-only
    index serving a spatio-temporal filter over-selects candidates by
    design, and that must not flag fresh sketches stale."""
    conf.PLAN_ESTIMATE_MIN_COUNT.set(4)
    ds = DataStore(metrics=MetricsRegistry())
    sft = FeatureType.from_spec("t", SPEC)
    sft.user_data["geomesa.indices.enabled"] = "z2"  # atemporal index
    ds.create_schema(sft)
    ds.write("t", _fc(sft, 4000))
    # one day of thirty: the z2 scan's candidates ignore time entirely
    q = (
        "BBOX(geom, -40, -40, 40, 40) AND dtg DURING "
        "2024-01-01T00:00:00Z/2024-01-02T00:00:00Z"
    )
    for _ in range(6):
        plan = ds.planner.plan("t", q)
        out = ds.planner.execute(plan)
        assert plan.index == "z2"
        assert plan.actual_rows == len(out)  # matched, not candidates
    rows = ds.accuracy.report()["indexes"]
    assert rows[0]["p90_error"] < float(conf.PLAN_ESTIMATE_STALE_P90.get())
    assert ds.accuracy.stale() == []


def test_estimate_union_with_limit_not_skewed():
    """Review-pinned: a union plan with a limit records the union's
    matched count, not the truncated result — record_query's hits
    fallback must never compare the sketch estimate against a
    post-limit row count."""
    conf.PLAN_ESTIMATE_MIN_COUNT.set(2)
    ds = DataStore(metrics=MetricsRegistry())
    sft = FeatureType.from_spec(
        "t", "name:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    ds.create_schema(sft)
    ds.write("t", _fc(sft, 4000))
    # spatial OR attribute: no single index serves both disjuncts
    q = "BBOX(geom, -40, -40, 0, 40) OR name = 'n'"
    for _ in range(3):
        plan = ds.planner.plan("t", q, limit=5)
        out = ds.planner.execute(plan)
        assert plan.union is not None and len(out) == 5
        # the union matched ~everything; the limit did not leak into
        # the recorded actual
        assert plan.actual_rows is not None and plan.actual_rows > 100
    assert ds.accuracy.stale() == []


# -- layer 4: telemetry rings + debug surfaces -----------------------------


def test_auto_analyze_claim_is_single_winner():
    """Review-pinned: the auto-analyze trip is an atomic claim — one
    winner per trip even with concurrent claimants; reset releases it
    for the next trip."""
    from geomesa_tpu.obs.accuracy import EstimateAccuracy

    acc = EstimateAccuracy()
    results = []
    barrier = threading.Barrier(8)

    def claimant():
        barrier.wait()
        results.append(acc.claim_analyze("t"))

    threads = [threading.Thread(target=claimant) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(results) == 1  # exactly one winner
    acc.reset("t")
    assert acc.claim_analyze("t")  # released for the next trip


def test_health_first_evaluation_ignores_preexisting_counters():
    """Review-pinned: a monitor constructed AFTER a shed storm must not
    report it — the baseline snapshot seeds at construction, so the
    first evaluation measures 'since this monitor existed', not
    process lifetime."""
    ds = _store(n=0)
    ds.metrics.counter("geomesa.serving.shed", 5)
    ds.metrics.counter("geomesa.standing.dropped", 3)
    mon = HealthMonitor(ds)
    report = mon.evaluate()
    assert report["status"] == "ready" and report["reasons"] == []
    # NEW movement after construction still fires
    ds.metrics.counter("geomesa.serving.shed", 1)
    assert _reasons(mon.evaluate()) == {"scheduler.shedding"}


def test_telemetry_recorder_restarts_after_stop():
    """Review-pinned: stop() then start() resumes sampling (the stop
    event clears), so a paused recorder's history does not silently
    freeze."""
    reg = MetricsRegistry()
    reg.gauge("geomesa.stream.hot_rows", 1.0)
    rec = TelemetryRecorder(reg, interval_ms=10.0, history=64)
    rec.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        if rec.series()["series"]:
            break
        time.sleep(0.01)
    rec.stop()
    n0 = len(rec.series()["series"]["geomesa.stream.hot_rows"]["v"])
    assert n0 >= 1
    rec.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        n = len(rec.series()["series"]["geomesa.stream.hot_rows"]["v"])
        if n > n0:
            break
        time.sleep(0.01)
    rec.stop()
    assert len(rec.series()["series"]["geomesa.stream.hot_rows"]["v"]) > n0


def test_telemetry_recorder_rings_window_and_bound():
    reg = MetricsRegistry()
    reg.gauge("geomesa.stream.hot_rows", 10.0)
    reg.counter("geomesa.query.count", 3)
    reg.observe("geomesa.query.scan", 0.02)
    rec = TelemetryRecorder(reg, interval_ms=1000.0, history=4)
    for k in range(8):
        reg.gauge("geomesa.stream.hot_rows", 10.0 + k)
        rec.sample(now=1000.0 + k)
    out = rec.series()
    ring = out["series"]["geomesa.stream.hot_rows"]
    assert len(ring["v"]) == 4  # bounded: oldest evicted
    assert ring["v"][-1] == 17.0
    assert out["series"]["geomesa.query.count"]["v"][-1] == 3.0
    assert "geomesa.query.scan.p99" in out["series"]
    assert out["series"]["geomesa.query.scan.p99"]["v"][-1] > 0
    # window filter keeps only recent points
    win = rec.series(window_s=2.5, now=1007.0)
    assert len(win["series"]["geomesa.stream.hot_rows"]["v"]) == 3


def test_debug_surfaces_slow_filter_audit_trace_crossref(tmp_path):
    """/debug/slow filters by type; /debug/audit rows carry the trace
    id that cross-references the slow capture and the Chrome export
    (``args.trace_id``); /stats serves the sketches; unknown paths 404."""
    conf.OBS_SLOW_MS.set(0.0001)  # everything is "slow"
    ds = _store(n=500, audit=True)
    sft2 = FeatureType.from_spec("u", SPEC)
    ds.create_schema(sft2)
    ds.write("u", _fc(sft2, 200, prefix="u"))
    ds.query("t", Q)
    ds.query("u", Q)
    srv = ds.serve_ops()
    try:
        _, body = _get(srv.url + "/debug/slow?type=u")
        only_u = json.loads(body)
        assert only_u and all(
            e["fingerprint"]["type"] == "u" for e in only_u
        )
        _, body = _get(srv.url + "/debug/slow")
        both = json.loads(body)
        assert {e["fingerprint"]["type"] for e in both} == {"t", "u"}
        # audit <-> slow <-> chrome cross-reference on one key
        _, body = _get(srv.url + "/debug/audit")
        audits = json.loads(body)
        assert len(audits) == 2
        trace_ids = {e["traceId"] for e in audits}
        assert None not in trace_ids
        slow_ids = {e["trace"]["trace_id"] for e in both}
        assert trace_ids == slow_ids
        _, body = _get(srv.url + "/debug/trace")
        chrome = json.loads(body)
        exported = {ev["args"]["trace_id"] for ev in chrome["traceEvents"]}
        assert trace_ids <= exported
        # /stats serves the sketch bundle per type
        _, body = _get(srv.url + "/stats")
        stats = json.loads(body)
        assert set(stats) == {"t", "u"}
        assert stats["t"]["count"]["count"] == 500
        # unknown path
        code, body = _get(srv.url + "/nope")
        assert code == 404 and "unknown path" in body
    finally:
        ds.close()


def test_ops_report_and_cli(tmp_path, capsys):
    """`geomesa ops` parity: the one-shot report carries health +
    slow + estimates, in text and --json."""
    from geomesa_tpu import cli

    conf.OBS_SLOW_MS.set(0.0001)
    ds = _store(n=400)
    ds.query("t", Q)
    rep = ops_report(ds, slow_n=5)
    assert rep["health"]["status"] in ("ready", "degraded")
    assert rep["slow_queries"] and rep["slow_queries"][0]["wall_ms"] > 0
    assert rep["health"]["estimates"]["indexes"]

    root = tmp_path / "cat"
    persist.save(ds, root)
    rc = cli.main(["ops", "-c", str(root), "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["health"]["status"] == "ready"
    rc = cli.main(["ops", "-c", str(root), "--slow", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "status: ready" in out
    assert "estimate accuracy" in out


@pytest.mark.parametrize("mount", ["ops", "data"])
def test_no_tuning_endpoint_on_either_mount(mount):
    """Both listeners answer from one route table, and it holds no
    ``/debug/tuning``: 404 on the ops port and on the data port."""
    from geomesa_tpu.obs.ops import OpsRoutes

    assert "/debug/tuning" not in OpsRoutes.PATHS
    ds = _store(n=0)
    srv = ds.serve_ops() if mount == "ops" else ds.serve(port=0)
    try:
        code, body = _get(srv.url + "/debug/tuning")
        assert code == 404 and "unknown path" in body
        code, _ = _get(srv.url + "/debug/stalls")
        assert code == 200  # the table itself is mounted
    finally:
        ds.close()


def test_no_tune_command_and_no_tuning_knob(tmp_path, capsys):
    """The parser refuses ``geomesa tune``, and the property tier
    declares no ``geomesa.tuning.*`` name: 90 knobs."""
    from geomesa_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(["tune", "-c", str(tmp_path)])
    assert e.value.code == 2
    assert "invalid choice: 'tune'" in capsys.readouterr().err
    assert not [n for n in conf.REGISTRY if n.startswith("geomesa.tuning.")]
    assert len(conf.REGISTRY) == 90


# -- layer 5: lifecycle (the bugfix regression) ----------------------------


def test_close_joins_threads_and_port_rebinds_immediately():
    """The DataStore.close() contract: after close, no ops/telemetry
    thread survives and the SAME port rebinds immediately (reuse-addr)
    — three open/close cycles back to back."""
    ds = _store(n=200)
    srv = ds.serve_ops()
    port = srv.port
    _get(srv.url + "/health")
    ds.close()
    assert srv.closed
    for _ in range(2):
        srv2 = ds.serve_ops(port=port)  # closed one is replaced
        assert srv2 is ds.ops and srv2.port == port
        _get(srv2.url + "/health")
        ds.close()
        assert srv2.closed
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = [
            t for t in threading.enumerate()
            if t.name in ("geomesa-ops", "geomesa-telemetry") and t.is_alive()
        ]
        if not leaked:
            break
        time.sleep(0.02)
    assert leaked == [], leaked


def test_serve_ops_idempotent_and_close_covers_scheduler():
    ds = _store(n=200)
    srv = ds.serve_ops()
    assert ds.serve_ops() is srv  # idempotent while open
    sched = ds.serve()
    ds.close()
    assert srv.closed and sched.closed
    ds.close()  # idempotent
