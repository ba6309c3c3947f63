"""Concurrent query serving (geomesa_tpu.serving): shed/deadline
semantics, backpressure, identical-fingerprint coalescing, cache-aware
admission, the adaptive window, and mixed-hints fused dispatches.

The sequential-equivalence matrix (threaded scheduler == sequential
query(), single-device and mesh4) lives in tests/test_query_many.py; the
cases here pin the scheduler's OWN behaviors, mostly on unstarted
schedulers so queue states are deterministic."""

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.planning.errors import QueryTimeout
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.planning.hints import QueryHints
from geomesa_tpu.serving import QueryScheduler, ServingConfig, ServingRejected
from geomesa_tpu.sft import FeatureType

DAY = 86400_000
Q = "bbox(geom, -10, -10, 10, 10)"


def _store(metrics=None, cache=None, n=4000):
    sft = FeatureType.from_spec(
        "ev", "kind:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    ds = DataStore(tile=64, metrics=metrics, cache=cache)
    ds.create_schema(sft)
    rng = np.random.default_rng(7)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    ds.write("ev", FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {
            "kind": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            "dtg": t0 + rng.integers(0, 20 * DAY, n),
            "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)),
        },
    ))
    return ds


@pytest.fixture(scope="module")
def ds():
    return _store(metrics=MetricsRegistry())


def test_serve_attach_surface(ds):
    s1 = ds.serve()
    assert ds.scheduler is s1
    assert ds.serve() is s1  # idempotent while open
    s1.close()
    s2 = ds.serve()
    assert s2 is not s1 and not s2.closed  # closed scheduler replaced
    s2.close()


def test_scheduler_query_equals_datastore_query(ds):
    with QueryScheduler(ds, ServingConfig()) as sched:
        out = sched.query("ev", Q)
    np.testing.assert_array_equal(
        np.asarray(out.ids), np.asarray(ds.query("ev", Q).ids)
    )


def test_shed_at_admission_when_timeout_inside_window():
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    sched = QueryScheduler(store, ServingConfig(window_ms=50.0), metrics=reg)
    sched._window_s = 0.05  # as if load grew the window to its cap
    exp = Explainer()
    fut = sched.submit("ev", Q, hints=QueryHints(timeout=0.001), explain=exp)
    with pytest.raises(QueryTimeout, match="shed before dispatch"):
        fut.result(1)
    assert reg.counters["geomesa.serving.shed"] == 1
    assert any("shed" in w for w in exp.warnings)


def test_shed_at_dispatch_when_deadline_expired_queued():
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    sched = QueryScheduler(store, ServingConfig(), metrics=reg)  # not started
    fut = sched.submit("ev", Q, hints=QueryHints(timeout=0.02))
    ok = sched.submit("ev", Q)  # no deadline: survives the stall
    time.sleep(0.08)
    sched.start()
    with pytest.raises(QueryTimeout, match="deadline expired"):
        fut.result(5)
    assert len(ok.result(5)) == len(store.query("ev", Q))
    assert reg.counters["geomesa.serving.shed"] == 1
    sched.close()


def test_queue_full_backpressure_and_shed():
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    sched = QueryScheduler(store, ServingConfig(queue_max=1), metrics=reg)
    f1 = sched.submit("ev", Q)  # fills the queue
    f2 = sched.submit("ev", "kind = 'b'", block=False)  # full -> shed
    with pytest.raises(ServingRejected):
        f2.result(1)
    assert reg.counters["geomesa.serving.shed"] == 1
    # block=True + an expired deadline while waiting for space -> shed
    f3 = sched.submit("ev", Q, hints=QueryHints(timeout=0.01))
    with pytest.raises(QueryTimeout, match="queue full"):
        f3.result(1)
    assert reg.counters["geomesa.serving.shed"] == 2
    # backpressure path: a blocking submit parks until the dispatcher
    # frees a slot, then resolves normally
    with ThreadPoolExecutor(1) as ex:
        blocked = ex.submit(sched.submit, "ev", Q)
        time.sleep(0.05)
        sched.start()
        f4 = blocked.result(5)
        assert len(f4.result(10)) == len(store.query("ev", Q))
    assert len(f1.result(10)) == len(store.query("ev", Q))
    sched.close()


def test_tenant_quota_sheds_while_the_shared_queue_is_open():
    """Admission's third reason, at the scheduler: a tenant at its own
    quota sheds (counted once) and the next tenant is still admitted.
    The other two are test_shed_at_admission_when_timeout_inside_window
    and test_queue_full_backpressure_and_shed."""
    from geomesa_tpu.serving.tenancy import TenantRegistry

    reg = MetricsRegistry()
    store = _store(metrics=reg)
    tenants = TenantRegistry(metrics=reg)
    tenants.configure("flood", queue_max=1)
    sched = QueryScheduler(
        store, ServingConfig(queue_max=8), metrics=reg, tenants=tenants
    )  # not started: what is admitted stays queued
    f1 = sched.submit("ev", Q, tenant="flood", block=False)
    f2 = sched.submit("ev", "kind = 'b'", tenant="flood", block=False)
    with pytest.raises(ServingRejected, match="tenant 'flood' admission quota full"):
        f2.result(1)
    calm = sched.submit("ev", "kind = 'c'", tenant="calm", block=False)
    assert reg.counters["geomesa.serving.shed"] == 1
    assert reg.counters["geomesa.tenant.shed"] == 1
    rows = {r["tenant"]: r for r in tenants.report()["tenants"]}
    assert rows["flood"]["shed"] == 1 and rows["calm"]["shed"] == 0
    sched.start()
    assert len(f1.result(10)) == len(store.query("ev", Q))
    assert len(calm.result(10)) == len(store.query("ev", "kind = 'c'"))
    sched.close()


def test_identical_fingerprints_coalesce_into_one_slot():
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    sched = QueryScheduler(store, ServingConfig(), metrics=reg)  # staged queue
    futs = [sched.submit("ev", Q) for _ in range(3)]
    other = sched.submit("ev", "kind = 'b'")
    sched.start()
    outs = [f.result(10) for f in futs]
    assert outs[1] is outs[0] and outs[2] is outs[0]  # ONE shared result
    np.testing.assert_array_equal(
        np.sort(np.asarray(outs[0].ids)),
        np.sort(np.asarray(store.query("ev", Q).ids)),
    )
    assert len(other.result(10)) == len(store.query("ev", "kind = 'b'"))
    assert reg.counters["geomesa.serving.coalesced"] == 2
    assert reg.counters["geomesa.serving.batches"] == 1
    assert reg.counters["geomesa.serving.batched_queries"] == 2  # leaders only
    # coalesced followers are still audited like their own queries
    assert reg.counters["geomesa.query.count"] == 4 + 2  # 4 via sched + oracle x2
    sched.close()


def test_mixed_hints_fuse_into_one_dispatch():
    """Different result-shaping hints ride ONE fused dispatch (hints
    shape post-processing, not the device scan): each caller gets the
    result sequential query() gives for its own hints."""
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    sched = QueryScheduler(store, ServingConfig(), metrics=reg)
    h1 = QueryHints(sort_by="kind")
    h2 = QueryHints(transforms=["kind"])
    f1 = sched.submit("ev", Q, hints=h1)
    f2 = sched.submit("ev", Q, hints=h2)
    f3 = sched.submit("ev", Q, limit=5)
    sched.start()
    a, b, c = f1.result(10), f2.result(10), f3.result(10)
    assert reg.counters["geomesa.serving.batches"] == 1
    assert reg.counters["geomesa.serving.batched_queries"] == 3  # no coalesce
    oa = store.query("ev", Q, hints=h1)
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(oa.ids))
    ob = store.query("ev", Q, hints=h2)
    assert list(b.columns) == list(ob.columns) == ["kind"]
    np.testing.assert_array_equal(np.asarray(b.ids), np.asarray(ob.ids))
    oc = store.query("ev", Q, limit=5)
    np.testing.assert_array_equal(np.asarray(c.ids), np.asarray(oc.ids))
    sched.close()


def test_cache_hits_never_queue():
    reg = MetricsRegistry()
    store = _store(metrics=reg, cache=True)
    sched = store.serve()
    first = sched.query("ev", Q)  # miss: fused dispatch + cache populate
    batches = reg.counters["geomesa.serving.batches"]
    assert batches >= 1
    h0 = reg.counters.get("geomesa.cache.hit", 0)
    second = sched.query("ev", Q)  # admission peek -> served in-caller
    assert reg.counters["geomesa.serving.batches"] == batches  # no dispatch
    assert reg.counters["geomesa.cache.hit"] == h0 + 1
    np.testing.assert_array_equal(
        np.asarray(first.ids), np.asarray(second.ids)
    )
    # bypass skips both the admission peek and the populate
    third = sched.query("ev", Q, hints=QueryHints(cache="bypass"))
    assert reg.counters["geomesa.serving.batches"] == batches + 1
    np.testing.assert_array_equal(np.asarray(first.ids), np.asarray(third.ids))
    sched.close()


def test_scheduled_miss_populates_result_cache():
    store = _store(metrics=MetricsRegistry(), cache=True)
    sched = store.serve()
    sched.query("ev", Q)
    assert len(store.cache.result) == 1  # admitted by the dispatch path
    # ... and a later PLAIN query() serves from it
    plan = store.planner.plan("ev", Q)
    out = store.planner.execute(plan)
    assert plan.cache_status == "hit"
    assert len(out) == len(store.query("ev", Q, hints=QueryHints(cache="bypass")))
    sched.close()


def test_adaptive_window_grows_and_shrinks():
    store = _store(metrics=MetricsRegistry())
    sched = QueryScheduler(store, ServingConfig(window_ms=4.0))
    assert sched.window_s == 0.0  # idle start: lone queries pay nothing
    sched._adapt(8)
    assert sched.window_s == pytest.approx(0.0005)  # cap/8 seed
    for _ in range(10):
        sched._adapt(8)
    assert sched.window_s == pytest.approx(0.004)  # grows to the cap
    sched._adapt(1)
    assert sched.window_s == pytest.approx(0.002)  # halves when singular
    for _ in range(10):
        sched._adapt(1)
    assert sched.window_s == 0.0  # collapses back to zero when idle


def test_partial_config_resolves_unset_knobs_from_properties():
    """ServingConfig(window_ms=...) must still honor the property tier
    (env/set overrides) for the knobs it does NOT name."""
    from geomesa_tpu import conf

    conf.SERVING_QUEUE_MAX.set(7)
    try:
        c = ServingConfig(window_ms=5.0)
        assert c.window_ms == 5.0
        assert c.queue_max == 7
        assert c.batch_max == conf.SERVING_BATCH_MAX.get()
    finally:
        conf.SERVING_QUEUE_MAX.clear()
    assert ServingConfig().queue_max == conf.SERVING_QUEUE_MAX.get()


def test_admission_anchored_deadlines_in_submit_many():
    """submit_many's ``deadlines`` anchor a scan's budget at admission:
    a budget already burned in the queue times the scan out, instead of
    restarting the clock at finish()."""
    import time as _t

    from geomesa_tpu.planning.errors import Deadline

    store = _store(metrics=MetricsRegistry())
    now = _t.monotonic()
    plan = store.planner.plan("ev", Q)
    burned = Deadline(start=now - 1.0, budget_s=0.5, cutoff=now - 0.5)
    fin = store.planner.submit_many([plan], deadlines=[burned])[0]
    with pytest.raises(QueryTimeout):
        fin()
    plan2 = store.planner.plan("ev", Q)
    fresh = Deadline(start=now, budget_s=30.0, cutoff=now + 30.0)
    out = store.planner.submit_many([plan2], deadlines=[fresh])[0]()
    assert len(out) == len(store.query("ev", Q))
    # non-simple plans (here a union) honor the anchor through their
    # synchronous execute() fallback too
    union_q = f"{Q} OR kind = 'c'"
    plan3 = store.planner.plan("ev", union_q)
    assert plan3.union is not None
    fin3 = store.planner.submit_many([plan3], deadlines=[burned])[0]
    with pytest.raises(QueryTimeout):
        fin3()
    out3 = store.planner.submit_many(
        [store.planner.plan("ev", union_q)], deadlines=[fresh]
    )[0]()
    assert len(out3) == len(store.query("ev", union_q))


def test_cancelled_future_does_not_poison_the_batch():
    """A client-side cancel() (disconnect) on one queued future must not
    fail the co-batched queries sharing its fused dispatch."""
    store = _store(metrics=MetricsRegistry())
    sched = QueryScheduler(store, ServingConfig())  # staged queue
    f1 = sched.submit("ev", Q)
    f2 = sched.submit("ev", Q)        # coalesces onto f1's slot
    f3 = sched.submit("ev", "kind = 'b'")
    assert f1.cancel()
    sched.start()
    assert len(f2.result(10)) == len(store.query("ev", Q))
    assert len(f3.result(10)) == len(store.query("ev", "kind = 'b'"))
    sched.close()


def test_no_coalescing_across_a_mutation():
    """Identical queries admitted on opposite sides of a committed write
    land in different mutation epochs: they must NOT share one result —
    the later submitter sees its own write."""
    reg = MetricsRegistry()
    store = _store(metrics=reg)
    e0 = store.planner.mutation_epoch
    sched = QueryScheduler(store, ServingConfig(), metrics=reg)  # staged
    f1 = sched.submit("ev", Q)
    sft = store.get_schema("ev")
    store.write("ev", FeatureCollection.from_columns(
        sft, ["w1", "w2"],
        {
            "kind": np.array(["a", "a"]),
            "dtg": np.full(2, np.datetime64("2024-01-02", "ms").astype(np.int64)),
            "geom": (np.array([1.0, 2.0]), np.array([1.0, 2.0])),
        },
    ))
    assert store.planner.mutation_epoch > e0
    f2 = sched.submit("ev", Q)  # same fingerprint, NEW epoch
    sched.start()
    r1, r2 = f1.result(10), f2.result(10)
    assert reg.counters.get("geomesa.serving.coalesced", 0) == 0
    assert r2 is not r1
    ids2 = set(np.asarray(r2.ids).tolist())
    assert {"w1", "w2"} <= ids2  # read-your-writes for the later caller
    sched.close()


def test_plan_errors_raise_at_submit():
    store = _store(metrics=MetricsRegistry())
    with QueryScheduler(store, ServingConfig()) as sched:
        with pytest.raises(KeyError):
            sched.submit("nope", Q)  # unknown type: caller-thread raise
        with pytest.raises(Exception):
            sched.submit("ev", "this is not ecql (")
        with pytest.raises(ValueError, match="sample"):
            # bad hints raise at submit too, never poisoning a batch
            sched.submit("ev", Q, hints=QueryHints(sample=5.0))


def test_execution_errors_land_on_the_future(monkeypatch):
    store = _store(metrics=MetricsRegistry())
    sched = QueryScheduler(store, ServingConfig())  # staged
    fut = sched.submit("ev", Q)  # planned against the healthy store

    def boom(*a, **k):
        raise RuntimeError("device gone")

    monkeypatch.setattr(store, "table", boom)  # dispatch-time failure
    sched.start()
    with pytest.raises(RuntimeError, match="device gone"):
        fut.result(10)
    sched.close()


def test_close_fails_pending_and_refuses_new():
    store = _store(metrics=MetricsRegistry())
    sched = QueryScheduler(store, ServingConfig())  # never started
    fut = sched.submit("ev", Q)
    sched.close()
    with pytest.raises(RuntimeError, match="closed"):
        fut.result(1)
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit("ev", Q)


def test_queue_wait_attribution(ds):
    """Queue wait is attributed separately from scan time: the live
    histogram lands in metrics and the explain trace carries both."""
    reg = ds.metrics
    sched = ds.serve()
    exp = Explainer()
    sched.submit("ev", Q, explain=exp).result(10)
    sched.close()
    snap = reg.snapshot()
    assert snap["histograms"]["geomesa.serving.queue_wait"]["count"] >= 1
    line = next(l for l in exp.lines if l.strip().startswith("serving:"))
    assert "queue wait" in line and "scan" in line and "fused batch" in line
    # the device-scan trace reaches the caller's explainer even through
    # the fused dispatch (submit_many per-plan explains)
    assert any("Device scan" in l for l in exp.lines)


def test_admission_gap_drains_and_bounds(ds):
    """The fold's between-slice yield (docs/streaming.md "Incremental
    fold"): an idle queue returns immediately; a queue that cannot drain
    (unstarted dispatcher) returns False at the bound; once the
    dispatcher runs, the gap closes."""
    sched = QueryScheduler(ds, ServingConfig(window_ms=0.0))
    assert sched.admission_gap(0.01) is True  # idle: immediate
    fut = sched.submit("ev", Q)               # queued, nothing drains it
    t0 = time.perf_counter()
    assert sched.admission_gap(0.05) is False
    assert time.perf_counter() - t0 < 2.0     # bounded wait
    sched.start()
    assert sched.admission_gap(5.0) is True
    assert len(fut.result(10)) > 0
    sched.close()
