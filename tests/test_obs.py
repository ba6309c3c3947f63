"""The observability layer (docs/observability.md): structured tracing
through the query/write paths, the slow-query log, live histograms in
context, and SLO tracking.

Layers:

- **disarmed is free**: with both arming knobs at 0 every tracing entry
  point returns the shared null singleton — no allocation, no trace;
- **trace vs explain**: the same regions are timed by both surfaces, so
  phase durations agree within tolerance and the breakdown rides the
  explain trail;
- **cross-thread correctness**: a scheduler-served query's span tree is
  ONE tree across the caller thread and the dispatcher (plan in one
  thread, scan in another, every phase parented on the root); fold
  slices land inside the flush trace;
- **surfaces**: sampling, the slow-query ring (fingerprint + span
  tree), Chrome trace-event export, SLO windows/burn rates and the
  ``/health``-servable report.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.obs.trace import NULL_SPAN
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.sft import FeatureType

SPEC = "name:String,dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
Q = "BBOX(geom, -20, -20, 20, 20)"


@pytest.fixture(autouse=True)
def _fresh_tracer():
    """Every test gets a fresh tracer, an empty stall record (it is the
    process's: the Chrome export holds it), no lock probe left running by
    the test before (a thread that retires its role reads its clock only
    while one runs) and restored knobs."""
    obs.install(obs.Tracer())
    obs.trace.clear_stalls()
    obs.trace.clear_lock()
    yield
    for knob in (conf.OBS_TRACE_SAMPLE, conf.OBS_SLOW_MS,
                 conf.OBS_TRACE_BUFFER, conf.OBS_SLOW_MAX):
        knob.clear()
    obs.install(obs.Tracer())


def _arm(sample=1, slow_ms=0.0):
    conf.OBS_TRACE_SAMPLE.set(sample)
    conf.OBS_SLOW_MS.set(slow_ms)


def _disarm():
    conf.OBS_TRACE_SAMPLE.set(0)
    conf.OBS_SLOW_MS.set(0.0)


def _store(n=4000, metrics=None, cache=False):
    ds = DataStore(metrics=metrics, cache=cache)
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    rng = np.random.default_rng(0)
    ds.write("t", FeatureCollection.from_columns(
        sft, [f"r{i}" for i in range(n)],
        {"name": np.array(["n"] * n),
         "dtg": T0 + rng.integers(0, 30 * DAY, n),
         "geom": (rng.uniform(-50, 50, n), rng.uniform(-50, 50, n))},
    ))
    return ds


# -- layer 1: disarmed is free --------------------------------------------


def test_disarmed_tracing_is_noop():
    """The no-op check: with both knobs at 0 the span entry returns the
    SHARED null singleton (identity — zero allocation on the hot path),
    roots never open, nothing is retained."""
    _disarm()
    t = obs.tracer()
    assert t.begin("query") is None
    # the singleton, not a fresh object per call
    assert obs.span("scan") is NULL_SPAN
    assert t.span("scan") is NULL_SPAN
    assert obs.span("scan") is obs.span("decode")
    with obs.span("scan") as s:
        assert s is NULL_SPAN
    ds = _store(n=500)
    for _ in range(5):
        ds.query("t", Q)
    assert t.traces() == []
    assert ds.slow_queries() == []


def test_disarmed_query_smoke_no_trace_state(monkeypatch):
    """End to end through every traced entry point (query, query_many,
    count, density, a served request in both formats): the disarmed path
    leaves no tracer state behind (buffer, slow ring, root counters all
    empty), allocates no span, reads no thread clock and enters no
    profiler annotation."""
    from geomesa_tpu.obs import trace as otrace
    from geomesa_tpu.serving import DataClient
    from geomesa_tpu.serving.http import DataServer

    _disarm()
    made = {"spans": 0, "cpu": 0, "annotations": 0}
    span_init, thread_time = otrace.Span.__init__, time.thread_time

    def counted_init(self, *a, **kw):
        made["spans"] += 1
        span_init(self, *a, **kw)

    def counted_thread_time():
        made["cpu"] += 1
        return thread_time()

    def counted_annotation(*a, **kw):
        made["annotations"] += 1

    monkeypatch.setattr(otrace.Span, "__init__", counted_init)
    monkeypatch.setattr(otrace.time, "thread_time", counted_thread_time)
    monkeypatch.setattr(otrace, "_annotation", counted_annotation)
    ds = _store(n=500)
    ds.query("t", Q)
    ds.query_many("t", [Q, "BBOX(geom, -10, -10, 30, 30)"])
    ds.count("t", Q)
    ds.density("t", Q, width=32, height=32)
    srv = DataServer(ds, port=0).start()
    try:
        with DataClient(srv.url) as c:
            assert len(c.query("t", Q)["features"])
            assert c.query("t", Q, fmt="arrow")
    finally:
        srv.close()
        ds.close()
    assert made == {"spans": 0, "cpu": 0, "annotations": 0}
    t = obs.tracer()
    with t._lock:
        assert len(t.buffer) == 0 and t.slow == [] and not t._n_roots


def test_null_span_event_allocates_nothing():
    """The disarmed forms of ``event`` and ``add``: the shared null
    span's methods and the module-level probes return without a single
    allocation."""
    _disarm()
    for _ in range(10):  # settle lazily-made method caches
        NULL_SPAN.event("wait"), obs.event("wait"), NULL_SPAN.add("blocks", 3)
    before = sys.getallocatedblocks()
    for _ in range(2000):
        assert NULL_SPAN.event("wait") is False
        assert obs.event("wait") is False
        NULL_SPAN.add("blocks", 3)
    assert sys.getallocatedblocks() - before < 8


# -- layer 2: trace vs explain --------------------------------------------


def test_trace_vs_explain_consistency():
    """The two timing surfaces cover the same regions: the trace's scan
    phase brackets the explainer's 'Device scan ... took X ms' line
    (within tolerance), and the per-phase breakdown is appended to the
    explain trail with the trace attached."""
    _arm(sample=1)
    ds = _store()
    ds.query("t", Q)  # warm the kernel variant so timings are honest
    exp = Explainer()
    out = ds.query("t", Q, explain=exp)
    assert len(out)
    tr = exp.trace
    assert tr is not None and tr is obs.tracer().traces()[-1]
    phases = {s.name: s for s in tr.phases()}
    assert {"plan", "scan", "decode"} <= set(phases)
    # explain's own span timing for the device scan
    lines = exp.lines
    i = next(j for j, l in enumerate(lines) if "Device scan" in l)
    took_ms = float(lines[i + 1].strip().removeprefix("took ").removesuffix("ms"))
    scan_ms = phases["scan"].dur_s * 1e3
    # the obs span wraps the explain span, so scan >= took, within slack
    assert scan_ms >= took_ms * 0.99
    assert scan_ms <= took_ms + max(2.0, took_ms)
    # the breakdown rides the trail
    assert any(l.startswith("trace: plan") for l in lines)
    assert any(l.startswith("trace: scan") for l in lines)
    assert any("phases cover" in l for l in lines)


def test_plan_probe_and_decompose_nested_under_plan():
    """The planner's memo probe and z-range decomposition are children
    of the plan phase: a cold plan decomposes, a warm repeat only
    probes."""
    _arm(sample=1)
    ds = _store()
    ds.query("t", Q)
    cold = obs.tracer().traces()[-1]
    names = {s.name for s in cold.spans}
    assert "plan.decompose" in names and "plan.probe" in names
    plan = next(s for s in cold.phases() if s.name == "plan")
    probe = next(s for s in cold.spans if s.name == "plan.probe")
    assert probe.parent_id == plan.span_id
    ds.query("t", Q)  # memoized: no decomposition
    warm = obs.tracer().traces()[-1]
    warm_names = [s.name for s in warm.spans]
    assert "plan.probe" in warm_names
    assert "plan.decompose" not in warm_names


# -- layer 3: cross-thread correctness ------------------------------------


def test_scheduler_thread_hop_keeps_one_tree():
    """A scheduler-served query's trace: plan in the caller thread,
    queue/dispatch/scan/decode attached from the dispatcher — ≥5
    distinct top-level phases, all parented on the root, and the
    top-level durations sum close to the root wall."""
    _arm(sample=1)
    ds = _store(metrics=MetricsRegistry())
    ds.query("t", Q)  # warm kernels outside the traced run
    obs.install(obs.Tracer())
    sched = ds.serve()
    try:
        out = sched.submit("t", Q).result(30)
        assert len(out)
    finally:
        sched.close()
    tr = next(
        t for t in reversed(obs.tracer().traces())
        if t.root.attrs and t.root.attrs.get("serving")
    )
    phases = tr.phases()
    names = [s.name for s in phases]
    assert len(set(names)) >= 5, names
    for want in ("plan", "queue", "dispatch", "scan", "decode"):
        assert want in names, names
    rid = tr.root.span_id
    assert all(s.parent_id == rid for s in phases)
    # the hop really happened: plan recorded on a different thread than
    # the device pull
    plan = next(s for s in phases if s.name == "plan")
    scan = next(s for s in phases if s.name == "scan")
    assert plan.tid != scan.tid
    covered = sum(s.dur_s for s in phases)
    assert covered <= tr.wall_s * 1.05
    assert covered >= tr.wall_s * 0.7, (covered, tr.wall_s)
    # queue wait went to the live histogram as well
    assert ds.metrics.snapshot()["histograms"][
        "geomesa.serving.queue_wait"
    ]["count"] >= 1


def test_flush_trace_carries_stage_and_fold_slice_spans():
    """The write path: one flush = one trace; worker-pool stage spans
    (parse/keys/sort) re-attach across the thread hop, and a sliced
    fold's per-slice publishes appear with the live pause histogram."""
    from geomesa_tpu.streaming import LambdaStore, StreamConfig

    _arm(sample=1)
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg)
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    lam = LambdaStore(ds, "t", config=StreamConfig(
        chunk_rows=128, workers=2, fold_rows=8, slice_rows=200,
    ))
    rng = np.random.default_rng(1)

    def rows(n, seed):
        r = np.random.default_rng(seed)
        return [
            {"__id__": f"w{i}", "name": "n",
             "dtg": np.datetime64(int(T0 + r.integers(0, DAY)), "ms"),
             "geom": f"POINT ({r.uniform(-50, 50):.5f} {r.uniform(-50, 50):.5f})"}
            for i in range(n)
        ]

    lam.write(rows(600, 2))
    lam.flush()
    lam.write(rows(600, 3))  # same ids: the update-fold path
    lam.flush(full=True)
    lam.close()
    flushes = [
        t for t in obs.tracer().traces() if t.name == "flush"
    ]
    assert flushes
    fold_flush = flushes[-1]
    names = [s.name for s in fold_flush.spans]
    for want in ("flush.parse", "flush.keys", "flush.sort", "flush.commit"):
        assert want in names, names
    slices = [s for s in fold_flush.spans if s.name == "fold.slice"]
    assert len(slices) >= 2  # 600 rows / 200 slice_rows
    commit = next(s for s in fold_flush.spans if s.name == "flush.commit")
    assert all(s.parent_id == commit.span_id for s in slices)
    # worker spans recorded from pool threads, same tree
    parse = next(s for s in fold_flush.spans if s.name == "flush.parse")
    assert parse.trace is fold_flush
    # the pause histogram is live
    h = reg.snapshot()["histograms"]["geomesa.stream.fold.slice"]
    assert h["count"] == len(slices)
    # write traces rooted too (WAL-less write: just the root)
    assert any(t.name == "write" for t in obs.tracer().traces())


def test_wal_spans_inside_write_trace(tmp_path):
    """A WAL-attached acknowledged write traces its append and fsync:
    wal.append/wal.sync spans inside the write root, and the fsync
    histogram records only real fsyncs."""
    from geomesa_tpu.storage import persist
    from geomesa_tpu.streaming import LambdaStore, StreamConfig, WalConfig

    _arm(sample=1)
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg)
    sft = FeatureType.from_spec("t", SPEC)
    ds.create_schema(sft)
    root = tmp_path / "s"
    persist.save(ds, root)
    lam = LambdaStore(
        ds, "t", config=StreamConfig(chunk_rows=64),
        wal_dir=str(root / "_wal"),
        wal_config=WalConfig(sync="always"),
    )
    lam.write([{
        "__id__": "a", "name": "n",
        "dtg": np.datetime64(T0, "ms"), "geom": "POINT (1 1)",
    }])
    lam.close()
    wt = next(t for t in obs.tracer().traces() if t.name == "write")
    names = [s.name for s in wt.spans]
    assert "wal.append" in names and "wal.sync" in names
    assert reg.snapshot()["histograms"]["geomesa.stream.wal.fsync"]["count"] >= 1


# -- layer 3b: every operation under one root, phases inside -------------

MANY = [f"BBOX(geom, {-30 + 3 * i}, -20, {10 + 3 * i}, 20)" for i in range(12)]

#: operation -> (root name, the direct children it must have)
ROOTED = {
    "query": ("query", {"plan", "dispatch", "scan", "decode"}),
    "query_many": ("query_many", {"plan", "dispatch", "scan", "decode"}),
    "count": ("count", {"plan", "dispatch", "scan", "decode"}),
    "density": ("density", {"plan", "dispatch", "agg"}),
    "served-geojson": ("http", {"http.parse", "http.wait", "encode"}),
    "served-arrow": ("http", {"http.parse", "http.wait", "encode"}),
}
#: a clock read on either side of a span's ends
SLACK_S = 2e-4


def _run_rooted(op, ds, client):
    if op == "query":
        return ds.query("t", Q)
    if op == "query_many":
        return ds.query_many("t", MANY)
    if op == "count":
        return ds.count("t", Q)
    if op == "density":
        return ds.density("t", Q, width=64, height=64)
    return client.query("t", Q, fmt=op.removeprefix("served-"))


@pytest.mark.parametrize("op", sorted(ROOTED))
def test_every_operation_is_one_root_with_covering_phases(op):
    """Each entry point of the two benchmark cells opens exactly ONE
    root of its own name; the named phases are its direct children, each
    inside it and none overlapping its sibling (the share of the wall
    they cover is ``span_coverage_pct``'s, on the chip);
    every span's segments lie within it; where a span reads the CPU clock
    its CPU time lies within its wall time;
    a served request's ``http`` root and its ``query`` root name each
    other."""
    from geomesa_tpu.serving import DataClient
    from geomesa_tpu.serving.http import DataServer

    _arm(sample=1)
    ds = _store()
    srv = client = None
    if op.startswith("served"):
        srv = DataServer(ds, port=0).start()
        client = DataClient(srv.url)
    root_name, want = ROOTED[op]
    try:
        for _ in range(3):  # warm kernels, imports, connections
            _run_rooted(op, ds, client)
        obs.install(obs.Tracer())
        _run_rooted(op, ds, client)
        if srv is not None:
            # the handler ends its root after the client has read
            # the last byte, the dispatcher its ``batch`` after the
            # last member is resolved
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline and not (
                {"http", "query", "batch"}
                <= {t.name for t in obs.tracer().traces()}
            ):
                time.sleep(0.005)
        traces = obs.tracer().traces()
        roots = [t for t in traces if t.name == root_name]
        assert len(roots) == 1, [t.name for t in traces]
        if srv is None:
            assert [t.name for t in traces] == [root_name]
        tr = roots[0]
        phases = tr.phases()
        assert want <= {s.name for s in phases}, [s.name for s in phases]
        # each phase lies inside its root, and none overlaps its sibling
        t_end = tr.root.t0 + tr.wall_s
        for s in phases:
            assert tr.root.t0 - SLACK_S <= s.t0, s.name
            assert s.t0 + s.dur_s <= t_end + SLACK_S, s.name
        for a, b in zip(phases, phases[1:]):
            assert a.t0 + a.dur_s <= b.t0 + SLACK_S, (a.name, b.name)
        for s in tr.spans:
            a = s.attrs or {}
            # the phases that never sleep by design read the CPU clock,
            # and so does a root that opens and closes on one thread
            assert ("cpu_s" in a) == (
                s.name in ("plan", "decode", "encode") or s is tr.root)
            if "cpu_s" in a:
                assert 0.0 <= a["cpu_s"] <= s.dur_s + SLACK_S, (s.name, a)
            segs = a.get("segments", {})
            assert all(w >= 0.0 for w in segs.values())
            assert sum(segs.values()) <= s.dur_s + SLACK_S
        by_name = {s.name: s for s in tr.spans}
        if op == "query_many":
            members = sorted(
                s.attrs["member"] for s in tr.spans if s.name == "scan"
            )
            assert members == list(range(len(MANY)))
            assert tr.root.attrs["members"] == len(MANY)
            pulls = [s for s in tr.spans if s.name == "scan"
                     and "wait" in s.attrs["segments"]]
            # one pull serves the fused group: one member carries it
            assert len(pulls) == 1 and pulls[0].attrs["group"] == len(MANY)
            # ONE plan for the batch (QueryPlanner.plan_many), a direct
            # child of the root, with an index's probe and decompose
            # as its children
            (plan,) = [s for s in tr.spans if s.name == "plan"]
            assert plan.parent_id == tr.root.span_id
            pa = plan.attrs
            assert pa["members"] == len(MANY)
            assert 0 <= pa["batched"] <= pa["members"]
            assert {"parse", "estimate"} <= set(pa["segments"]) <= {
                "parse", "extract", "decompose", "spans", "estimate"}
            kids = [s for s in tr.spans if s.name.startswith("plan.")]
            assert kids and all(s.parent_id == plan.span_id for s in kids)
            assert all(s.attrs["members"] <= len(MANY) for s in kids)
        if op in ("query", "count"):
            assert set(by_name["scan"].attrs["segments"]) == {
                "wait", "pull", "bits"}
            assert set(by_name["decode"].attrs["segments"]) == {
                "gather", "refine", "post"}
            d = by_name["dispatch"].attrs
            assert set(d["segments"]) == {"prune", "enqueue"}
            assert 1 <= d["blocks"] <= d["slots"]
        if op == "density":
            assert set(by_name["agg"].attrs["segments"]) == {"wait", "pull"}
        if srv is not None:
            query = next(t for t in traces if t.name == "query")
            batch = next(t for t in traces if t.name == "batch")
            assert tr.root.attrs["query_trace"] == query.trace_id
            assert query.root.attrs["http_trace"] == tr.trace_id
            assert query.root.attrs["batch_trace"] == batch.trace_id
            assert tr.root.attrs["status"] == 200
            assert tr.root.attrs["fmt"] == op.removeprefix("served-")
            assert tr.root.attrs["rows"] > 0
            enc = by_name["encode"].attrs
            assert enc["bytes"] == tr.root.attrs["bytes"] > 0
            assert 0.0 <= enc["write_s"] <= by_name["encode"].dur_s
            assert {"dispatch"} <= {s.name for s in batch.phases()}
    finally:
        if client is not None:
            client.close()
        if srv is not None:
            srv.close()
        ds.close()


def test_profiler_annotations_only_for_retained_traces(monkeypatch):
    """Spans and segments of a RETAINED trace are ``geomesa:`` profiler
    annotations carrying the trace id, properly nested; a tree built
    only for the slow log enters none and reads no CPU clock."""
    import jax.profiler

    from geomesa_tpu.obs import trace as otrace

    cpu_reads = []
    thread_time = time.thread_time
    monkeypatch.setattr(
        otrace.time, "thread_time",
        lambda: cpu_reads.append(1) or thread_time(),
    )

    live: list = []
    seen: list = []

    class FakeAnnotation:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            live.append(self.name)
            seen.append((self.name, self.kw))

        def __exit__(self, *exc):
            assert live.pop() == self.name  # innermost first

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    ds = _store()
    _arm(sample=0, slow_ms=0.0001)  # every tree built, none retained
    ds.query("t", Q)
    assert len(ds.slow_queries()) == 1 and seen == [] and cpu_reads == []
    _arm(sample=1)
    ds.query("t", Q)
    assert live == []
    assert len(cpu_reads) == 6  # both ends of the root, of ``plan`` and of ``decode``
    names = [n for n, _ in seen]
    for want in ("geomesa:query", "geomesa:plan", "geomesa:dispatch",
                 "geomesa:dispatch.prune", "geomesa:dispatch.enqueue",
                 "geomesa:scan", "geomesa:scan.wait", "geomesa:scan.pull",
                 "geomesa:scan.bits", "geomesa:decode",
                 "geomesa:decode.gather", "geomesa:decode.refine",
                 "geomesa:decode.post"):
        assert names.count(want) == 1, (want, names)
    tid = obs.tracer().traces()[-1].trace_id
    assert all(kw == {"trace": tid} for _, kw in seen)
    # retroactive spans and roots that end on another thread cannot be
    # annotations: a served query's root and its queue phases are not
    seen.clear()
    sched = ds.serve()
    try:
        sched.submit("t", Q).result(30)
    finally:
        sched.close()
        ds.close()
    names = {n for n, _ in seen}
    assert "geomesa:batch" in names and "geomesa:plan" in names
    assert not names & {"geomesa:query", "geomesa:queue", "geomesa:admit",
                        "geomesa:batch.wait"}


# -- layer 4: surfaces ----------------------------------------------------


def test_sampling_retains_every_nth_root():
    _arm(sample=4)
    t = obs.tracer()
    for _ in range(8):
        with t.trace("query"):
            pass
    assert len(t.traces()) == 2


def test_retention_counters_record():
    """geomesa.obs.traces / geomesa.obs.slow_queries are LIVE counters:
    a tracer with an explicit registry records there; the default
    (metrics=None) falls back to the process-global registry like every
    other unconfigured component."""
    from geomesa_tpu.metrics import global_registry

    reg = MetricsRegistry()
    t = obs.install(obs.Tracer(metrics=reg))
    _arm(sample=1, slow_ms=0.0001)
    with t.trace("query"):
        time.sleep(0.001)
    assert reg.counter_value("geomesa.obs.traces") == 1
    assert reg.counter_value("geomesa.obs.slow_queries") == 1
    t2 = obs.install(obs.Tracer())  # default: global fallback
    before = global_registry().counter_value("geomesa.obs.traces")
    with t2.trace("query"):
        pass
    assert global_registry().counter_value("geomesa.obs.traces") == before + 1


def test_trace_buffer_is_bounded():
    conf.OBS_TRACE_BUFFER.set(8)
    obs.install(obs.Tracer())  # picks up the cap
    _arm(sample=1)
    t = obs.tracer()
    for i in range(20):
        with t.trace("query", i=i):
            pass
    kept = t.traces()
    assert len(kept) == 8
    assert kept[-1].root.attrs["i"] == 19  # newest retained


def test_slow_query_log_captures_fingerprint_and_tree():
    """Always-on slow log: sampling OFF, threshold tiny — every query
    lands in the ring with its plan fingerprint and full span tree."""
    _arm(sample=0, slow_ms=0.0001)
    ds = _store()
    ds.query("t", Q)
    assert obs.tracer().traces() == []  # not sampled ...
    slow = ds.slow_queries()
    assert len(slow) == 1  # ... but captured
    entry = slow[0]
    assert entry["wall_ms"] > 0
    assert entry["fingerprint"]["type"] == "t"
    assert entry["fingerprint"]["strategy"] in ("z3", "z2")
    span_names = {s["name"] for s in entry["trace"]["spans"]}
    assert {"plan", "scan", "decode"} <= span_names
    # ring is bounded
    conf.OBS_SLOW_MAX.set(3)
    for _ in range(6):
        ds.query("t", Q)
    assert len(ds.slow_queries()) == 3


def test_chrome_trace_export(tmp_path):
    _arm(sample=1, slow_ms=0.0001)
    ds = _store()
    ds.query("t", Q)
    path = ds.dump_trace(str(tmp_path / "trace.json"))
    payload = json.load(open(path))
    events = payload["traceEvents"]
    assert events
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["dur"] >= 0 and ev["ts"] >= 0
    names = {ev["name"] for ev in events}
    assert {"query", "plan", "scan"} <= names
    # slow-ring traces export once even when also sampled
    ids = [ev["args"]["trace_id"] for ev in events if ev["name"] == "query"]
    assert len(ids) == len(set(ids))
    # one process, a lane per real thread, on the wall clock: traces of
    # concurrent requests line up, each span on the thread that ran it
    assert {ev["pid"] for ev in events} == {os.getpid()}
    # (a stall of the runtime lies on the lane of the thread it stopped,
    # which a collection started by a leftover thread need not share)
    assert {ev["tid"] for ev in events if "kind" not in ev["args"]} == {
        threading.get_ident()}
    now_us = time.time() * 1e6
    assert all(now_us - 60e6 < ev["ts"] <= now_us for ev in events)


def test_concurrent_tracing_keeps_trees_separate():
    """Parallel traced queries never cross-contaminate span trees
    (thread-local propagation): every span's trace is its own root's."""
    _arm(sample=1)
    ds = _store(n=2000)
    errs = []

    def worker(seed):
        try:
            for _ in range(5):
                ds.query("t", Q)
        except BaseException as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    traces = obs.tracer().traces()
    assert len(traces) == 20
    for tr in traces:
        rid = tr.root.span_id
        for s in tr.spans:
            assert s.trace is tr
            assert s.parent_id is None or s.parent_id == rid or any(
                p.span_id == s.parent_id for p in tr.spans
            )


# -- layer 5: SLO tracking ------------------------------------------------


def test_slo_objective_windows_and_burn_rate():
    """Observations feed sliding windows; the report carries windowed
    quantiles, violation fractions and burn rates; old slices age out."""
    o = obs.SloObjective("q99", "geomesa.query.scan", 0.99, 0.1, budget=0.02)
    slo = obs.SloTracker([o], window_s=10.0, slices=5)
    now = 1_000_000.0
    for _ in range(96):
        slo.observe("geomesa.query.scan", 0.01, now=now)
    for _ in range(4):
        slo.observe("geomesa.query.scan", 0.5, now=now)
    rep = slo.report(now=now)
    row = rep["objectives"][0]
    assert row["count"] == 100 and row["violations"] == 4
    assert row["burn_rate"] == pytest.approx(0.04 / 0.02, rel=1e-6)
    assert not row["ok"] and rep["status"] == "breach"
    # the breach ages out of the window
    later = now + 30.0
    for _ in range(50):
        slo.observe("geomesa.query.scan", 0.01, now=later)
    rep2 = slo.report(now=later)
    row2 = rep2["objectives"][0]
    assert row2["count"] == 50 and row2["violations"] == 0
    assert row2["ok"] and rep2["status"] == "ok"
    assert row2["value_ms"] <= 100.0


def test_two_trackers_on_one_registry_fan_out():
    """Two stores sharing one registry (the bench pattern): a second
    attach must fan observations out to BOTH trackers, never silently
    detach the first; re-attaching the same tracker stays idempotent
    (no double counting)."""
    reg = MetricsRegistry()
    a = obs.SloTracker([
        obs.SloObjective("q99", "geomesa.query.scan", 0.99, 0.1),
    ]).attach(reg)
    a.attach(reg)  # idempotent re-attach
    b = obs.SloTracker([
        obs.SloObjective("q99", "geomesa.query.scan", 0.99, 0.1),
    ]).attach(reg)
    reg.observe("geomesa.query.scan", 0.01)
    assert a.report()["objectives"][0]["count"] == 1
    assert b.report()["objectives"][0]["count"] == 1


def test_slo_ignores_unmatched_metrics():
    slo = obs.SloTracker([
        obs.SloObjective("q99", "geomesa.query.scan", 0.99, 0.1),
    ])
    slo.observe("geomesa.stream.wal.fsync", 9.0)
    assert slo.report()["objectives"][0]["count"] == 0


def test_default_objectives_follow_knobs():
    objs = {o.name for o in obs.default_objectives()}
    assert objs == {
        "query_p99", "fold_slice_p99", "wal_fsync_p99",
        "standing_alert_p99", "replica_staleness_p99", "tiles_p99",
    }
    conf.OBS_SLO_WAL_P99_MS.set(0)
    conf.OBS_SLO_STANDING_P99_MS.set(0)
    conf.OBS_SLO_REPLICA_STALENESS_P99_MS.set(0)
    conf.OBS_SLO_TILES_P99_MS.set(0)
    try:
        objs = {o.name for o in obs.default_objectives()}
        assert "wal_fsync_p99" not in objs
        assert "standing_alert_p99" not in objs
        assert "replica_staleness_p99" not in objs
        assert "tiles_p99" not in objs
    finally:
        conf.OBS_SLO_WAL_P99_MS.clear()
        conf.OBS_SLO_STANDING_P99_MS.clear()
        conf.OBS_SLO_REPLICA_STALENESS_P99_MS.clear()
        conf.OBS_SLO_TILES_P99_MS.clear()


def test_datastore_slo_report_end_to_end():
    """attach_slo wires the registry observer: real queries move the
    query_p99 objective; the report is /health-servable (plain JSON
    types only); an unattached store reports ok/empty."""
    ds0 = _store(n=200)
    assert ds0.slo_report() == {
        "status": "ok", "window_s": 0.0, "objectives": []
    }
    ds = _store(metrics=MetricsRegistry())
    tracker = ds.attach_slo()
    assert ds.slo is tracker
    for _ in range(5):
        ds.query("t", Q)
    rep = ds.slo_report()
    row = next(r for r in rep["objectives"] if r["objective"] == "query_p99")
    assert row["count"] == 5
    assert row["value_ms"] > 0
    json.dumps(rep)  # strictly serializable
    # a store built WITHOUT a registry gets one on attach
    ds2 = _store()
    assert ds2.metrics is None
    ds2.attach_slo()
    assert ds2.metrics is not None
    ds2.query("t", Q)
    assert ds2.slo_report()["objectives"][0]["count"] == 1
    # re-attaching REPLACES the store's tracker (no fan-out chain to
    # the detached one: observations reach only the live tracker)
    old = ds2.slo
    new = ds2.attach_slo()
    assert new is not old
    assert ds2.metrics.observer == new.observe
    ds2.query("t", Q)
    assert new.report()["objectives"][0]["count"] == 1
    assert old.report()["objectives"][0]["count"] == 1  # frozen, detached
