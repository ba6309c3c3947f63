"""Runtime stalls (docs/observability.md "Runtime stalls"): the
collector's pauses and the compiler's phases on the process's stall
record, on the span that was open, on every root that waited, and on the
surfaces the tracer already had; then the benchmark's six readers of the
record (``benchmark/layer_metrics/``) over hand-made views.

- **the collector's hook**: a forced collection under a span lands on it,
  in the ring with the span's trace id, and as ``gc_wait_s`` on a root
  another thread had open; it takes no lock (forced under the registry's
  and the tracer's, with the lock witness armed);
- **the compiler's hook**: a first call of a fresh ``jax.jit`` function
  puts its three phases in the ring under its name, ``compile_s`` and
  ``compile_n`` on the span and one ``geomesa.query.compiled``;
- **the record is the process's**: ``install`` and ``reset`` keep it;
- **surfaces**: ``/metrics``, ``/debug/stalls``, the Chrome payload, the
  explain trail's line; disarmed, a root installs no hook.
"""

import gc
import importlib
import json
import os
import sys
import threading
import time

import pytest

from geomesa_tpu import conf, lockwitness, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.obs import trace as otrace
from geomesa_tpu.obs.ops import OpsRoutes
from geomesa_tpu.obs.trace import NULL_SPAN

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BENCH_PACKAGES = ("harness", "layer_metrics")
NEW = ["gc_pause_pct", "gc_max_ms", "gc_tail_pct", "idle_gc_pct", "compile_host_s",
       "compile_top_s"]


@pytest.fixture(autouse=True)
def _fresh():
    """A fresh tracer, an empty stall record, restored knobs, and no
    collection but the ones a test forces (their callbacks still run), so
    that the counts are the test's own."""
    obs.install(obs.Tracer())
    gc.collect()
    gc.disable()
    otrace.clear_stalls()
    yield
    gc.enable()
    for knob in (conf.OBS_TRACE_SAMPLE, conf.OBS_SLOW_MS):
        knob.clear()
    obs.install(obs.Tracer())
    otrace.clear_stalls()


def _arm(sample=1, slow_ms=0.0):
    conf.OBS_TRACE_SAMPLE.set(sample)
    conf.OBS_SLOW_MS.set(slow_ms)


def _collect():
    """One full collection over a heap large enough that it takes a
    millisecond (the ring's threshold)."""
    junk = [[i] for i in range(300_000)]
    gc.collect()
    return len(junk)


# -- the collector's hook ---------------------------------------------------


def test_forced_collection_lands_on_the_span_the_ring_and_other_threads_roots():
    _arm()
    t = obs.tracer()
    opened, done, other = threading.Event(), threading.Event(), {}

    def elsewhere():
        with t.trace("count") as tr:
            other["trace"] = tr
            opened.set()
            done.wait(30)

    th = threading.Thread(target=elsewhere)
    th.start()
    assert opened.wait(30)
    try:
        with t.trace("query") as tr:
            with obs.span("dispatch") as sp:
                _collect()
    finally:
        done.set()
        th.join(30)
    assert sp.attrs["gc_n"] == 1 and sp.attrs["gc_s"] > 0
    pauses = [r for r in obs.stalls() if r["kind"] == "gc"]
    assert len(pauses) == 1
    (rec,) = pauses
    assert rec["name"] == "gen2" and rec["trace_id"] == tr.trace_id
    assert rec["dur_s"] == pytest.approx(sp.attrs["gc_s"]) and rec["dur_s"] >= otrace.GC_RING_S
    assert rec["tid"] == threading.get_ident()
    # the pause was everybody's: the root on this thread and the one on the other
    assert tr.root.attrs["gc_wait_s"] == pytest.approx(rec["dur_s"])
    assert other["trace"].root.attrs["gc_wait_s"] == pytest.approx(rec["dur_s"])
    assert "gc_s" not in (other["trace"].root.attrs or {})
    # a root that began after the pause ended waited for nothing
    with t.trace("query") as later:
        pass
    assert "gc_wait_s" not in (later.root.attrs or {})
    tot = obs.stall_totals()
    assert tot["gc"]["gen2"]["n"] == 1 and tot["dropped"] == 0
    assert tot["gc"]["gen2"]["max_s"] == pytest.approx(rec["dur_s"])
    end = rec["t0"] + rec["dur_s"]
    assert obs.stall_totals(rec["t0"], end + 1e-6)["gc"]["gen2"]["n"] == 1
    assert obs.stall_totals(end + 1e-6, None)["gc"]["gen2"]["n"] == 0


def test_a_short_collection_goes_to_the_totals_only():
    _arm()
    t = obs.tracer()
    with t.trace("query"):
        with obs.span("scan") as sp:
            gc.collect(0)
            now = time.perf_counter()
    assert sp.attrs["gc_n"] >= 1
    assert [r for r in obs.stalls() if r["kind"] == "gc"] == []
    assert obs.stall_totals()["gc"]["gen0"]["n"] >= 1
    assert obs.stall_totals(now - 1.0, now + 1.0)["gc"]["gen0"]["n"] >= 1
    assert obs.stall_totals(now + 1.0, None)["gc"]["gen0"]["n"] == 0


def test_collection_under_the_registrys_and_the_tracers_lock_returns():
    """The hook runs wherever the thread is: it takes no lock and calls
    no registry. Under the lock witness, holding each of the two
    innermost locks in turn, a collection returns and no acquisition is
    witnessed beneath them."""
    lockwitness.enable()
    try:
        reg = MetricsRegistry()
        t = obs.install(obs.Tracer(metrics=reg))
        _arm()
        out = {}

        def body():
            with t.trace("query"):
                with obs.span("decode") as sp:
                    with t._lock:
                        assert lockwitness.held_locks() == ("Tracer._lock",)
                        _collect()
                    with reg._lock:
                        assert lockwitness.held_locks() == ("MetricsRegistry._lock",)
                        _collect()
                    out["attrs"] = dict(sp.attrs)

        th = threading.Thread(target=body, daemon=True)
        th.start()
        th.join(60)
        assert not th.is_alive(), "a collection under a held lock did not return"
        assert out["attrs"]["gc_n"] == 2
        assert lockwitness.REPORT.snapshot()["edges"] == []
    finally:
        lockwitness.disable()


# -- the compiler's hook ----------------------------------------------------


def test_first_call_of_a_jitted_function_is_three_records_and_one_count():
    import jax
    import jax.numpy as jnp

    ds = DataStore(metrics=MetricsRegistry())  # the first DataStore hooks the compiler
    x = jnp.arange(8.0) + 1.0
    x.block_until_ready()
    _arm()
    otrace.clear_stalls()

    def stalls_probe_fn(v):
        return v * 3.0 - 1.0

    fn = jax.jit(stalls_probe_fn)
    t = obs.tracer()
    with t.trace("query") as tr:
        with obs.span("dispatch") as sp:
            fn(x).block_until_ready()
    assert sp.attrs["compile_n"] == 1 and sp.attrs["compile_s"] > 0
    mine = [r for r in obs.stalls() if r["kind"] == "compile" and r["name"] == "stalls_probe_fn"]
    assert [r["phase"] for r in mine] == ["trace", "lower", "backend"]
    assert all(r["trace_id"] == tr.trace_id and r["tid"] == threading.get_ident() for r in mine)
    assert mine[-1]["root"] == "query"
    tot = obs.stall_totals()
    assert tot["compiled"] == 1
    prog = tot["compile"]["stalls_probe_fn"]
    assert prog["calls"] == 1 and prog["backend"] == pytest.approx(mine[-1]["dur_s"])
    # a program is three records: the ``multiply`` and ``subtract`` traced inside its
    # trace are that trace's time, not records (nor seconds) of their own
    assert [r for r in obs.stalls() if r["kind"] == "compile"] == mine
    assert sp.attrs["compile_s"] == pytest.approx(sum(r["dur_s"] for r in mine))
    assert any("stalled gc" in line and "compile" in line for line in obs.phase_breakdown(tr))
    n = len(obs.stalls())
    with t.trace("query"):
        with obs.span("dispatch") as again:
            fn(x).block_until_ready()
    assert len([r for r in obs.stalls() if r["kind"] == "compile"]) == len(
        [r for r in obs.stalls()[:n] if r["kind"] == "compile"])
    assert "compile_n" not in (again.attrs or {})
    assert obs.stall_totals()["compiled"] == 1
    # a compile under no request's root is recorded and not counted against one
    with t.trace("flush"):
        jax.jit(lambda v: v + 2.0)(x).block_until_ready()
    assert obs.stall_totals()["compiled"] == 1
    code, _, text = OpsRoutes(ds).handle("/metrics", {})
    assert code == 200 and "geomesa_query_compiled 1\n" in text
    ds.close()


# -- the record is the process's ---------------------------------------------


def test_install_and_reset_keep_the_ring_and_clear_stalls_empties_it():
    _arm()
    with obs.tracer().trace("query"):
        _collect()
    assert len(obs.stalls()) == 1
    obs.install(obs.Tracer())
    obs.tracer().reset()
    assert len(obs.stalls()) == 1 and obs.stall_totals()["gc"]["gen2"]["n"] == 1
    otrace.clear_stalls()
    assert obs.stalls() == [] and obs.stall_totals()["gc"]["gen2"]["n"] == 0


def test_the_ring_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(otrace, "STALL_RING", 4)
    otrace.clear_stalls()
    for k in range(6):
        otrace._on_compile("/jax/core/compile/backend_compile_duration", 0.001 * (k + 1),
                           fun_name=f"jit(p{k})")
    ring = obs.stalls()
    assert [r["name"] for r in ring] == ["p2", "p3", "p4", "p5"]
    assert obs.stall_totals()["dropped"] == 2
    # the totals of the process outlive the ring
    assert len(obs.stall_totals()["compile"]) == 6


# -- surfaces ------------------------------------------------------------------


def test_metrics_and_debug_stalls_serve_the_record():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_metrics import _parse_openmetrics

    _arm()
    ds = DataStore(metrics=MetricsRegistry())
    with obs.tracer().trace("query"):
        _collect()
    otrace._on_compile("/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="scan")
    otrace._on_compile("/jax/core/compile/backend_compile_duration", 0.5, fun_name="jit(scan)")
    routes = OpsRoutes(ds)
    code, ctype, text = routes.handle("/metrics", {})
    assert code == 200 and ctype.startswith("text/plain")
    fams = _parse_openmetrics(text)
    kind, samples = fams["geomesa_runtime_gc_collections"]
    assert kind == "counter" and ("geomesa_runtime_gc_collections", 'generation="2"', 1.0) in samples
    assert fams["geomesa_runtime_gc_seconds"][0] == "counter"
    kind, (only,) = fams["geomesa_runtime_gc_max_seconds"]
    assert kind == "gauge" and only[2] >= otrace.GC_RING_S
    by_phase = {s[1]: s[2] for s in fams["geomesa_runtime_compile_seconds"][1]}
    assert by_phase == {'phase="trace"': 0.25, 'phase="lower"': 0.0, 'phase="backend"': 0.5}
    assert fams["geomesa_runtime_compile_programs"][1][0][2] == 1
    assert fams["geomesa_query_compiled"][1][0][2] == 0
    # pulled as the scrape renders: nothing of it sits in the registry
    snap = ds.metrics.snapshot()
    assert not [k for kind in ("counters", "gauges") for k in snap[kind] if ".runtime." in k]
    code, ctype, body = routes.handle("/debug/stalls", {})
    ring = json.loads(body)["stalls"]  # beside ``lock``: tests/test_lock_record.py
    assert code == 200 and [r["kind"] for r in ring] == ["gc", "compile", "compile"]
    assert json.loads(routes.handle("/debug/stalls", {"n": ["1"]})[2])["stalls"] == ring[-1:]
    assert "/debug/stalls" in OpsRoutes.PATHS
    ds.close()


def test_chrome_payload_holds_the_stalls_on_their_threads_lanes():
    _arm()
    with obs.tracer().trace("query"):
        _collect()
    events = obs.tracer().chrome_payload()["traceEvents"]
    (pause,) = [e for e in events if e["name"] == "gc:gen2"]
    assert pause["ph"] == "X" and pause["tid"] == threading.get_ident() and pause["dur"] >= 1e3
    (root,) = [e for e in events if e["name"] == "query"]
    assert root["ts"] <= pause["ts"] + 1e3 and root["args"]["gc_wait_s"] > 0


def test_a_pause_is_a_profiler_annotation_once_the_profiler_is_loaded(monkeypatch):
    seen = []

    class FakeProfiler:
        class TraceAnnotation:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                seen.append(("in", self.name))

            def __exit__(self, *exc):
                seen.append(("out", self.name))

    _arm()
    with obs.tracer().trace("query"):
        monkeypatch.setattr(otrace, "_profiler", None)
        _collect()
        assert seen == []
        monkeypatch.setattr(otrace, "_profiler", FakeProfiler)
        gc.collect(0)  # generation 0: hundreds a second, never an annotation
        assert seen == []
        _collect()
    assert seen == [("in", "geomesa:gc.gen2"), ("out", "geomesa:gc.gen2")]


def test_disarmed_a_root_installs_no_hook(monkeypatch):
    if otrace._on_gc in gc.callbacks:
        gc.callbacks.remove(otrace._on_gc)
    monkeypatch.setattr(otrace, "_gc_hooked", False)
    conf.OBS_TRACE_SAMPLE.set(0)
    conf.OBS_SLOW_MS.set(0.0)
    t = obs.tracer()
    with t.trace("query") as tr:
        assert tr is None and obs.span("scan") is NULL_SPAN
        gc.collect()
    assert otrace._on_gc not in gc.callbacks and not otrace._gc_hooked
    assert obs.stall_totals()["gc"]["gen2"]["n"] == 0
    _arm(sample=0, slow_ms=1000.0)  # the default: the slow log alone arms it
    with t.trace("query"):
        pass
    assert gc.callbacks.count(otrace._on_gc) == 1
    with t.trace("query"):
        pass
    assert gc.callbacks.count(otrace._on_gc) == 1


def test_importing_the_tracer_does_not_import_jax():
    import subprocess

    code = ("import sys, importlib.util as u; sys.modules['geomesa_tpu'] = type(sys)('geomesa_tpu');"
            "sys.modules['geomesa_tpu'].__path__ = [sys.argv[1]];"
            "import geomesa_tpu.obs.trace as t; t.tracer().trace('query').__enter__();"
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code, os.path.join(ROOT, "geomesa_tpu")],
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == "False", out.stderr


# -- the benchmark's readers -------------------------------------------------------


@pytest.fixture(scope="module")
def readers():
    """The six readers, imported as the benchmark imports them."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        yield {n: importlib.import_module("layer_metrics." + n) for n in NEW}
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


def _root(i, name, wall_ms, **attrs):
    return {"trace": i, "root": name, "id": i, "parent": None, "name": name, "t0": 0.0,
            "dur_s": wall_ms / 1e3, "self_s": wall_ms / 1e3, "attrs": attrs}


def _view(workload="gdelt.analyst"):
    """A window of 100..110 s. Forty roots: the two slowest are one that
    waited 30% of its wall for the collector and one that waited 5%."""
    spans = [_root(1, "query_many", 200.0, gc_wait_s=0.060),
             _root(2, "query_many", 180.0, gc_wait_s=0.009)]
    spans += [_root(10 + k, "query", 5.0 + 0.1 * k) for k in range(37)]
    spans += [_root(90, "density", 4.0, gc_wait_s=0.004)]
    spans += [dict(s) for s in spans]  # the harness lists a root twice
    spans += [{"trace": 1, "root": "query_many", "id": 200, "parent": 1, "name": "plan",
               "t0": 0.0, "dur_s": 0.5, "self_s": 0.5, "attrs": {"gc_s": 0.06, "gc_n": 1}}]
    return {"workload": workload, "spans": spans, "device": None, "perf_window": (100.0, 110.0),
            "client": {"query_ms": []}}


def _record(monkeypatch):
    """Set-up compiled two programs; the window held one collection of
    0.2 s, one of 2 ms and a thousand short ones of generation 0."""
    otrace.clear_stalls()
    rec = otrace._stalls
    for k, (name, phase, dur, end, extra) in enumerate([
            ("scan", "trace", 0.5, 50.0, {}), ("scan", "lower", 1.5, 52.0, {}),
            ("scan", "backend", 4.0, 56.0, {"cache": "miss"}),
            ("density", "trace", 0.25, 60.0, {}), ("density", "lower", 0.75, 61.0, {}),
            ("density", "backend", 0.5, 62.0, {"cache": "hit"}),
            ("late", "lower", 9.0, 105.0, {})]):  # inside the window: not set-up's
        rec.ring.append({"seq": k, "kind": "compile", "name": name, "phase": phase,
                         "t0": end - dur, "dur_s": dur, "tid": 1,
                         "trace_id": None, **extra})
    for k, (gen, dur, end) in enumerate([("gen2", 0.3, 99.0), ("gen2", 0.2, 103.0),
                                         ("gen1", 0.002, 104.0), ("gen2", 0.4, 110.5)]):
        rec.ring.append({"seq": 7 + k, "kind": "gc", "name": gen, "t0": end - dur,
                         "dur_s": dur, "tid": 1, "trace_id": None, "collected": 0})
    rec.gc_short[int(105.0 / otrace._SLICE_S)] = [1000, 0.098, 0, 0.0, 0, 0.0]
    rec.gc_short[int(95.0 / otrace._SLICE_S)] = [500, 0.05, 0, 0.0, 0, 0.0]


WANT = {"gc_pause_pct": 100.0 * (0.2 + 0.002 + 0.098) / 10.0, "gc_max_ms": 200.0,
        "gc_tail_pct": 50.0, "idle_gc_pct": None, "compile_host_s": 0.5 + 1.5 + 0.25 + 0.75,
        "compile_top_s": 6.0}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_made_up_record(name, readers, monkeypatch):
    _record(monkeypatch)
    got = readers[name].read(_view())
    assert got is None if WANT[name] is None else got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_hooks(name, readers, monkeypatch):
    """The parent of PR 35: ``obs.trace`` has no ``stall_totals``, no root
    has ``gc_wait_s``, the trace holds no ``geomesa:gc.`` event."""
    monkeypatch.delattr(otrace, "stall_totals")
    view = _view()
    for s in view["spans"]:
        s["attrs"] = {}
    assert readers[name].read(view) is None


def test_gc_tail_reads_the_http_roots_where_there_are_any(readers, monkeypatch):
    _record(monkeypatch)
    view = _view("gdelt.dashboard")
    view["spans"] += [_root(300 + k, "http", 300.0 - k, gc_wait_s=0.1 if k < 3 else 0.0)
                      for k in range(100)]
    # the slowest five of a hundred http roots, three of them the collector's
    assert readers["gc_tail_pct"].read(view) == pytest.approx(60.0)
    otrace.clear_stalls()
    assert readers["gc_max_ms"].read(view) == 0.0 and readers["gc_pause_pct"].read(view) == 0.0
    assert readers["compile_top_s"].read(view) == 0.0


def test_idle_gc_share_on_hand_made_events(readers):
    """benchmark/tests/test_span_readers.py's idle case: window 0..1000,
    busy 100..200 and 600..700, so idle 800; collections over 0..150,
    400..500, 450..650 and (outside the window) 1200..1300 cover 300."""
    from layer_metrics import idle_gc_pct, idle_named_pct

    assert idle_gc_pct.share is idle_named_pct.share and idle_gc_pct.PREFIX == "geomesa:gc."
    busy = [(100.0, 200.0), (600.0, 700.0)]
    pauses = [(0.0, 150.0), (400.0, 500.0), (450.0, 650.0), (1200.0, 1300.0)]
    assert idle_gc_pct.share(busy, pauses, (0.0, 1000.0)) == pytest.approx(37.5)
    assert idle_gc_pct.share(busy, [], (0.0, 1000.0)) is None


def test_idle_gc_reads_nothing_without_a_trace(readers, tmp_path, monkeypatch):
    monkeypatch.setattr(readers["idle_gc_pct"], "OUT_DIR", str(tmp_path))
    view = dict(_view(), device={"window_s": 1.0, "busy_s": 0.1})
    assert readers["idle_gc_pct"].read(view) is None


def test_the_six_readers_are_entries_of_the_benchmark():
    """By name, not by place: a later PR appends its own after them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = [m for m in bench["per_layer"] if m["layer"] == "host runtime"]
    assert [m["name"] for m in mine[:len(NEW)]] == NEW
    cells = {w["name"] for w in bench["workloads"]}
    assert all(set(m["workloads"]) <= cells for m in mine)
    assert all(len(m["workloads"]) >= 5 for m in mine[:len(NEW)])
    for m in mine:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
