"""The interpreter lock under the program's own instruments
(docs/observability.md "The interpreter lock"): one process-wide record in
``obs/trace.py`` beside the stall record, fed by four instruments, and the
benchmark's seven readers of it over hand-made views.

- **the hand-off probe**: its median reads under a millisecond with no
  other runnable thread and over two beside a thread spinning in Python
  (the switch interval is 5), through the native nap and through the
  ``time.sleep`` fallback alike; ring and totals cut by ``t_lo`` / ``t_hi``;
- **arming**: with sample 0 and no ops plane no thread named
  ``geomesa-lockprobe`` exists after a query; a retained root or
  ``serve_ops`` starts exactly one;
- **the CPU ledger**: the roles sum to ``process_time`` for a pure-Python
  spin on a ``caller`` and a ``handler`` thread, and a thread that ended
  keeps its seconds, retired or not;
- **hand-offs on the spans**: a native call, a device wait, a response's
  writes, each on the span it happens under, and nothing on a thread
  without one; root ``cpu_s`` on retained traces only;
- **the native stamp**: every exported function ends through the guard,
  ``_call`` is the one door, the stamps lie on ``perf_counter``'s clock,
  ``native_s`` + ``reacquire_s`` never exceed the call's wall and
  ``reacquire_s`` grows beside a spinning thread;
- **surfaces**: ``/debug/stalls``, ``/metrics``, the explain trail's line;
- **the record is the process's**, takes no lock and adds no knob.
"""

import importlib
import json
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

from geomesa_tpu import conf, lockwitness, native, obs
from geomesa_tpu.analysis import lockmodel
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.obs import trace as otrace
from geomesa_tpu.obs.ops import OpsRoutes
from geomesa_tpu.serving import DataClient
from geomesa_tpu.sft import FeatureType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BENCH_PACKAGES = ("harness", "layer_metrics")
NEW = ["lock_handoff_ms", "lock_handoff_tail_ms", "python_cpu_cores", "request_cpu_ms",
       "dispatcher_cpu_pct", "handoffs_per_request", "native_reacquire_ms"]
PROBE = "geomesa-lockprobe"

needs_native = pytest.mark.skipif(not native.available(), reason="no native tier")


@pytest.fixture(autouse=True)
def _fresh():
    """A fresh tracer, an empty lock record with no probe running, and
    restored knobs."""
    obs.install(obs.Tracer())
    otrace.clear_lock()
    yield
    for knob in (conf.OBS_TRACE_SAMPLE, conf.OBS_SLOW_MS):
        knob.clear()
    obs.install(obs.Tracer())
    otrace.clear_lock()


def _arm(sample=1, slow_ms=0.0):
    conf.OBS_TRACE_SAMPLE.set(sample)
    conf.OBS_SLOW_MS.set(slow_ms)


def _probes():
    return [t for t in threading.enumerate() if t.name == PROBE]


class _Spinner:
    """A thread that does nothing but run Python: it holds the interpreter
    lock for a whole switch interval at a time."""

    def __init__(self, role=None, retire=True):
        self.stop = False
        self.started = threading.Event()
        body = self._spin
        if role is not None:
            body = otrace.as_role(role, body) if retire else self._named(role)
        self.thread = threading.Thread(target=body, daemon=True)

    def _named(self, role):
        def run():
            otrace.name_role(role)  # and never retires: it just ends
            self._spin()

        return run

    def _spin(self):
        self.started.set()
        x = 0
        while not self.stop:
            x += 1

    def __enter__(self):
        self.thread.start()
        assert self.started.wait(30)
        return self

    def __exit__(self, *exc):
        self.stop = True
        self.thread.join(30)
        assert not self.thread.is_alive()


def _window(seconds):
    t0 = time.perf_counter()
    time.sleep(seconds)
    return t0, time.perf_counter()


def _wait_for_ledger(n_more=1, timeout=30.0):
    """Until the ledger holds ``n_more`` samples more than now."""
    want = obs.lock_cpu()["samples"] + n_more
    deadline = time.monotonic() + timeout
    while obs.lock_cpu()["samples"] < want:
        assert time.monotonic() < deadline, "the probe took no ledger sample"
        time.sleep(0.02)


# -- the hand-off probe -------------------------------------------------------


@pytest.mark.parametrize("route", ["native", "sleep"])
def test_probe_reads_the_switch_interval_beside_a_spinner_and_little_alone(route, monkeypatch):
    if route == "native" and not native.available():
        pytest.skip("no native tier")
    if route == "sleep":
        monkeypatch.setattr(native, "nap", lambda seconds: None)
    otrace.arm_lock_probe()
    # alone: a shared test machine may starve one window; one quiet one is enough
    for _ in range(6):
        idle = obs.lock_probe(*_window(0.3))
        if idle["n"] >= 10 and idle["p50_s"] < 1e-3:
            break
    assert idle["n"] >= 10 and idle["p50_s"] < 1e-3, idle
    assert idle["exact"] is (route == "native")
    with _Spinner():
        t0, t1 = _window(0.6)
    busy = obs.lock_probe(t0, t1)
    assert busy["n"] >= 10 and busy["p50_s"] > 2e-3, busy
    assert busy["p95_s"] >= busy["p50_s"] and busy["max_s"] >= busy["p95_s"]
    assert busy["sum_s"] == pytest.approx(
        sum(s["wait_s"] for s in obs.lock_probe(t0, t1, newest=10 ** 6)["samples"]))
    # the cut is by the sample's time: nothing before the probe, nothing after now
    assert obs.lock_probe(None, t0 - 3600.0)["n"] == 0
    assert obs.lock_probe(time.perf_counter() + 1.0, None)["n"] == 0
    total = obs.lock_probe()
    assert total["n"] >= idle["n"] + busy["n"] and total["max_s"] >= busy["max_s"]
    assert obs.lock_probe(None, t1)["n"] + obs.lock_probe(t1, None)["n"] == pytest.approx(
        obs.lock_probe()["n"], abs=3)  # the probe goes on sampling between the three calls
    newest = obs.lock_probe(newest=5)["samples"]
    assert len(newest) == 5 and newest == sorted(newest, key=lambda s: s["t"])


def test_probe_ring_is_bounded_and_the_totals_outlive_it(monkeypatch):
    monkeypatch.setattr(otrace, "PROBE_RING", 8)
    otrace.clear_lock()
    otrace.arm_lock_probe()
    deadline = time.monotonic() + 30
    while obs.lock_probe()["n"] < 12:
        assert time.monotonic() < deadline
        time.sleep(0.02)
    assert len(otrace._lock.probe) == 8 and obs.lock_probe()["n"] >= 12
    assert obs.lock_probe(None, time.perf_counter() + 1.0)["n"] == 8


# -- arming ---------------------------------------------------------------------


def _tiny_store(n=64, **kw):
    sft = FeatureType.from_spec("t", "name:String,dtg:Date,*geom:Point:srid=4326")
    ds = DataStore(tile=64, **kw)
    ds.create_schema(sft)
    ds.write("t", FeatureCollection.from_columns(sft, [f"f{i}" for i in range(n)], {
        "name": np.array([f"n{i % 7}" for i in range(n)]),
        "dtg": 1_704_067_200_000 + np.arange(n) * 1000,
        "geom": (np.linspace(-5, 5, n), np.linspace(-4, 4, n))}))
    return ds


BOX = "BBOX(geom, -6, -5, 6, 5)"


def test_without_a_retained_root_or_an_ops_plane_the_probe_never_exists():
    _arm(sample=0, slow_ms=1000.0)  # the driver's untraced runs: the slow log alone
    otrace._lock.threads.pop(threading.get_native_id(), None)  # an earlier test's name
    otrace._tls.role = None
    before = set(otrace._lock.threads)
    ds = _tiny_store()
    srv = ds.serve(port=0)  # the data plane is no ops plane
    try:
        assert len(ds.query("t", BOX)) == 64
        assert DataClient(srv.url).query("t", cql=BOX)
    finally:
        ds.close()
    assert _probes() == []
    assert obs.lock_probe()["n"] == 0 and obs.lock_cpu()["samples"] == 0
    # nobody reads the ledger: this thread was not named a ``caller``, and the
    # handlers and the dispatcher took their names with them
    assert set(otrace._lock.threads) <= before and otrace._tls.role is None


@pytest.mark.parametrize("by", ["retained root", "serve_ops"])
def test_a_retained_root_or_serve_ops_starts_exactly_one_probe(by):
    ds = _tiny_store()
    try:
        if by == "serve_ops":
            _arm(sample=0, slow_ms=0.0)
            ds.serve_ops()
            ds.serve_ops()
        else:
            _arm(sample=2)  # every second root is kept
            ds.query("t", BOX)
            assert _probes() == []  # the first was sampled out
            for _ in range(4):
                ds.query("t", BOX)
        assert len(_probes()) == 1
        _wait_for_ledger(0)
        assert obs.lock_cpu()["threads"]["probe"] == 1
    finally:
        ds.close()
    otrace.clear_lock()
    assert _probes() == []  # a clear ends it; the next arming starts a new one
    otrace.arm_lock_probe()
    otrace.arm_lock_probe()
    assert len(_probes()) == 1


def test_install_and_reset_keep_the_record_and_clear_lock_empties_it():
    otrace.arm_lock_probe()
    _wait_for_ledger(1)
    obs.install(obs.Tracer())
    obs.tracer().reset()
    assert obs.lock_probe()["n"] > 0 and obs.lock_cpu()["samples"] >= 2 and _probes()
    otrace.clear_lock()
    assert obs.lock_probe() == {"n": 0, "sum_s": 0.0, "max_s": 0.0, "p50_s": 0.0,
                                "p95_s": 0.0, "exact": None}
    assert obs.lock_cpu() == {"cpu_s": {}, "threads": {}, "process_s": 0.0, "samples": 0}


# -- the CPU ledger -----------------------------------------------------------


def test_ledger_roles_sum_to_process_time_for_a_spin_on_a_caller_and_a_handler():
    _arm()
    with obs.tracer().trace("query"):  # this thread becomes a ``caller``; the probe starts
        pass
    _wait_for_ledger(1)
    with _Spinner("handler"):
        _wait_for_ledger(1)  # the probe has seen the handler
        t0 = time.perf_counter()
        x, until = 0, time.monotonic() + 0.8
        while time.monotonic() < until:
            x += 1
        t1 = time.perf_counter()
        _wait_for_ledger(1)  # a sample past t1 to interpolate against
        got = obs.lock_cpu(t0, t1)
        # (at least: another test file's store may have left a thread behind)
        assert got["threads"]["probe"] == 1 and got["threads"]["caller"] >= 1
        assert got["threads"]["handler"] >= 1
    roles = got["cpu_s"]
    assert {"caller", "handler", "probe"} <= set(roles)
    assert roles["caller"] > 0.1 and roles["handler"] > 0.1 and roles["probe"] < 0.1
    # two spinning threads share one interpreter: together about one core, never two
    assert 0.5 * (t1 - t0) < roles["caller"] + roles["handler"] < 1.3 * (t1 - t0)
    # what the process used, its named threads used: within a tick and
    # whatever an unnamed thread of the test runner did meanwhile
    assert sum(roles.values()) == pytest.approx(got["process_s"], abs=0.06)
    # a window before the first sample holds nothing
    assert sum(obs.lock_cpu(t0 - 7200.0, t0 - 3600.0)["cpu_s"].values()) == 0.0


@pytest.mark.parametrize("end", ["retires", "vanishes"])
def test_a_thread_that_ended_keeps_its_seconds(end):
    otrace.arm_lock_probe()
    _wait_for_ledger(1)
    role = "spinner-" + end  # a role is any name: this one is nobody else's
    with _Spinner(role, retire=(end == "retires")):
        _wait_for_ledger(3)
        live = obs.lock_cpu()
        assert live["threads"][role] == 1 and live["cpu_s"][role] > 0.1
    _wait_for_ledger(2)
    after = obs.lock_cpu()
    assert role not in after["threads"]
    # retired: its own last reading; vanished: the probe's last reading of it
    assert after["cpu_s"][role] >= live["cpu_s"][role]
    assert not [e for e in otrace._lock.threads.values() if e[0] == role]
    _wait_for_ledger(1)
    assert obs.lock_cpu()["cpu_s"][role] == after["cpu_s"][role]  # counted once


def test_every_long_lived_thread_of_a_served_store_names_its_role():
    _arm(sample=0, slow_ms=0.0)
    ds = _tiny_store(metrics=MetricsRegistry())
    srv = ds.serve(port=0)
    ds.serve_ops()
    try:
        client = DataClient(srv.url)
        for _ in range(3):
            client.query("t", cql=BOX)
        _wait_for_ledger(2)
        threads = obs.lock_cpu()["threads"]
    finally:
        ds.close()
    assert threads["dispatcher"] >= 1 and threads["probe"] == 1
    assert threads["handler"] >= 1 and threads["ops"] >= 2  # accept loops, the recorder


# -- hand-offs on the spans -------------------------------------------------------


@needs_native
def test_handoffs_count_a_native_call_under_its_span_and_nothing_without_one():
    _arm()
    xs = np.arange(1000, dtype=np.uint64)
    native.morton2(xs, xs)  # no span on this thread: counted nowhere, and no error
    otrace.add("handoffs", 1)
    with obs.tracer().trace("query") as tr:
        with obs.span("decode") as sp:
            native.morton2(xs, xs)
            native.morton3(xs, xs, xs)
        native.morton2(xs, xs)
    assert sp.attrs["handoffs"] == 2 and sp.attrs["native_n"] == 2
    assert tr.root.attrs["handoffs"] == 1 and tr.root.attrs["native_n"] == 1
    assert [t.name for t in obs.tracer().traces()] == ["query"]


def test_handoffs_count_a_device_wait_under_its_span():
    import jax.numpy as jnp

    from geomesa_tpu.storage.table import _await_device

    _arm()
    arr = jnp.arange(8) + 1
    assert _await_device(arr) is False  # untraced: nothing
    with obs.tracer().trace("query"):
        with obs.span("scan") as sp:
            assert _await_device(arr) is True
    assert sp.attrs["handoffs"] == 2 and set(sp.attrs["segments"]) == {"wait", "pull"}


@pytest.mark.parametrize("fmt", ["geojson", "arrow"])
def test_handoffs_count_a_responses_writes_and_its_wait_on_the_future(fmt):
    if fmt == "arrow":
        pytest.importorskip("pyarrow")
    _arm()
    ds = _tiny_store(n=200)
    srv = ds.serve(port=0)
    try:
        DataClient(srv.url).query("t", cql=BOX, fmt=fmt, page_rows=64)
    finally:
        ds.close()
    (http,) = [tr for tr in obs.tracer().traces() if tr.name == "http"]
    by_name = {s.name: s for s in http.spans}
    enc = by_name["encode"].attrs
    extra = 0
    if fmt == "arrow":  # pyarrow's four calls, the native import, a serialize a later page
        assert enc["chunks"] == 5  # four pages and the end-of-stream marker
        extra = 4 + enc["arrow_native"] + 3
    natives = enc.get("native_n", 0)  # the serializer's own native calls
    assert enc["handoffs"] == enc["chunks"] + 1 + extra + natives
    assert by_name["http.wait"].attrs["handoffs"] == 1
    assert http.root.attrs["handoffs"] == 1  # the headers' write
    assert "cpu_s" in http.root.attrs
    # the same request's ``query`` root crossed threads: no CPU clock on it
    (query,) = [tr for tr in obs.tracer().traces() if tr.name == "query"]
    assert "cpu_s" not in (query.root.attrs or {})


@needs_native
def test_root_cpu_and_the_native_stamp_are_for_retained_traces_only():
    xs = np.arange(1000, dtype=np.uint64)
    _arm(sample=0, slow_ms=1000.0)  # a tree built for the slow log alone
    with obs.tracer().trace("query") as tr:
        native.morton2(xs, xs)
    assert tr is not None and tr.root.attrs == {"handoffs": 1}
    _arm(sample=1)
    with obs.tracer().trace("query") as tr:
        native.morton2(xs, xs)
    assert {"cpu_s", "handoffs", "native_s", "reacquire_s", "native_n"} <= set(tr.root.attrs)


def test_explain_trail_shows_a_requests_handoffs_where_it_has_any():
    _arm()
    with obs.tracer().trace("query") as tr:
        with obs.span("scan") as sp:
            sp.add("handoffs", 2)
        otrace.add("handoffs", 1)
        lines = obs.phase_breakdown(tr) or []
    with obs.tracer().trace("query") as quiet:
        with obs.span("scan"):
            pass
    tr.root.dur_s = quiet.root.dur_s = 1.0
    assert obs.phase_breakdown(tr)[-1] == (
        "trace: stalled gc 0.000ms, compile 0.000ms, handoffs 3")
    assert not [ln for ln in obs.phase_breakdown(quiet) if "stalled" in ln] and lines == []


# -- the native stamp -------------------------------------------------------------


def test_every_exported_function_ends_through_the_guard_and_call_is_the_one_door():
    with open(os.path.join(ROOT, "geomesa_tpu", "native", "geomesa_native.cpp")) as fh:
        cpp = fh.read()
    block = cpp[cpp.index('extern "C" {'):cpp.index('}  // extern "C"')]
    heads = re.findall(r'^extern "C" [\w ]+?(\w+)\(', cpp, re.M)
    heads += re.findall(r"^(?!static)[a-z]\w+ (\w+)\(", block, re.M)
    assert len(heads) == 31 and {"nap", "stamp_entry", "stamp_return"} <= set(heads)
    for name in heads:
        at = re.search(r"\b%s\([^{;]*\{\s*([^\n]*)" % name, cpp).group(1)
        if name.startswith("stamp_"):
            assert at.startswith("return g_stamp_")  # the getters read, and stamp nothing
        else:
            assert at == "Stamp stamp_;", name
    with open(os.path.join(ROOT, "geomesa_tpu", "native", "__init__.py")) as fh:
        py = fh.read()
    direct = re.findall(r"(?<![\w.])lib\.(\w+)\(", py)
    direct.remove("with_name")  # ``lib`` the path, in ``_build``
    assert direct == ["nap"], direct  # every other call goes through _call
    assert len(re.findall(r"\b_call\(", py)) == 23  # 22 call sites and the definition


@needs_native
def test_the_stamps_lie_on_perf_counters_clock_and_inside_the_calls_wall():
    assert time.get_clock_info("perf_counter").implementation == "clock_gettime(CLOCK_MONOTONIC)"
    entry, back = native._stamps
    t0 = time.perf_counter()
    waited = native.nap(0.02)
    t1 = time.perf_counter()
    assert t0 <= entry() <= back() <= t1
    assert back() - entry() >= 0.02 and 0.0 <= waited <= t1 - back() + 1e-9
    _arm()
    xs = np.arange(200_000, dtype=np.uint64)
    with obs.tracer().trace("query") as tr:
        t0 = time.perf_counter()
        for _ in range(5):
            native.morton2(xs, xs)
        wall = time.perf_counter() - t0
    a = tr.root.attrs
    assert a["native_n"] == 5 and a["native_s"] > 0 and a["reacquire_s"] >= 0
    assert a["native_s"] + a["reacquire_s"] <= wall


@needs_native
def test_reacquire_grows_beside_a_spinning_thread():
    _arm()
    # a release has to last as long as a real call's: after one of a few
    # microseconds the caller has the lock back before the waiter has woken
    xs = np.arange(1 << 21, dtype=np.uint64)

    def mean_wait():
        with obs.tracer().trace("query") as tr:
            for _ in range(10):
                native.morton2(xs, xs)
        return tr.root.attrs["reacquire_s"] / tr.root.attrs["native_n"]

    alone = min(mean_wait() for _ in range(3))
    with _Spinner():
        beside = max(mean_wait() for _ in range(3))
    assert alone < 0.5e-3 and beside > 1e-3 and beside > 4 * alone, (alone, beside)


# -- surfaces -------------------------------------------------------------------


def test_metrics_and_debug_stalls_serve_the_lock_record():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_metrics import _parse_openmetrics

    _arm()
    ds = DataStore(metrics=MetricsRegistry())
    with obs.tracer().trace("query"):
        pass
    _wait_for_ledger(2)
    routes = OpsRoutes(ds)
    code, ctype, text = routes.handle("/metrics", {})
    assert code == 200
    fams = _parse_openmetrics(text)
    assert fams["geomesa_runtime_lock_handoff_seconds"][0] == "counter"
    kind, ((_, _, n),) = fams["geomesa_runtime_lock_handoff_samples"]
    assert kind == "counter" and n >= 25
    assert fams["geomesa_runtime_lock_handoff_max_seconds"][0] == "gauge"
    kind, samples = fams["geomesa_runtime_cpu_seconds"]
    assert kind == "counter" and {'role="caller"', 'role="probe"'} <= {s[1] for s in samples}
    snap = ds.metrics.snapshot()  # pulled as the scrape renders: nothing in the registry
    assert not [k for kind in ("counters", "gauges") for k in snap[kind] if ".runtime." in k]
    code, _, body = routes.handle("/debug/stalls", {"n": ["3"]})
    got = json.loads(body)
    assert code == 200 and set(got) == {"stalls", "lock"}
    probe, cpu = got["lock"]["probe"], got["lock"]["cpu"]
    assert len(probe["samples"]) == 3 and set(probe["samples"][0]) == {"t", "wait_s"}
    assert probe["n"] >= 25 and {"sum_s", "p50_s", "p95_s", "max_s", "exact"} <= set(probe)
    assert {"caller", "probe"} <= set(cpu["cpu_s"]) and cpu["threads"]["probe"] == 1
    assert cpu["process_s"] > 0
    ds.close()


def test_the_record_takes_no_lock_and_adds_no_knob():
    """Written with the lock witness armed, under the tracer's and the
    registry's locks in turn: no acquisition is witnessed beneath them;
    ``analysis/lockmodel.py`` declares no lock for it and ``conf.py`` no
    property."""
    lockwitness.enable()
    try:
        reg = MetricsRegistry()
        t = obs.install(obs.Tracer(metrics=reg))
        _arm()
        with t.trace("query"):
            for held in (t._lock, reg._lock):
                with held:
                    otrace.add("handoffs", 1)
                    otrace.arm_lock_probe()
                    obs.lock_probe()
                    obs.lock_cpu()
        _wait_for_ledger(1)
        assert lockwitness.REPORT.snapshot()["edges"] == []
    finally:
        lockwitness.disable()
    assert not [n for n in lockmodel.LOCKS if "LockRecord" in n or "lock_probe" in n.lower()]
    assert "lockprobe" not in conf.describe().lower()
    assert ".obs.lock" not in conf.describe().lower()


# -- the benchmark's readers -------------------------------------------------------


@pytest.fixture(scope="module")
def readers():
    """The seven readers, imported as the benchmark imports them."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        yield {n: importlib.import_module("layer_metrics." + n) for n in NEW}
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


def _span(i, root, name, parent=None, **attrs):
    return {"trace": root, "root": "http", "id": i, "parent": parent, "name": name, "t0": 101.0,
            "dur_s": 0.01, "self_s": 0.01, "attrs": attrs}


def _view():
    """A window of 100..110 s in which the client counted 40 operations."""
    spans = []
    for k in range(40):
        root = _span(1000 + 10 * k, k, "http", handoffs=1)
        spans += [root, dict(root),  # the harness lists a root twice
                  _span(1001 + 10 * k, k, "http.wait", root["id"], handoffs=1),
                  _span(1002 + 10 * k, k, "encode", root["id"], handoffs=8, native_n=2,
                        native_s=0.004, reacquire_s=0.003)]
    return {"workload": "gdelt.dashboard", "spans": spans, "device": None,
            "perf_window": (100.0, 110.0), "seconds": 10.0,
            "client": {"query_ms": [5.0] * 40}}


def _record():
    """The probe sampled 1..100 ms inside the window and 0.5 s outside it;
    the ledger was read every 2 s from 98 to 112: handlers 0.3 cores,
    the dispatcher 0.1, a caller nothing, the probe 0.01."""
    otrace.clear_lock()
    rec = otrace._lock
    rec.probe.append((99.5, 0.5))
    for k in range(100):
        rec.probe.append((100.0 + 0.1 * k, 0.001 * (k + 1)))
    rec.probe.append((110.5, 0.5))
    for k in range(8):
        t = 98.0 + 2.0 * k
        rec.cpu.append((t, {"handler": 7.0 + 0.3 * t, "dispatcher": 0.1 * t, "caller": 3.0,
                            "probe": 0.01 * t},
                        50.0 + 0.5 * t, {"handler": 16, "dispatcher": 1, "caller": 1,
                                         "probe": 1}))


WANT = {"lock_handoff_ms": 51.0, "lock_handoff_tail_ms": 96.0, "python_cpu_cores": 0.4,
        "request_cpu_ms": 100.0, "dispatcher_cpu_pct": 25.0, "handoffs_per_request": 10.0,
        "native_reacquire_ms": 1.5}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_made_up_record(name, readers):
    _record()
    assert readers[name].read(_view()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_record(name, readers, monkeypatch):
    """The parent of PR 51: ``obs.trace`` has no ``lock_probe`` and no
    ``lock_cpu``, and no span counts a hand-off or carries a stamp."""
    _record()
    monkeypatch.delattr(otrace, "lock_probe")
    monkeypatch.delattr(otrace, "lock_cpu")
    view = _view()
    for s in view["spans"]:
        s["attrs"] = {}
    assert readers[name].read(view) is None


def test_readers_read_nothing_from_an_empty_record_or_an_empty_window(readers):
    otrace.clear_lock()  # the record is there, the probe never ran
    view = _view()
    for name in NEW[:5]:
        assert readers[name].read(view) is None
    _record()
    view["client"]["query_ms"] = []  # no operation ended in the window
    assert readers["request_cpu_ms"].read(view) is None
    assert readers["handoffs_per_request"].read(view) is None
    view = _view()
    for s in view["spans"]:
        s["attrs"].pop("native_n", None)  # no native call wrote a stamp
    assert readers["native_reacquire_ms"].read(view) is None
    embedded = _view()
    rec = otrace._lock
    rows = [(t, {r: v for r, v in by.items() if r != "dispatcher"}, p, n)
            for t, by, p, n in rec.cpu]
    rec.cpu.clear()
    rec.cpu.extend(rows)
    assert readers["dispatcher_cpu_pct"].read(embedded) is None  # no such role
    assert readers["python_cpu_cores"].read(embedded) == pytest.approx(0.3)


def test_the_seven_readers_are_entries_of_the_benchmark():
    """By name, not by place: a later PR appends its own after them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert list(mine) == NEW
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name, m in mine.items():
        assert m["layer"] == "host runtime" and m["moves"] in e2e and m["better"] == "lower"
        assert set(m["workloads"]) <= set(cells)
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert mine["dispatcher_cpu_pct"]["workloads"] == ["gdelt.dashboard", "gdelt.ingest-reads"]
    assert {"gdelt.dashboard", "gdelt.ingest-reads", "gdelt.analyst"} <= set(
        mine["native_reacquire_ms"]["workloads"])
    for name in ("lock_handoff_ms", "lock_handoff_tail_ms", "python_cpu_cores",
                 "request_cpu_ms", "handoffs_per_request"):
        # the nine cells PR 51 had, then whichever later cells list them (PR 53's does)
        assert mine[name]["workloads"][:9] == cells[:9]
        assert set(mine[name]["workloads"][9:]) <= set(cells[9:])
