"""The spans and counters of the two processes (PR 46; docs/processes.md,
docs/observability.md): ``knn_search`` / ``knn_many`` under ONE root
``knn`` a call, ``tube_select`` under ONE root ``tube`` whose query is
``DataStore.query``'s own ``query`` root, linked both ways.

(a) the attribute identities: a ``knn`` root's ``windows`` is its rounds'
    ``pending`` summed and ``rounds`` their number, ``candidates`` >=
    ``returned``, ``short`` counts the members answered under ``k``, every
    round holds one ``plan`` a pending member and one ``dispatch``; a
    ``tube`` root's ``kept`` <= ``rows`` <= ``candidates``, ``boxes`` /
    ``windows`` / ``ranges`` are the chosen plan's config (summed over the
    ``groups`` branches of a time-sliced union, PR 47), ``bins`` the slices;
    its ``query`` root holds one ``plan``, one ``dispatch`` and a ``scan`` +
    ``decode`` a branch;
(b) answers are identical with tracing on and off, ids and every column;
(c) with sampling off no span object is made;
(d) the boundary repair of PR 46: a row at exactly the track's last instant
    is in the answer whatever the bins' width.
"""

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.predicates import During, Slices
from geomesa_tpu.obs import trace as otrace
from geomesa_tpu.process import knn_many, knn_search, tube_select
from geomesa_tpu.process.tube import _slices as _tube_slices
from geomesa_tpu.sft import FeatureType

N = 20_000
T0 = 1_700_000_000_000
SPAN_MS = 6 * 3_600_000


@pytest.fixture(scope="module")
def store():
    """Reports round a harbour (dense) and along a line out of it (sparse),
    whole-second times over six hours; z3 + z2 as the AIS cell has them."""
    rng = np.random.default_rng(46)
    sft = FeatureType.from_spec("rep", "mmsi:Integer,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    dense = N * 3 // 4
    x = np.concatenate([rng.normal(-122.4, 0.01, dense), rng.uniform(-124.0, -122.4, N - dense)])
    y = np.concatenate([rng.normal(37.8, 0.01, dense), rng.uniform(37.0, 37.8, N - dense)])
    t = T0 + rng.integers(0, SPAN_MS // 1000, N) * 1000
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("rep", FeatureCollection.from_columns(
        sft, np.arange(N, dtype=np.int64),
        {"mmsi": rng.integers(0, 500, N).astype(np.int32), "dtg": t, "geom": (x, y)}),
        check_ids=False)
    return ds


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _all(trace):
    return list(trace.spans)  # the root is among them once it has finished


def _named(trace, name):
    return [s for s in _all(trace) if s.name == name]


def _children(trace, parent, name):
    return [s for s in _named(trace, name) if s.parent_id == parent.span_id]


def _same(a, b):
    assert np.array_equal(a.ids, b.ids)
    for name, col in a.columns.items():
        other = b.columns[name]
        if hasattr(col, "x"):
            assert np.array_equal(col.x, other.x) and np.array_equal(col.y, other.y)
        else:
            assert np.array_equal(col, other)


WINDOW = During("dtg", T0 + 3_600_000, T0 + 3 * 3_600_000)
#: (points, k, estimated_distance_m, max_distance_m): one round; several rounds
#: from a start radius far too short; members that end short of k at the limit
KNN_CASES = {
    "one-round": ([(-122.4, 37.8)], 16, 2_000.0, 100_000.0),
    "expands": ([(-123.6, 37.3), (-122.4, 37.8)], 16, 5.0, 200_000.0),
    "short": ([(-123.9, 37.05), (-110.0, 20.0)], 64, 100.0, 3_000.0),
    "many-16": ([(-124.0 + 0.1 * i, 37.0 + 0.05 * i) for i in range(16)], 8, None, 100_000.0),
}


@pytest.mark.parametrize("case", sorted(KNN_CASES))
def test_a_knn_roots_counters_are_its_rounds_summed(case, store, traced):
    points, k, est, limit = KNN_CASES[case]
    out = knn_many(store, "rep", points, k, estimated_distance_m=est, max_distance_m=limit,
                   filter=WINDOW)
    (tr,) = [t for t in traced.traces() if t.name == "knn"]
    a = tr.root.attrs
    rounds = _named(tr, "knn.round")
    assert a["members"] == len(points) and a["k"] == k
    assert a["rounds"] == len(rounds) >= 1
    assert a["windows"] == sum(r.attrs["pending"] for r in rounds)
    assert rounds[0].attrs["pending"] == len(points)
    assert a["returned"] == sum(len(fc) for fc in out) <= a["candidates"]
    assert a["short"] == sum(len(fc) < k for fc in out)
    for r in rounds:
        assert len(_children(tr, r, "plan")) == r.attrs["pending"]
        assert len(_children(tr, r, "dispatch")) == 1
        assert len(_children(tr, r, "knn.rank")) == r.attrs["pending"]
        assert r.attrs["radius_max_m"] <= limit
    ranks = _named(tr, "knn.rank")
    assert sum(s.attrs["rows"] for s in ranks) == a["candidates"]
    assert sum(s.attrs.get("candidates", 0) for s in _named(tr, "decode")) >= a["candidates"]
    (est_span,) = _named(tr, "knn.estimate")
    assert ("probes" in (est_span.attrs or {})) == (est is None)
    if case == "expands":
        assert a["rounds"] > 1 and a["windows"] > a["members"]
    if case == "short":
        assert a["short"] == 2 and all(r.attrs["radius_max_m"] <= limit for r in rounds)
    if case == "one-round":
        assert a["rounds"] == 1 and a["short"] == 0


def test_knn_search_is_one_root_of_one_member(store, traced):
    out = knn_search(store, "rep", -122.4, 37.8, 5, estimated_distance_m=500.0, filter=WINDOW)
    (tr,) = [t for t in traced.traces() if t.name == "knn"]
    assert tr.root.attrs["members"] == 1 and tr.root.attrs["returned"] == len(out) == 5


def _track(n, t_lo, t_hi, x0=-122.42, y0=37.79, x1=-123.2, y1=37.4):
    f = np.linspace(0.0, 1.0, n)
    t = (t_lo + f * (t_hi - t_lo)).astype(np.int64) // 1000 * 1000
    return np.stack([x0 + f * (x1 - x0), y0 + f * (y1 - y0)], 1), t


TUBE_CASES = {"slices-256": (360, 2_000.0), "slices-40": (40, 10_000.0), "one-slice": (2, 500.0)}


@pytest.mark.parametrize("case", sorted(TUBE_CASES))
def test_a_tube_roots_counters_are_its_querys(case, store, traced):
    n, buffer_m = TUBE_CASES[case]
    xy, t = _track(n, T0 + 600_000, T0 + 5 * 3_600_000)
    out = tube_select(store, "rep", xy, t, buffer_m)
    (tr,) = [x for x in traced.traces() if x.name == "tube"]
    (inner,) = [x for x in traced.traces() if x.name == "query"]
    a = tr.root.attrs
    assert a["waypoints"] == n and a["buffer_m"] == buffer_m
    assert a["kept"] == len(out) <= a["rows"] <= a["candidates"]
    # linked both ways, by the tracer
    assert inner.root.attrs["tube_trace"] == tr.trace_id
    assert a["query_trace"] == inner.trace_id
    assert a["candidates"] == sum(s.attrs["candidates"] for s in _named(inner, "decode"))
    # the chosen plan's configs, as a fresh plan of the same filter reads them:
    # one scan's, or past sixteen slices the union's branches' summed
    tube = _tube_slices("geom", "dtg", xy, t, buffer_m, None, 256)
    carried = isinstance(tube, Slices)  # one slice stays And(BBox, During)
    assert a["bins"] == (len(tube) if carried else 1)
    plan = store.planner.plan("rep", tube)
    branches = [plan] if plan.union is None else plan.union
    cfgs = [p.config for p in branches]
    assert a["groups"] == (0 if plan.union is None else len(branches))
    assert a["groups"] == {"slices-256": 16, "slices-40": 3, "one-slice": 0}[case]
    assert a["boxes"] == sum(0 if c.boxes is None else len(c.boxes) for c in cfgs)
    assert a["windows"] == sum(0 if c.windows is None else len(c.windows) for c in cfgs)  # z2: none
    assert a["ranges"] == sum(c.n_ranges for c in cfgs)
    assert {s.name for s in _all(tr)} >= {"tube", "tube.bins"}
    if len(out) or a["rows"]:
        (refine,) = _named(tr, "tube.refine")
        assert refine.attrs["rows"] == a["rows"]
    # ONE query root: one plan, one dispatch and a scan + decode a branch directly under it
    for name, count in (("plan", 1), ("dispatch", 1), ("scan", len(branches)),
                        ("decode", len(branches))):
        assert len(_children(inner, inner.root, name)) == count, name
    (planned,) = _children(inner, inner.root, "plan")
    assert planned.attrs["sliced"] == a["groups"]
    # PR 48: the slices reach the indexes as the carrier's array rows, and the root says so
    assert planned.attrs["slice_rows"] == (a["bins"] if carried else 0)
    assert a["arrays"] == int(carried)


def test_a_tube_of_many_slices_is_sixteen_scans_of_their_own_windows(store, traced):
    """What the cell was built to show (PERF.md section 7), as PR 47 left
    it: past 16 disjuncts the planner no longer scans every slice's box for
    the whole track's duration (256 boxes, ONE window) but sixteen
    time-ordered groups, each its own 16 boxes under its own window (two
    where a group crosses a z3 bin's edge; none where z2 costs less)."""
    xy, t = _track(360, T0 + 600_000, T0 + 5 * 3_600_000)
    out = tube_select(store, "rep", xy, t, 2_000.0)
    (tr,) = [x for x in traced.traces() if x.name == "tube"]
    (inner,) = [x for x in traced.traces() if x.name == "query"]
    a = tr.root.attrs
    assert a["bins"] == 256 == a["boxes"] and a["groups"] == 16
    plan = store.planner.plan("rep", _tube_slices("geom", "dtg", xy, t, 2_000.0, None, 256))
    assert len(plan.union) == 16 and all(len(p.config.boxes) == 16 for p in plan.union)
    per_group = [0 if p.config.windows is None else len(p.config.windows) for p in plan.union]
    assert all(w <= 2 for w in per_group) and 1 <= a["windows"] == sum(per_group) <= 32
    spans_ms = [w[:, 2].max() - w[:, 1].min() for w in
                (p.config.windows for p in plan.union if p.config.windows is not None)]
    # a group's window is its sixteenth of the track (z3 offsets are seconds here)
    assert max(spans_ms) <= (t[-1] - t[0]) / 1000 / 16 + 2
    # the rows come group by group: each branch's decode in time order of the track
    decodes = sorted(_children(inner, inner.root, "decode"), key=lambda s: s.attrs["member"])
    assert len(decodes) == 16 and sum(s.attrs["candidates"] for s in decodes) == a["candidates"]
    assert len(out) == a["kept"] <= a["rows"] <= a["candidates"]


@pytest.mark.parametrize("what", ["knn", "knn-many", "tube"])
def test_an_untraced_answer_is_the_traced_one(what, store):
    xy, t = _track(360, T0 + 600_000, T0 + 5 * 3_600_000)

    def ask():
        if what == "knn":
            return [knn_search(store, "rep", -122.41, 37.8, 32, filter=WINDOW,
                               max_distance_m=100_000.0)]
        if what == "knn-many":
            return knn_many(store, "rep", KNN_CASES["many-16"][0], 8, filter=WINDOW,
                            max_distance_m=100_000.0)
        return [tube_select(store, "rep", xy, t, 2_000.0)]

    plain = ask()
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    try:
        seen = ask()
        assert obs.tracer().traces()
    finally:
        conf.OBS_TRACE_SAMPLE.clear()
        obs.install(obs.Tracer())
    assert len(plain) == len(seen) and sum(len(fc) for fc in plain) > 0
    for a, b in zip(plain, seen):
        _same(a, b)


@pytest.mark.parametrize("slow_ms", [None, 0.0])
def test_no_span_is_made_with_sampling_off(slow_ms, store, monkeypatch):
    """Unsampled, neither root is built (``capture=False``: the slow log
    never takes them) and so nothing under a ``knn_many``; a tube's query
    builds its own ``query`` tree for the always-on slow log, as it did
    before PR 46, and nothing with that log off."""
    made = []
    real = otrace.Span.__init__

    def counting(self, trace, name, *a, **kw):
        made.append(name)
        real(self, trace, name, *a, **kw)

    monkeypatch.setattr(otrace.Span, "__init__", counting)
    if slow_ms is not None:
        conf.OBS_SLOW_MS.set(slow_ms)
    try:
        assert conf.OBS_TRACE_SAMPLE.get() == 0
        xy, t = _track(100, T0 + 600_000, T0 + 2 * 3_600_000)
        knn_search(store, "rep", -122.4, 37.8, 8, filter=WINDOW)
        knn_many(store, "rep", [(-122.4, 37.8), (-123.0, 37.5)], 4, filter=WINDOW)
        assert made == []
        tube_select(store, "rep", xy, t, 1_000.0)
        assert not [n for n in made if n.startswith(("knn", "tube"))]
        assert ("query" in made) == (slow_ms is None)
        if slow_ms is not None:
            assert made == []
    finally:
        conf.OBS_SLOW_MS.clear()


@pytest.mark.parametrize("bins", [256, 40, 1])
def test_the_audit_event_and_the_slow_log_hold_the_tubes_ecql(bins, store, traced):
    """PR 48: the carrier renders its text only when asked (the audit
    writer, the slow-query log taking the trace), and the text is ECQL that
    parses back to the ``Or`` of ``And(BBox, During)`` the tube means."""
    from geomesa_tpu.audit import AuditWriter
    from geomesa_tpu.filter import ecql

    xy, t = _track(360, T0 + 600_000, T0 + 5 * 3_600_000)
    tube = _tube_slices("geom", "dtg", xy, t, 2_000.0, None, bins)
    want = tube.expand() if isinstance(tube, Slices) else tube
    store.audit = AuditWriter()
    conf.OBS_SLOW_MS.set(1e-6)  # every query is slow: the log takes its trace
    rendered = []
    real = Slices.ecql
    try:
        Slices.ecql = Slices.__repr__ = lambda self: rendered.append(1) or real(self)
        out = tube_select(store, "rep", xy, t, 2_000.0, max_bins=bins)
        (event,) = store.audit.events
        (entry,) = [e for e in store.slow_queries() if e["fingerprint"].get("type") == "rep"]
        n_slow = len(rendered)
        conf.OBS_SLOW_MS.set(1e9)  # nothing is slow: the text is rendered for the audit alone
        tube_select(store, "rep", xy, t, 2_000.0, max_bins=bins)
    finally:
        Slices.ecql = Slices.__repr__ = real
        conf.OBS_SLOW_MS.clear()
        store.audit = None
    assert event.hits >= len(out)  # the query's rows, before the distance test
    for text in (event.filter, entry["fingerprint"]["filter"]):
        if bins > 1:
            assert ecql.parse(text) == want and text.count("DURING") == bins
    assert entry["fingerprint"]["strategy"] == event.strategy
    if bins > 1:
        assert n_slow == 2 and len(rendered) == 3  # the audit's and the log's; then the audit's


@pytest.mark.parametrize("span_s", [256 * 40, 256 * 40 + 1, 32 * 673])
def test_a_row_at_the_tracks_last_instant_is_in_the_answer(span_s):
    """PR 46's repair: where the track's duration is a whole multiple of
    its bins the last slice ended AT the last instant, exclusive, and a row
    of that instant (the vessel's own last report) was left out."""
    sft = FeatureType.from_spec("edge", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    n = 300
    xy, _ = _track(n, 0, 1)
    t = T0 + (np.linspace(0, span_s, n).astype(np.int64)) * 1000
    assert (t[-1] - t[0]) == span_s * 1000
    ds = DataStore()
    ds.create_schema(sft)
    # the track's own reports, one more a millisecond past its end, one before its start
    rows_t = np.concatenate([t, [t[-1] + 1, t[0] - 1]])
    rows_xy = np.concatenate([xy, xy[-1:], xy[:1]])
    ds.write("edge", FeatureCollection.from_columns(
        sft, np.arange(n + 2, dtype=np.int64),
        {"dtg": rows_t, "geom": (rows_xy[:, 0].copy(), rows_xy[:, 1].copy())}), check_ids=False)
    got = np.sort(np.asarray(tube_select(ds, "edge", xy, t, 50.0).ids).astype(np.int64))
    assert np.array_equal(got, np.arange(n))
