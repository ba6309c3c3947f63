"""The time-sliced union (PR 47; ``filter/dnf.py`` ``time_slices``, the
planner's ``_plan_members`` and ``_execute_union``): an ``Or`` of MORE than
sixteen box-and-interval slices is planned as ``ceil(n / 16)`` time-ordered
groups, each a scan of its own boxes over its own stretch of time, and
dispatched fused; sixteen or fewer, or a disjunct without a bounded time
predicate, keep the single scan.

A 2^16-row z3 + z2 store against a NumPy brute force that knows nothing of
filters: rows exactly on a slice's boundary instant, at the last instant,
under overlapping intervals (a row in two groups: dedup), under
``And(Or, residual)``, under ``limit``, through the scheduler.
"""

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.dnf import MAX_DISJUNCTS, time_slices
from geomesa_tpu.filter.extract import extract_intervals
from geomesa_tpu.filter.predicates import And, BBox, Cmp, During, Or, Slices
from geomesa_tpu.planning.errors import QueryTimeout
from geomesa_tpu.planning.hints import QueryHints
from geomesa_tpu.serving import QueryScheduler, ServingConfig
from geomesa_tpu.sft import FeatureType

N = 1 << 16
T0 = 1_700_000_000_000
STEP_MS = 60_000  # a slice is a minute; rows lie on whole seconds
SLICES_MAX = 256
TYPE = "rep"


@pytest.fixture(scope="module")
def rows():
    """Reports along a diagonal corridor and round it, whole-second times
    over 256 minutes and a little past both ends; one row in eight sits
    EXACTLY on a minute (a slice's boundary instant)."""
    rng = np.random.default_rng(47)
    f = rng.uniform(-0.02, 1.02, N)
    x = -122.0 + 2.0 * f + rng.normal(0, 0.02, N)
    y = 36.0 + 1.0 * f + rng.normal(0, 0.02, N)
    t = T0 + (f * SLICES_MAX * STEP_MS).astype(np.int64) // 1000 * 1000
    t += rng.integers(-300, 300, N) * 1000
    on_minute = rng.random(N) < 0.125
    t[on_minute] = T0 + (t[on_minute] - T0) // STEP_MS * STEP_MS
    return x, y, t, rng.integers(0, 100, N).astype(np.int32)


@pytest.fixture(scope="module")
def store(rows):
    x, y, t, mmsi = rows
    sft = FeatureType.from_spec(TYPE, "mmsi:Integer,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    ds = DataStore()
    ds.create_schema(sft)
    ds.write(TYPE, FeatureCollection.from_columns(
        sft, np.arange(N, dtype=np.int64), {"mmsi": mmsi, "dtg": t, "geom": (x, y)}),
        check_ids=False)
    return ds


def _boxes(n, overlap_ms=0, half=0.05):
    """``n`` (xmin, ymin, xmax, ymax, lo, hi) along the corridor, slice i
    over minute i (widened by ``overlap_ms`` each way)."""
    out = []
    for i in range(n):
        c = (i + 0.5) / SLICES_MAX
        cx, cy = -122.0 + 2.0 * c, 36.0 + 1.0 * c
        out.append((cx - half, cy - half, cx + half, cy + half,
                    T0 + i * STEP_MS - overlap_ms, T0 + (i + 1) * STEP_MS + overlap_ms))
    return out


def _filter(boxes):
    parts = [And((BBox("geom", a, b, c, d), During("dtg", lo, hi))) for a, b, c, d, lo, hi in boxes]
    return parts[0] if len(parts) == 1 else Or(tuple(parts))


def _brute(rows, boxes, more=None):
    """The ids the slices hold: a closed box, a half-open interval."""
    x, y, t, _ = rows
    hit = np.zeros(N, bool)
    for a, b, c, d, lo, hi in boxes:
        hit |= (x >= a) & (x <= c) & (y >= b) & (y <= d) & (t >= lo) & (t < hi)
    if more is not None:
        hit &= more
    return np.flatnonzero(hit)


def _ids(fc):
    return np.sort(np.asarray(fc.ids).astype(np.int64))


# ------------------------------------------------------------------ the rule


@pytest.mark.parametrize("n", [17, 33, 64, 255, 256])
def test_time_slices_cuts_even_consecutive_groups(n):
    boxes = _boxes(n)
    order = np.random.default_rng(n).permutation(n)  # the caller's order is not time's
    groups = time_slices(_filter([boxes[i] for i in order]), "dtg")
    k = -(-n // MAX_DISJUNCTS)
    assert len(groups) == k
    # an Or of And(BBox, During) is converted once: the groups are carriers over row slices
    assert all(isinstance(g, Slices) for g in groups)
    sizes = [len(g) for g in groups]
    assert sum(sizes) == n and max(sizes) <= MAX_DISJUNCTS and max(sizes) - min(sizes) <= 1
    flat = [lo for g in groups for lo in g.windows[:, 0].tolist()]
    assert flat == sorted(flat) == [b[4] for b in boxes]  # every slice once, in time order


@pytest.mark.parametrize("n", [17, 64, 256])
def test_an_or_the_carrier_cannot_express_is_cut_as_objects(n):
    """A disjunct with a second predicate keeps the object path: groups of ``Or``."""
    boxes = _boxes(n)
    parts = list(_filter(boxes).filters)
    parts[3] = And((*parts[3].filters, Cmp("mmsi", "<", 50)))
    groups = time_slices(Or(tuple(parts)), "dtg")
    assert len(groups) == -(-n // MAX_DISJUNCTS) and all(isinstance(g, Or) for g in groups)
    starts = [extract_intervals(d, "dtg").values[0].lo for g in groups for d in g.filters]
    assert starts == [b[4] for b in boxes]


NOT_SLICED = {
    "sixteen": lambda b: _filter(b[:16]),
    "one-predicate": lambda b: BBox("geom", -122, 36, -120, 37),
    "no-time-in-one": lambda b: Or((*_filter(b[:20]).filters, BBox("geom", -121.5, 36, -121.4, 36.1))),
    "open-ended": lambda b: Or((*_filter(b[:20]).filters,
                                And((BBox("geom", -121.5, 36, -121.4, 36.1),
                                     Cmp("dtg", ">", T0 + 200 * STEP_MS))))),
    "two-ors": lambda b: And((_filter(b[:20]), _filter(_boxes(20, half=5.0)))),
    "and-without-or": lambda b: And((BBox("geom", -122, 36, -120, 37), Cmp("mmsi", "<", 50))),
}


@pytest.mark.parametrize("case", sorted(NOT_SLICED))
def test_any_other_filter_keeps_its_single_plan(case, store, rows):
    f = NOT_SLICED[case](_boxes(64))
    assert time_slices(f, "dtg") is None
    plan = store.planner.plan(TYPE, f)
    assert plan.union is None and plan.index in ("z3", "z2")
    fc = store.features(TYPE)
    want = np.sort(np.asarray(fc.ids)[np.asarray(f.evaluate(fc.batch))].astype(np.int64))
    assert np.array_equal(_ids(store.query(TYPE, f)), want) and len(want)


def test_a_type_without_a_date_is_not_sliced():
    assert time_slices(_filter(_boxes(40)), None) is None


def test_the_other_conjuncts_go_into_every_group():
    rest = Cmp("mmsi", "<", 50)
    groups = time_slices(And((rest, _filter(_boxes(40)))), "dtg")
    assert len(groups) == 3
    for g in groups:
        assert isinstance(g, And) and rest in g.filters
        (inner,) = [c for c in g.filters if isinstance(c, Slices)]
        assert len(inner) in (13, 14)


# ------------------------------------------------------------------ the plan


@pytest.mark.parametrize("n", [2, 8, 16, 17, 64, 256])
def test_the_plan_is_one_scan_to_sixteen_and_groups_past_it(n, store):
    plan = store.planner.plan(TYPE, _filter(_boxes(n)))
    if n <= MAX_DISJUNCTS:
        assert plan.union is None and plan.index in ("z3", "z2")
        return
    k = -(-n // MAX_DISJUNCTS)
    assert plan.index is None and plan.config is None and len(plan.union) == k
    assert plan.strategy.startswith("union(") and plan.strategy.count("z") == k
    last = -1
    for branch in plan.union:
        assert branch.index in ("z3", "z2") and branch.limit is None and branch.union is None
        ivs = extract_intervals(branch.filter, "dtg").values
        assert ivs[0].lo > last  # time-ordered, and no slice in two groups
        last = ivs[-1].hi - 1
        if branch.index == "z3":
            # its own stretch of the track, not the whole duration
            assert ivs[-1].hi - ivs[0].lo <= MAX_DISJUNCTS * STEP_MS
    assert sum(len(b.config.boxes) for b in plan.union) == n
    # the branches of one query share its range target
    target = conf.SCAN_RANGES_TARGET.get()
    for branch in plan.union:
        windows = 1 if branch.config.windows is None else len(branch.config.windows)
        assert branch.config.n_ranges <= max(1, target // k) * windows
    assert plan.estimated_rows == sum(b.estimated_rows for b in plan.union)


def _assert_same_plan(a, b):
    assert (a.type_name, a.index, a.ids, a.limit, a.strategy) == (
        b.type_name, b.index, b.ids, b.limit, b.strategy)
    assert repr(a.filter) == repr(b.filter)
    assert a.estimated_rows == b.estimated_rows and a.warnings == b.warnings
    assert (a.config is None) == (b.config is None)
    if a.config is not None:
        for name in ("range_bins", "range_lo", "range_hi", "boxes", "windows"):
            x, y = getattr(a.config, name), getattr(b.config, name)
            assert (x is None) == (y is None) and (x is None or np.array_equal(x, y)), name
    assert (a.union is None) == (b.union is None)
    for x, y in zip(a.union or [], b.union or []):
        _assert_same_plan(x, y)


@pytest.mark.parametrize("n", [16, 17, 64, 256])
@pytest.mark.parametrize("limit", [None, 7])
def test_plan_and_plan_many_agree_field_for_field(n, limit, store):
    mixed = [_filter(_boxes(n)), _filter(_boxes(3)), And((Cmp("mmsi", "<", 50), _filter(_boxes(40))))]
    store.planner.invalidate_config_memo()
    ones = [store.planner.plan(TYPE, f, limit=limit) for f in mixed]
    store.planner.invalidate_config_memo()
    many = store.planner.plan_many(TYPE, mixed, limit=limit)
    assert len(many) == len(ones) == 3
    for a, b in zip(many, ones):
        _assert_same_plan(a, b)
    assert all(p.limit == limit for p in many)


def test_a_group_no_index_serves_falls_back_to_the_single_plan():
    """Time-only slices on a z2-only type: a union would be a full scan a
    group, so the member keeps its one full scan."""
    sft = FeatureType.from_spec("z2only", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    t = T0 + np.arange(4000, dtype=np.int64) * 1000
    ds.write("z2only", FeatureCollection.from_columns(
        sft, np.arange(4000, dtype=np.int64),
        {"dtg": t, "geom": (np.linspace(-10, 10, 4000), np.linspace(-5, 5, 4000))}),
        check_ids=False)
    f = Or(tuple(During("dtg", T0 + i * 100_000, T0 + i * 100_000 + 50_000) for i in range(20)))
    assert len(time_slices(f, "dtg")) == 2
    plan = ds.planner.plan("z2only", f)
    assert plan.union is None and plan.strategy == "full-scan"
    want = np.flatnonzero(((t - T0) % 100_000 < 50_000) & (t - T0 < 2_000_000))
    assert np.array_equal(_ids(ds.query("z2only", f)), want)


# --------------------------------------------------------------- the answers

ANSWERS = {
    # n, overlap_ms, residual, limit
    "17": (17, 0, False, None),
    "64": (64, 0, False, None),
    "256": (256, 0, False, None),
    "255-odd-groups": (255, 0, False, None),
    "overlap-dedup": (96, 90_000, False, None),
    "and-residual": (64, 0, True, None),
    "limit": (128, 0, False, 25),
    "overlap-residual-limit": (200, 30_000, True, 40),
}


@pytest.mark.parametrize("case", sorted(ANSWERS))
@pytest.mark.parametrize("through", ["query", "scheduler", "query_many"])
def test_the_answer_is_the_brute_forces(case, through, store, rows):
    n, overlap, residual, limit = ANSWERS[case]
    boxes = _boxes(n, overlap_ms=overlap)
    f = _filter(boxes)
    more = None
    if residual:
        f, more = And((f, Cmp("mmsi", "<", 50))), rows[3] < 50
    want = _brute(rows, boxes, more)
    assert store.planner.plan(TYPE, f).union is not None
    if through == "query":
        out = store.query(TYPE, f, limit=limit)
    elif through == "scheduler":
        with QueryScheduler(store, ServingConfig()) as sched:
            out = sched.query(TYPE, f, limit=limit)
    else:
        out, other = store.query_many(TYPE, [f, _filter(_boxes(5))], limit=limit)
        beside = _brute(rows, _boxes(5))  # the one-scan member beside it
        assert np.isin(_ids(other), beside).all()
        assert len(other) == (len(beside) if limit is None else min(limit, len(beside)))
    got = _ids(out)
    assert len(np.unique(got)) == len(got)  # no row twice
    if limit is None:
        assert np.array_equal(got, want) and len(want) > 50
    else:
        assert len(got) == limit < len(want) and np.isin(got, want).all()
    # every attribute the row's own
    x, y, t, mmsi = rows
    ids = np.asarray(out.ids).astype(np.int64)
    assert np.array_equal(np.asarray(out.columns["dtg"], np.int64), t[ids])
    assert np.array_equal(np.asarray(out.columns["mmsi"]), mmsi[ids])


def test_rows_on_a_boundary_instant_and_the_last_instant(store, rows):
    """A row exactly at minute i belongs to slice i, not i - 1 (half-open),
    whichever groups the two fall in; the last slice's end is exclusive."""
    x, y, t, _ = rows
    boxes = _boxes(256, half=5.0)  # the boxes hold everything: time alone decides
    got = _ids(store.query(TYPE, _filter(boxes)))
    want = np.flatnonzero((t >= T0) & (t < T0 + 256 * STEP_MS))
    assert np.array_equal(got, want)
    edge = np.flatnonzero(((t - T0) % (MAX_DISJUNCTS * STEP_MS) == 0) & (t >= T0)
                          & (t < T0 + 256 * STEP_MS))
    assert len(edge) > 100 and np.isin(edge, got).all()  # rows ON a group's boundary
    assert (t == T0 + 256 * STEP_MS).any() and not np.isin(
        np.flatnonzero(t == T0 + 256 * STEP_MS), got).any()


def test_an_overlap_is_answered_once_and_said_so(store, rows):
    from geomesa_tpu.planning.explain import Explainer

    boxes = _boxes(96, overlap_ms=90_000, half=5.0)
    exp = Explainer()
    out = store.query(TYPE, _filter(boxes), explain=exp)
    assert np.array_equal(_ids(out), _brute(rows, boxes))
    assert "Union dedup" in exp.render() and "time-ordered groups" in exp.render()


def test_the_querys_one_deadline_bounds_the_union(store):
    with pytest.raises(QueryTimeout):
        store.query(TYPE, _filter(_boxes(64)), hints=QueryHints(timeout=1e-9))


# ------------------------------------------------------------------ the trace


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


@pytest.mark.parametrize("n", [16, 17, 256])
def test_one_plan_one_dispatch_a_scan_and_a_decode_a_branch(n, store, traced):
    out = store.query(TYPE, _filter(_boxes(n)))
    (tr,) = traced.traces()
    k = -(-n // MAX_DISJUNCTS) if n > MAX_DISJUNCTS else 1
    under_root = [s for s in tr.spans if s.parent_id == tr.root.span_id]
    names = [s.name for s in under_root]
    assert names.count("plan") == 1 and names.count("dispatch") == 1
    assert names.count("scan") == names.count("decode") == k
    (plan,) = [s for s in under_root if s.name == "plan"]
    assert plan.attrs["sliced"] == (k if n > MAX_DISJUNCTS else 0)
    assert plan.attrs["members"] == 1 and plan.attrs["batched"] == 1
    if n > MAX_DISJUNCTS:
        (dispatch,) = [s for s in under_root if s.name == "dispatch"]
        assert dispatch.attrs["members"] == k
        decodes = [s for s in under_root if s.name == "decode"]
        assert sorted(s.attrs["member"] for s in decodes) == list(range(k))
        assert sum(s.attrs["candidates"] for s in decodes) >= len(out)


def test_a_batchs_plan_span_counts_the_groups_of_all_members(store, traced):
    store.query_many(TYPE, [_filter(_boxes(40)), _filter(_boxes(4)), _filter(_boxes(100))])
    (tr,) = traced.traces()
    (plan,) = [s for s in tr.spans if s.name == "plan"]
    assert plan.attrs["members"] == 3 and plan.attrs["batched"] == 3
    assert plan.attrs["sliced"] == 3 + 7
