"""``QueryPlanner.plan_many`` runs each stage of ``plan`` once for a batch;
every plan it returns equals, field for field, what ``plan`` returns for
the same filter on the same store.

- the matrix: the analyst notebook's z3 members at every width and window,
  z2 boxes, polygons, an antimeridian box, disjoint and unbounded-time
  filters, the shapes the batched stages hand to ``plan``'s own (ids, a
  union, an attribute predicate), a filter twice, batches of 0 and 1, and
  all of these in one batch; on a ``DataStore``, on a mesh store of four
  virtual devices, on a table with a delta tier, on a type with an
  attribute index (whose ``scan_configs`` takes the members that bound the
  attribute, a disjoint pair of bounds and ``IN`` lists among them), and on
  stores whose tables lack the batched entries;
- each config's ``_spans`` slot: filled by ``plan_many`` as ``scan_spans``
  fills it, so the dispatch finds them;
- the config memo: a second ``plan_many`` decomposes nothing; a mutation
  between the stages leaves nothing memoised;
- the array forms the stages stand on against their one-member forms.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

from geomesa_tpu import obs
from geomesa_tpu.curve.binnedtime import BinnedTime
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.filter.extract import extract_filter
from geomesa_tpu.metrics import global_registry
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.planning.explain import ExplainNull
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.stats.sketches import Histogram
from geomesa_tpu.storage.adapter import HostAdapter
from geomesa_tpu.storage.delta import TieredTable

T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
SPAN_MS = 16 * DAY
N = 1 << 14
TYPE = "pts"
WIDTHS = (1.0, 2.0, 5.0, 10.0, 20.0, 40.0)
HOURS = (6, 24, 72, 168, 336)


def _bench_data():
    """benchmark/harness/data.py (imports NumPy alone), by path."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "harness", "data.py")
    spec = importlib.util.spec_from_file_location("_bench_harness_data_pm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _iso(ms):
    return f"{np.datetime64(int(ms), 'ms')}Z"


def _bbox(box):
    return "bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*(float(v) for v in box))


def _during(win):
    return f"dtg DURING {_iso(win[0])}/{_iso(win[1])}"


def _ngon(box, k):
    x0, y0, x1, y1 = box
    cx, cy, rx, ry = (x0 + x1) / 2, (y0 + y1) / 2, (x1 - x0) / 2, (y1 - y0) / 2
    pts = [(round(cx + rx * np.cos(2 * np.pi * i / k), 4),
            round(cy + ry * np.sin(2 * np.pi * i / k), 4)) for i in range(k)]
    ring = ", ".join(f"{float(x)!r} {float(y)!r}" for x, y in pts + [pts[0]])
    return f"INTERSECTS(geom, POLYGON(({ring})))"


def _notebook(seed, n):
    """n z3 members as the analyst mix's generator draws them: every
    width and every window length, dealt evenly."""
    data = _bench_data()
    rng = data.sub_rng(seed, 11)
    boxes = data.box_queries(rng, n, WIDTHS)
    wins = data.time_windows(rng, n, T0, SPAN_MS, HOURS)
    return [f"{_bbox(b)} AND {_during(w)}" for b, w in zip(boxes, wins)]


WIN = (T0 + 2 * DAY, T0 + 9 * DAY)
CASES = {
    "z3-notebook": _notebook(2_600_000_011, 30),
    "z3-another-seed": _notebook(3_100_000_007, 32),
    "z2-boxes": [_bbox(b) for b in _bench_data().box_queries(
        _bench_data().sub_rng(5, 11), 8, (1.0, 2.0, 5.0, 10.0))],
    "polygons": [_ngon((10, 10, 22, 16), 6), _ngon((-60, -20, -48, -14), 24),
                 f"{_ngon((10, 10, 22, 16), 6)} AND {_during(WIN)}",
                 f"{_ngon((-60, -20, -40, -10), 24)} AND {_during(WIN)}"],
    "antimeridian": [f"{_bbox((170, -10, 190, 10))} AND {_during(WIN)}",
                     _bbox((170, -10, 190, 10))],
    "disjoint-window": [f"{_bbox((0, 0, 20, 10))} AND {_during((T0 + 400 * DAY, T0 + 401 * DAY))}",
                        f"{_bbox((0, 0, 5, 5))} AND {_bbox((50, 50, 60, 60))}"],
    "disjoint-window-first": [
        f"{_bbox((0, 0, 20, 10))} AND {_during((T0 + 400 * DAY, T0 + 401 * DAY))}",
        f"{_bbox((0, 0, 20, 10))} AND {_during(WIN)}",
        f"{_bbox((0, 0, 20, 10))} AND {_during((T0 - 90 * DAY, T0 - 80 * DAY))}",
        f"{_bbox((5, 5, 9, 8))} AND {_during((T0 + DAY, T0 + 2 * DAY))}"],
    "unbounded-time": [_bbox((0, 0, 20, 10)), f"{_bbox((0, 0, 20, 10))} AND dtg > {_iso(WIN[0])}"],
    "time-alone": [_during(WIN), f"dtg BETWEEN {_iso(WIN[0])} AND {_iso(WIN[1])}"],
    "two-windows": [f"{_bbox((0, 0, 30, 20))} AND ({_during(WIN)} OR "
                    f"{_during((T0 + 12 * DAY, T0 + 13 * DAY))})"],
    "ids": ["IN ('17', '99', 'nope')"],
    "union": [f"{_bbox((-5, -5, 5, 5))} OR name = 'c'",
              f"{_bbox((-5, -5, 5, 5))} OR {_bbox((40, 40, 50, 50))}"],
    "attribute": ["name = 'b'", f"name = 'b' AND {_bbox((0, 0, 30, 20))} AND {_during(WIN)}"],
    "attribute-in": [f"name IN ('a', 'c') AND {_bbox((0, 0, 30, 20))} AND {_during(WIN)}",
                     "name IN ('b', 'nobody', 'c') AND name > 'a'", "name BETWEEN 'b' AND 'bz'"],
    # the attribute index alone sees these are empty, after z3 and z2 offered a plan
    "attribute-disjoint": [f"name = 'a' AND name = 'b' AND {_bbox((0, 0, 30, 20))} AND {_during(WIN)}",
                           f"name < 'a' AND name > 'b' AND {_bbox((0, 0, 30, 20))}"],
    "include": ["INCLUDE"],
    "twice": [f"{_bbox((3, 3, 9, 6))} AND {_during(WIN)}"] * 2 + [_bbox((3, 3, 9, 6))] * 2,
    "one": [f"{_bbox((3, 3, 9, 6))} AND {_during(WIN)}"],
    "empty-batch": [],
}
CASES["mixed"] = [f for k in sorted(CASES) for f in CASES[k][:3]]


def _fc(sft, seed, n, ids_from=0):
    data = _bench_data()
    rng = data.sub_rng(seed, 0)
    x, y = data.gdelt_points(n, rng, *data.cluster_centres(rng))
    t = T0 + rng.integers(0, SPAN_MS, n)
    names = np.array(["a", "b", "c"])[rng.integers(0, 3, n)]
    return FeatureCollection.from_columns(
        sft, [str(i) for i in range(ids_from, ids_from + n)],
        {"name": names, "dtg": t, "geom": (x, y)})


def _build(kind):
    spec = "name:String,dtg:Date,*geom:Point:srid=4326"
    if kind == "attr-index":
        spec = "name:String:index=true,dtg:Date,*geom:Point:srid=4326"
    sft = FeatureType.from_spec(TYPE, spec)
    sft.user_data["geomesa.z3.interval"] = "week"
    kw = {"tile": 64}
    if kind == "mesh4":
        kw["mesh"] = make_mesh(4)
    if kind == "host-adapter":
        kw["adapter"] = HostAdapter()
    ds = DataStore(**kw)
    ds.create_schema(sft)
    if kind != "no-data":
        ds.write(TYPE, _fc(sft, 2_600_000_011, N))
    if kind == "delta":
        ds.write(TYPE, _fc(sft, 7, 300, ids_from=N))
        assert isinstance(ds.table(TYPE, "z3"), TieredTable)
    return ds


_STORES: dict = {}


def _store(kind):
    if kind not in _STORES:
        _STORES[kind] = _build(kind)
    return _STORES[kind]


ARRAY_FIELDS = ("range_bins", "range_lo", "range_hi", "boxes", "windows", "range_contained",
                "boxes_inner", "windows_inner", "range_lo2", "range_hi2", "poly", "rast")
FLAG_FIELDS = ("index", "extent_mode", "geom_precise", "time_precise", "disjoint",
               "contained_exact", "clip_rows")


def _assert_configs_equal(got, want):
    assert (got is None) == (want is None)
    if got is None:
        return
    assert {f.name for f in dataclasses.fields(got)} == set(ARRAY_FIELDS + FLAG_FIELDS) | {"_spans"}
    for name in FLAG_FIELDS:
        assert getattr(got, name) == getattr(want, name), name
    for name in ARRAY_FIELDS:
        a, b = getattr(got, name), getattr(want, name)
        assert (a is None) == (b is None), name
        if a is not None:
            assert a.dtype == b.dtype and a.shape == b.shape, name
            assert np.array_equal(a, b), name


def _assert_spans_equal(got, want):
    for name in ("overlap", "contained", "union"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.lo.dtype == np.int64 and a.hi.dtype == np.int64
        assert np.array_equal(a.lo, b.lo) and np.array_equal(a.hi, b.hi), name


def _assert_plans_equal(ds, got, want):
    assert type(got) is type(want)
    assert (got.type_name, got.index, got.ids, got.limit) == (
        want.type_name, want.index, want.ids, want.limit)
    assert repr(got.filter) == repr(want.filter)
    assert got.estimated_rows == want.estimated_rows
    assert got.warnings == want.warnings
    assert got.strategy == want.strategy
    _assert_configs_equal(got.config, want.config)
    assert (got.union is None) == (want.union is None)
    for a, b in zip(got.union or [], want.union or []):
        _assert_plans_equal(ds, a, b)
    if got.config is not None and got.index is not None and not got.config.disjoint \
            and ds.row_count(TYPE):
        # the slot cost() left: this table's spans, and the ones a fresh
        # config computes on its own
        table = ds.table(TYPE, got.index)
        main = getattr(table, "main", table)
        sk = getattr(main, "_sk", main)
        slot = got.config._spans
        assert slot is not None and slot[0]() is sk
        fresh = dataclasses.replace(got.config)
        _assert_spans_equal(slot[1], sk.scan_spans(fresh)[0])
        _assert_spans_equal(slot[1], want.config._spans[1])


def _plan_an_index_at_a_time(ds, f, limit=None):
    """``plan`` as it was before the array stages, from the planner's own
    pieces: each index decomposes and costs the filter on its own, and
    the estimate extracts it again."""
    pl, exp = ds.planner, ExplainNull()
    plan = pl._select(TYPE, pl._prepare(TYPE, f, True), limit, exp)
    pl._estimate_rows([plan], [exp])
    pl._finish(plan, True, exp)
    return plan


def _plan_both(ds, filters, limit=None):
    """(plan_many's, the oracle's); ``plan`` of each is held to both."""
    ds.planner.invalidate_config_memo()
    want = [_plan_an_index_at_a_time(ds, f, limit) for f in filters]
    ds.planner.invalidate_config_memo()
    ones = [ds.planner.plan(TYPE, f, limit=limit) for f in filters]
    for one, w in zip(ones, want):
        _assert_plans_equal(ds, one, w)
    ds.planner.invalidate_config_memo()
    got = ds.planner.plan_many(TYPE, filters, limit=limit)
    return got, want


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("kind", ["single", "mesh4", "delta", "attr-index"])
def test_plan_many_equals_plan(kind, case):
    ds = _store(kind)
    filters = CASES[case]
    got, want = _plan_both(ds, filters, limit=None if case != "mixed" else 11)
    assert len(got) == len(want) == len(filters)
    for g, w in zip(got, want):
        _assert_plans_equal(ds, g, w)
    if filters:
        share = got[0].planning_s
        assert share > 0 and all(p.planning_s == share for p in got)
        assert len({id(p) for p in got}) == len(got)  # a plan a member, never shared


@pytest.mark.parametrize("kind", ["host-adapter", "no-data"])
def test_plan_many_equals_plan_where_the_batched_entries_are_missing(kind):
    """A table without ``candidate_rows_many`` (the host adapter's): every
    member through ``plan``'s own stages. A type with no rows yet: no table
    to cost, the multiplier alone."""
    ds = _store(kind)
    got, want = _plan_both(ds, CASES["mixed"])
    for g, w in zip(got, want):
        _assert_plans_equal(ds, g, w)


@pytest.mark.parametrize("kind", ["single", "delta", "attr-index"])
def test_the_explain_trail_of_plan_is_the_index_at_a_time_trail(kind):
    """Which index found what, in the indexes' order, then the strategy
    and the estimate: line for line what planning an index at a time says."""
    from geomesa_tpu.planning.explain import Explainer

    ds = _store(kind)
    pl = ds.planner
    for f in CASES["mixed"]:
        want = Explainer()
        pl.invalidate_config_memo()
        prepared = pl._prepare(TYPE, f, True)
        want(f"Planning query on '{TYPE}': {type(prepared).__name__}")
        plan = pl._select(TYPE, prepared, None, want)
        pl._estimate_rows([plan], [want])
        got = Explainer()
        pl.invalidate_config_memo()
        pl.plan(TYPE, f, explain=got)
        assert got.lines == want.lines and len(got.lines) >= 2, f


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    from geomesa_tpu import conf

    conf.OBS_TRACE_SAMPLE.set(1)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _plan_span(trace):
    (plan,) = [s for s in trace.spans if s.name == "plan"]
    kids = [s for s in trace.spans if s.parent_id == plan.span_id]
    return plan, kids


@pytest.mark.parametrize("kind,batched", [("single", True), ("delta", True),
                                          ("host-adapter", False), ("attr-index", True)])
def test_one_plan_span_counts_the_members_the_arrays_took(traced, kind, batched):
    ds = _store(kind)
    filters = CASES["mixed"]
    ds.planner.invalidate_config_memo()
    with obs.tracer().trace("query_many", type=TYPE):
        plans = ds.planner.plan_many(TYPE, filters)
    plan, kids = _plan_span(traced()[-1])
    a = plan.attrs
    assert a["members"] == len(filters) and "cpu_s" in a
    # ids, INCLUDE, the OR with an attribute predicate and, where no index
    # holds the attribute, the predicates that bound it alone: plan()'s own
    own = sum(1 for p in plans if p.ids is not None or p.index is None)
    assert (3 if kind == "attr-index" else 6) <= own < len(filters) or not batched
    assert a["batched"] == (len(filters) - own if batched else 0)
    segs = a["segments"]
    assert set(segs) == ({"parse", "extract", "decompose", "spans", "estimate"}
                         if batched else {"parse", "estimate"})
    assert sum(segs.values()) <= plan.dur_s + 2e-4
    if kind == "attr-index":
        # the decider's record, as an index at a time writes it a plan
        attr = [p for q in plans for p in (q.union or [q])
                if p.index is not None and "name" in repr(p.filter) and not p.config.disjoint]
        assert a["attr_offered"] == len(attr) > 4
        assert a["attr_won"] == sum(p.index == "attr_name" for p in attr) > 0
    else:
        assert "attr_offered" not in a and "attr_won" not in a
    if batched:
        names = [(s.name, s.attrs["index"]) for s in kids]
        # once an index; the members plan() took come after
        for idx in ("z3", "z2") + (("attr_name",) if kind == "attr-index" else ()):
            assert names.count(("plan.probe", idx)) >= 1
            first = next(s for s in kids if s.name == "plan.decompose"
                         and s.attrs["index"] == idx)
            assert 1 < first.attrs["members"] <= len(filters) and first.attrs["ranges"] > 0


def test_a_second_plan_many_decomposes_nothing_and_computes_no_spans(traced):
    ds = _store("single")
    filters = CASES["z3-notebook"] + CASES["z2-boxes"]
    ds.planner.invalidate_config_memo()
    first = ds.planner.plan_many(TYPE, filters)
    reg = global_registry()
    c0 = reg.counter_value("geomesa.scan.spans.computed")
    with obs.tracer().trace("query_many", type=TYPE):
        again = ds.planner.plan_many(TYPE, filters)
    assert not [s for s in traced()[-1].spans if s.name == "plan.decompose"]
    assert reg.counter_value("geomesa.scan.spans.computed") == c0
    for a, b in zip(again, first):
        assert a is not b and a.config is b.config  # the memo's own configs


def test_a_mutation_between_the_stages_leaves_nothing_memoised(monkeypatch):
    ds = _store("single")
    filters = CASES["z3-notebook"][:8]
    want = [ds.planner.plan(TYPE, f) for f in filters]
    ds.planner.invalidate_config_memo()
    z3 = next(i for i in ds.indexes(TYPE) if i.name == "z3")
    real = z3.scan_configs

    def mutate_then_decompose(extractions):
        ds.planner.invalidate_config_memo()  # what a committed write does
        return real(extractions)

    monkeypatch.setattr(z3, "scan_configs", mutate_then_decompose)
    got = ds.planner.plan_many(TYPE, filters)
    for g, w in zip(got, want):
        _assert_plans_equal(ds, g, w)  # usable for this call
    assert not [k for k in ds.planner._config_memo if k[0] is z3]  # never memoised
    monkeypatch.undo()
    assert ds.planner.plan_many(TYPE, filters)[0].config is not got[0].config


def test_a_write_between_plan_many_and_the_dispatch_recomputes_the_spans():
    ds = _build("single")
    f = CASES["one"]
    (plan,) = ds.planner.plan_many(TYPE, f)
    old = plan.config._spans[0]()
    ds.write(TYPE, _fc(ds.get_schema(TYPE), 9, N, ids_from=N))  # swaps the tables in
    assert ds.table(TYPE, plan.index) is not old
    (out,) = ds.planner.execute_many([plan])
    (ref,) = [ds.query(TYPE, f[0])]
    assert np.array_equal(np.sort(np.asarray(out.ids)), np.sort(np.asarray(ref.ids)))


@pytest.mark.parametrize("kind", ["single", "mesh4", "delta"])
def test_query_many_answers_equal_query(kind):
    ds = _store(kind)
    filters = CASES["mixed"]
    for got, f in zip(ds.query_many(TYPE, filters), filters):
        want = ds.query(TYPE, f)
        assert np.array_equal(np.sort(np.asarray(got.ids)), np.sort(np.asarray(want.ids)))
    assert ds.query_many(TYPE, []) == []


# -- the array forms against their one-member forms ------------------------

@pytest.mark.parametrize("index", ["z3", "z2", "attr_name"])
def test_scan_configs_of_a_batch_are_scan_config_of_each(index):
    ds = _store("attr-index" if index == "attr_name" else "single")
    idx = next(i for i in ds.indexes(TYPE) if i.name == index)
    sft = ds.get_schema(TYPE)
    filters = [ecql.parse(f) for f in CASES["mixed"]]
    exs = [extract_filter(f, sft.geom_field, sft.dtg_field) for f in filters]
    for got, f in zip(idx.scan_configs(exs), filters):
        _assert_configs_equal(got, idx.scan_config(f))


@pytest.mark.parametrize("index", ["z3", "z2", "attr_name"])
def test_spans_of_a_batch_are_the_spans_of_each(index):
    ds = _store("attr-index" if index == "attr_name" else "single")
    idx = next(i for i in ds.indexes(TYPE) if i.name == index)
    sk = ds.table(TYPE, index)
    cfgs = [c for c in (idx.scan_config(ecql.parse(f)) for f in CASES["mixed"])
            if c is not None]
    # contained flags that do not count beside ones that do
    cfgs += [dataclasses.replace(c, contained_exact=False) for c in cfgs[:5]]
    many = sk._compute_spans([dataclasses.replace(c) for c in cfgs])
    assert len(many) == len(cfgs)
    assert any(len(s.contained) for s in many) or index == "attr_name"
    for got, cfg in zip(many, cfgs):
        _assert_spans_equal(got, sk._compute_spans([dataclasses.replace(cfg)])[0])
    rows = sk.candidate_rows_many([dataclasses.replace(c) for c in cfgs])
    assert rows.dtype == np.int64
    assert rows.tolist() == [sk.candidate_spans(dataclasses.replace(c)).n_rows() for c in cfgs]


@pytest.mark.parametrize("period", ["day", "week", "month", "year"])
def test_bins_for_intervals_is_bins_for_interval_of_each(period):
    bt = BinnedTime(period)
    rng = np.random.default_rng(3)
    lo = T0 + rng.integers(-400 * DAY, 400 * DAY, 40)
    hi = lo + rng.integers(0, 900 * DAY, 40)
    lo[:3], hi[:3] = (-5, 0, T0), (10, 0, 10**18)  # clamped at both ends
    bins, los, his, counts = bt.bins_for_intervals(lo, hi)
    assert counts.sum() == len(bins) == len(los) == len(his)
    end = 0
    for a, z, n in zip(lo.tolist(), hi.tolist(), counts.tolist()):
        start, end = end, end + n
        b1, l1, h1 = bt.bins_for_interval(a, z)
        for got, want in ((bins[start:end], b1), (los[start:end], l1), (his[start:end], h1)):
            assert got.dtype == want.dtype and np.array_equal(got, want)
    with pytest.raises(ValueError, match="inverted interval"):
        bt.bins_for_intervals([5, 100], [9, 50])


def _estimate_range_loop(h: Histogram, lo: float, hi: float) -> float:
    """``Histogram.estimate_range`` as it was before the array form."""
    w = (h.hi - h.lo) / h.n_bins
    edges = h.lo + np.arange(h.n_bins + 1) * w
    overlap = np.clip(np.minimum(hi, edges[1:]) - np.maximum(lo, edges[:-1]), 0.0, w)
    return float((h.counts * (overlap / w)).sum())


@pytest.mark.parametrize("n_bins", [7, 64, 1000])
def test_estimate_ranges_is_the_loop_to_the_bit(n_bins):
    rng = np.random.default_rng(n_bins)
    h = Histogram(n_bins, -180.0, 180.0)
    h.observe(rng.normal(0, 60, 50_000))
    lo = rng.uniform(-200, 150, 300)
    hi = lo + rng.uniform(0, 90, 300)
    got = h.estimate_ranges(lo, hi)
    want = [_estimate_range_loop(h, float(a), float(z)) for a, z in zip(lo, hi)]
    assert got.dtype == np.float64 and got.tolist() == want
    assert [h.estimate_range(float(a), float(z)) for a, z in zip(lo[:20], hi[:20])] == want[:20]


def test_estimates_of_a_batch_are_estimate_filter_of_each():
    ds = _store("single")
    sft, stats = ds.get_schema(TYPE), ds.stats_for(TYPE)
    filters = [ecql.parse(f) for f in CASES["mixed"] if f != "INCLUDE"]
    exs = [extract_filter(f, sft.geom_field, sft.dtg_field) for f in filters]
    got = stats.estimate_extractions(sft, exs)
    assert got == [stats.estimate_filter(sft, f) for f in filters]
    assert sum(e is not None and e > 0 for e in got) > 10
