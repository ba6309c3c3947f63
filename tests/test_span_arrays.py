"""A scan config's candidate row spans are two int64 arrays a class,
computed once a (config, sorted table) and shared by ``QueryPlanner.cost``
and the dispatch of the same query (``SortedKeys.scan_spans``).

- the arrays against the tuple-list implementation they replaced, kept
  here as the oracle, element for element: z3, z2, an attribute index with
  tie-run narrowing, ``contained_exact`` on and off, hand-made ranges;
- ``candidate_blocks`` and the span helpers against their per-span forms;
- ``cost()`` and the chosen index for the benchmark's generated filters;
- the config's slot: filled by ``cost()``, found by the dispatch, valid by
  the table's identity alone (a write that swaps the table recomputes).
"""

import dataclasses
import importlib.util
import os
import types

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.index.api import ScanConfig, WriteKeys
from geomesa_tpu.metrics import global_registry
from geomesa_tpu.planning.planner import index_priority
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage import table as tbl
from geomesa_tpu.storage.delta import TieredTable
from geomesa_tpu.storage.table import IndexTable, RowSpans, SortedKeys

T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
SPAN_MS = 16 * DAY
N = 1 << 15
TYPE = "pts"


# -- the oracle: the tuple-list implementation, as the package had it -----

def _oracle_merge(spans):
    if not spans:
        return []
    spans = sorted(spans)
    merged = [spans[0]]
    for a, z in spans[1:]:
        if a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], z))
        else:
            merged.append((a, z))
    return merged


def _oracle_split(sk: SortedKeys, config: ScanConfig):
    cont_flags = config.range_contained
    use_contained = config.contained_exact and cont_flags is not None
    overlap, contained = [], []
    for b in np.unique(config.range_bins):
        i = int(np.searchsorted(sk.ubins, b))
        if i >= len(sk.ubins) or sk.ubins[i] != b:
            continue
        s, e = int(sk.bin_starts[i]), int(sk.bin_starts[i + 1])
        sel = config.range_bins == b
        seg = sk.zs[s:e]
        lo = np.searchsorted(seg, config.range_lo[sel], side="left") + s
        hi = np.searchsorted(seg, config.range_hi[sel], side="right") + s
        if sk.subkeys is not None and config.range_lo2 is not None:
            lo_end = np.searchsorted(seg, config.range_lo[sel], side="right") + s
            hi_start = np.searchsorted(seg, config.range_hi[sel], side="left") + s
            lo2 = config.range_lo2[sel]
            hi2 = config.range_hi2[sel]
            for k in range(len(lo)):
                lo[k] = sk._narrow_lo(int(lo[k]), int(lo_end[k]), lo2[k])
                hi[k] = sk._narrow_hi(int(hi_start[k]), int(hi[k]), hi2[k])
        if use_contained:
            cf = cont_flags[sel]
        else:
            cf = np.zeros(int(sel.sum()), dtype=bool)
        for a, z, c in zip(lo.tolist(), hi.tolist(), cf.tolist()):
            if z > a:
                (contained if c else overlap).append((a, z))
    return _oracle_merge(overlap), _oracle_merge(contained)


def _oracle_union(sk, config):
    overlap, contained = _oracle_split(sk, config)
    return _oracle_merge(overlap + contained)


def _oracle_blocks(spans, block):
    if not spans:
        return np.zeros(0, np.int64)
    ids = [np.arange(a // block, (z - 1) // block + 1, dtype=np.int64) for a, z in spans]
    return np.unique(np.concatenate(ids))


def _oracle_rows(spans):
    if not spans:
        return np.zeros(0, np.int64)
    return np.concatenate([np.arange(a, z, dtype=np.int64) for a, z in spans])


def _oracle_intersect(rng, spans):
    lo, hi = rng
    return any(a < hi and z > lo for a, z in spans)


def _pairs(spans: RowSpans):
    assert spans.lo.dtype == np.int64 and spans.hi.dtype == np.int64
    assert spans.lo.shape == spans.hi.shape and spans.lo.ndim == 1
    return list(zip(spans.lo.tolist(), spans.hi.tolist()))


def _assert_spans_equal(sk: SortedKeys, config: ScanConfig):
    """overlap, contained, union: element for element, order included."""
    want_o, want_c = _oracle_split(sk, config)
    config = dataclasses.replace(config)  # an empty slot: compute here
    got_o, got_c = sk.candidate_spans_split(config)
    assert _pairs(got_o) == want_o
    assert _pairs(got_c) == want_c
    assert _pairs(sk.candidate_spans(config)) == _oracle_merge(want_o + want_c)
    return want_o, want_c


# -- the corpus: a store shaped like the benchmark's, and its filters -----

def _bench_data():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "harness", "data.py")
    spec = importlib.util.spec_from_file_location("_span_arrays_data", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _iso(ms):
    return f"{np.datetime64(int(ms), 'ms')}Z"


def _bbox(box):
    return "bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*(float(v) for v in box))


def _bbox_during(box, win):
    return f"{_bbox(box)} AND dtg DURING {_iso(win[0])}/{_iso(win[1])}"


def _cell_filters(shape, seed, n=12):
    data = _bench_data()
    rng = data.sub_rng(seed, 11)
    if shape == "analyst":  # analyst-notebook.json: 1-40 degrees, 6 h to 2 weeks
        boxes = data.box_queries(rng, n)
        wins = data.time_windows(rng, n, T0, SPAN_MS)
    else:  # map-viewports.json: 0.25-2 degrees, 6-168 h
        boxes = data.box_queries(rng, n, widths=(0.25, 0.5, 1.0, 2.0))
        wins = data.time_windows(rng, n, T0, SPAN_MS, hours=(6, 24, 72, 168))
    return [_bbox_during(b, w) for b, w in zip(boxes, wins)] + [_bbox(b) for b in boxes[:4]]


def _columns(seed, n=N, ids_from=0):
    data = _bench_data()
    rng = data.sub_rng(seed, 1)
    cx, cy = data.cluster_centres(rng)
    x, y = data.gdelt_points(n, rng, cx, cy)
    t = T0 + rng.integers(0, SPAN_MS, n)
    return np.arange(ids_from, ids_from + n, dtype=np.int64), x, y, t


def _fc(sft, ids, x, y, t):
    return FeatureCollection.from_columns(sft, ids, {
        "name": np.array(["a", "b", "c"])[ids % 3], "dtg": t, "geom": (x.copy(), y.copy())})


def _store(mesh=None, seed=2_600_000_011):
    sft = FeatureType.from_spec(TYPE, "name:String,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    sft.user_data["geomesa.z3.interval"] = "week"
    ds = DataStore(mesh=mesh, tile=4096)
    ds.create_schema(sft)
    cols = _columns(seed)
    ds.write(TYPE, _fc(sft, *cols), check_ids=False)
    return ds, sft, cols


@pytest.fixture(scope="module")
def store():
    return _store()


def _reference_ids(cols_list, f: str):
    """The NumPy reference of a bbox [AND DURING] filter over id, x, y, t
    column sets: bbox closed, DURING open at both ends."""
    got = []
    box = [float(v) for v in f.split("bbox(geom, ")[1].split(")")[0].split(", ")]
    win = None
    if "DURING" in f:
        a, b = f.split("DURING ")[1].split("/")
        win = [int(np.datetime64(s.rstrip("Z"), "ms").astype(np.int64)) for s in (a, b)]
    for ids, x, y, t in cols_list:
        m = (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
        if win is not None:
            m &= (t > win[0]) & (t < win[1])
        got.append(ids[m])
    return np.sort(np.concatenate(got))


def _ids(fc):
    return np.sort(np.asarray(fc.ids).astype(np.int64))


# -- (a) the arrays against the oracle ------------------------------------

@pytest.mark.parametrize("exact", [True, False], ids=["contained_exact", "contained_off"])
@pytest.mark.parametrize("seed", [2_600_000_011, 3_100_000_007])
@pytest.mark.parametrize("shape", ["analyst", "dashboard"])
def test_spans_of_generated_filters_equal_the_tuple_lists(store, shape, seed, exact):
    ds = store[0]
    tables = {n: ds.table(TYPE, n) for n in ("z3", "z2")}
    split = met = 0
    for f in _cell_filters(shape, seed):
        for idx in ds.indexes(TYPE):
            cfg = idx.scan_config(ecql.parse(f))
            if cfg is None or cfg.disjoint:
                continue
            assert cfg.contained_exact and cfg.range_contained is not None
            cfg = dataclasses.replace(cfg, contained_exact=exact)
            want_o, want_c = _assert_spans_equal(tables[idx.name], cfg)
            met += bool(want_o or want_c)
            split += bool(want_o) and bool(want_c)
            assert exact or not want_c
    assert met > (10 if shape == "analyst" else 0)
    assert not exact or shape == "dashboard" or split > 0  # both classes in one config


def _long_string_table():
    rng = np.random.default_rng(5)
    n, n_distinct = 4000, 60
    sft = FeatureType.from_spec("ls", "tag:String:index=true,*geom:Point:srid=4326")
    ds = DataStore()
    ds.create_schema(sft)
    prefix = "SHAREDPREFIX-"  # 13 bytes > the 8-byte primary code
    distinct = np.array([f"{prefix}{i:04d}-tail" for i in range(n_distinct)])
    vals = distinct[rng.integers(0, n_distinct, n)]
    ds.write("ls", FeatureCollection.from_columns(
        sft, np.arange(n),
        {"tag": vals, "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))}))
    idx = next(i for i in ds.indexes("ls") if i.name == "attr_tag")
    return ds.table("ls", "attr_tag"), idx, vals, distinct


@pytest.mark.parametrize("ecql_of", [
    lambda d: f"tag = '{d[17]}'",
    lambda d: f"tag >= '{d[10]}' AND tag <= '{d[20]}'",
    lambda d: f"tag > '{d[3]}'",
    lambda d: f"tag IN ('{d[1]}', '{d[2]}', '{d[40]}')",
    lambda d: "tag = 'SHAREDPREFIX-none'",
], ids=["equality", "range", "open_range", "in_list", "no_hit"])
def test_spans_of_an_attribute_index_with_tie_run_narrowing(ecql_of):
    table, idx, vals, distinct = _long_string_table()
    assert table.subkeys is not None
    cfg = idx.scan_config(ecql.parse(ecql_of(distinct)))
    assert cfg.range_lo2 is not None
    want_o, want_c = _assert_spans_equal(table, cfg)
    # the narrowing did bite: the whole table shares the 8-byte prefix
    assert sum(z - a for a, z in want_o + want_c) < table.n


def _hand_keys():
    """Bins 3, 4 and 6 (none for 5); z values with tie runs."""
    bins = np.repeat(np.array([3, 4, 6], np.int32), [40, 25, 35])
    zs = np.concatenate([
        np.sort(np.repeat(np.arange(10, 50, 2, dtype=np.uint64), 2)),
        np.arange(100, 125, dtype=np.uint64),
        np.sort(np.repeat(np.arange(7, dtype=np.uint64) * 10, 5)),
    ])
    return SortedKeys(None, WriteKeys(bins=bins, zs=zs, device_cols={}), 0)


def _hand_config(ranges, contained=None, exact=True):
    bins, lo, hi = (np.array(v) for v in zip(*ranges)) if ranges else ([], [], [])
    return ScanConfig(
        index="hand",
        range_bins=np.asarray(bins, np.int32),
        range_lo=np.asarray(lo, np.uint64),
        range_hi=np.asarray(hi, np.uint64),
        boxes=None, windows=None,
        range_contained=None if contained is None else np.asarray(contained, bool),
        contained_exact=exact,
    )


HAND = {
    "empty_config": ([], None),
    "bin_the_table_lacks": ([(5, 0, 1000), (7, 0, 9)], [True, False]),
    "lacking_and_present_bins": ([(5, 0, 1000), (4, 101, 103), (2, 0, 5)], [False, True, False]),
    "empty_bins": ([(3, 0, 9), (3, 51, 60), (4, 0, 99), (6, 61, 99)], [False, True, False, True]),
    "ranges_between_keys": ([(3, 11, 11), (3, 13, 13), (6, 1, 9)], None),
    "adjacent": ([(3, 10, 13), (3, 14, 17), (3, 18, 21), (4, 100, 104), (4, 105, 109)],
                 [False, False, False, True, True]),
    "adjacent_across_classes": ([(3, 10, 13), (3, 14, 17), (3, 18, 21)], [False, True, False]),
    "nested": ([(3, 10, 48), (3, 20, 30), (3, 22, 24), (6, 0, 60), (6, 10, 20)],
               [False, False, False, True, True]),
    "nested_across_classes": ([(3, 10, 48), (3, 20, 30), (6, 0, 60), (6, 10, 20)],
                              [False, True, True, False]),
    "overlapping_unsorted": ([(4, 110, 120), (4, 100, 112), (3, 30, 40), (3, 12, 32), (4, 118, 124)],
                             [False, False, True, True, False]),
    "duplicates": ([(3, 12, 20), (3, 12, 20), (3, 12, 18), (6, 20, 20), (6, 20, 20)],
                   [True, True, False, False, False]),
    "bins_interleaved": ([(6, 0, 10), (3, 10, 12), (6, 30, 40), (4, 100, 100), (3, 40, 48)],
                         [True, False, False, True, True]),
    "whole_table": ([(3, 0, 1 << 40), (4, 0, 1 << 40), (6, 0, 1 << 40)], [True, True, True]),
    "one_range": ([(4, 103, 110)], [False]),
}


@pytest.mark.parametrize("exact", [True, False], ids=["contained_exact", "contained_off"])
@pytest.mark.parametrize("case", sorted(HAND))
def test_spans_of_hand_made_ranges_equal_the_tuple_lists(case, exact):
    ranges, contained = HAND[case]
    sk = _hand_keys()
    want_o, want_c = _assert_spans_equal(sk, _hand_config(ranges, contained, exact))
    if case in ("empty_config", "bin_the_table_lacks"):
        assert want_o == want_c == []
    if not exact or contained is None:
        assert want_c == []


def test_random_ranges_equal_the_tuple_lists():
    sk = _hand_keys()
    rng = np.random.default_rng(27)
    for _ in range(200):
        k = int(rng.integers(0, 12))
        bins = rng.choice([2, 3, 4, 5, 6], k)
        lo = rng.integers(0, 130, k)
        hi = lo + rng.integers(0, 40, k)
        cfg = _hand_config(list(zip(bins, lo, hi)), rng.uniform(size=k) < 0.4,
                           bool(rng.integers(0, 2)))
        _assert_spans_equal(sk, cfg)


# -- (b) blocks and helpers from the arrays -------------------------------

def _random_spans(rng, k, reach):
    cuts = np.sort(rng.choice(np.arange(1, reach), 2 * k, replace=False))
    return [(int(a), int(z)) for a, z in zip(cuts[::2], cuts[1::2])]  # merged: gaps between


@pytest.mark.parametrize("block", [4096, 8192, 64])
@pytest.mark.parametrize("k", [0, 1, 2, 7, 60])
def test_candidate_blocks_equal_the_arange_form(block, k):
    rng = np.random.default_rng(block + k)
    me = types.SimpleNamespace(block=block)
    for _ in range(25):
        spans = _random_spans(rng, k, 40 * block if k < 60 else 3 * block)
        arr = RowSpans(*(np.array(spans, np.int64).reshape(-1, 2).T))
        got = IndexTable.candidate_blocks(me, arr)
        want = _oracle_blocks(spans, block)
        assert got.dtype == want.dtype == np.int64
        assert np.array_equal(got, want)


@pytest.mark.parametrize("k", [0, 1, 5, 40])
def test_span_helpers_equal_their_per_span_forms(k):
    rng = np.random.default_rng(k)
    for _ in range(25):
        spans = _random_spans(rng, k, 2000)
        arr = RowSpans(*(np.array(spans, np.int64).reshape(-1, 2).T))
        rows = _oracle_rows(spans)
        assert np.array_equal(tbl._span_rows(arr), rows)
        assert arr.n_rows() == len(rows) == sum(z - a for a, z in spans)
        probe = np.unique(rng.integers(0, 2100, 300)).astype(np.int64)
        assert np.array_equal(tbl._rows_in_spans(probe, arr), np.isin(probe, rows))
        lo = rng.integers(0, 2100, 50)
        hi = lo + rng.integers(1, 100, 50)
        want = np.array([_oracle_intersect(r, spans) for r in zip(lo.tolist(), hi.tolist())])
        assert np.array_equal(tbl._spans_intersect(lo, hi, arr), want)


# -- (c) cost() and the chosen index --------------------------------------

@pytest.mark.parametrize("seed", [2_600_000_011, 3_100_000_007])
@pytest.mark.parametrize("shape", ["analyst", "dashboard"])
def test_cost_and_chosen_index_equal_the_tuple_lists(store, shape, seed):
    ds = store[0]
    chosen = set()
    for f in _cell_filters(shape, seed):
        flt = ecql.parse(f)
        options = []
        for idx in ds.indexes(TYPE):
            cfg = idx.scan_config(flt)
            if cfg is None:
                continue
            rows = sum(z - a for a, z in _oracle_union(ds.table(TYPE, idx.name), cfg))
            want = (rows + 1) * index_priority(idx.name)
            got = ds.planner.cost(TYPE, idx.name, cfg)
            assert type(got) is float and got == want
            options.append((want, idx.name))
        plan = ds.planner.plan(TYPE, f)
        assert plan.index == min(options)[1]
        chosen.add(plan.index)
    assert chosen == {"z3", "z2"}


# -- (d) the config's slot ------------------------------------------------

@pytest.fixture()
def traced():
    """Every root retained by a fresh tracer; knobs restored after."""
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    conf.OBS_SLOW_MS.set(0.0)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    conf.OBS_SLOW_MS.clear()
    obs.install(obs.Tracer())


def _dispatches(trace):
    return [s for s in [trace.root] + list(trace.spans) if s.name == "dispatch"]


def _counted():
    reg = global_registry()
    return (reg.counter_value("geomesa.scan.spans.computed"),
            reg.counter_value("geomesa.scan.spans.reused"))


FILTER = _bbox_during((-30.0, -20.0, 10.0, 20.0), (T0 + 2 * DAY, T0 + 9 * DAY))


def test_the_dispatch_finds_the_spans_cost_left(traced):
    ds, sft, cols = _store()
    c0, r0 = _counted()
    got = ds.query(TYPE, FILTER)
    c1, r1 = _counted()
    assert np.array_equal(_ids(got), _reference_ids([cols], FILTER)) and len(got) > 100
    assert c1 - c0 == 2 and r1 - r0 == 1  # cost() of z3 and of z2; the dispatch
    (d,) = _dispatches(traced()[-1])
    assert d.attrs["spans_reused"] == 1 and d.attrs["blocks"] > 0


def test_a_repeat_filter_computes_no_spans(traced, monkeypatch):
    ds, sft, cols = _store()
    first = ds.query(TYPE, FILTER)
    calls = []
    real = SortedKeys._compute_spans
    monkeypatch.setattr(SortedKeys, "_compute_spans",
                        lambda self, cfg: calls.append(cfg) or real(self, cfg))
    c0, r0 = _counted()
    again = ds.query(TYPE, FILTER)  # the planner's config memo hands back the same configs
    assert calls == [] and _counted() == (c0, r0 + 3)
    assert np.array_equal(_ids(again), _ids(first))
    assert _dispatches(traced()[-1])[0].attrs["spans_reused"] == 1


def _second_batch(sft, n=N):
    cols = _columns(3_100_000_007, n, ids_from=N)
    return cols, _fc(sft, *cols)


@pytest.fixture()
def eager_compaction():
    """Writes past 1,000 delta rows (and an eighth of the table) compact,
    which swaps in new tables, as a large write does at the default."""
    conf.COMPACT_MIN_ROWS.set(1000)
    yield
    conf.COMPACT_MIN_ROWS.clear()


@pytest.mark.parametrize("mutation", ["write", "compact"])
def test_an_old_plan_recomputes_on_the_swapped_table(traced, eager_compaction, mutation):
    ds, sft, cols = _store()
    plan = ds.planner.plan(TYPE, FILTER)
    old = ds.table(TYPE, plan.index)
    held = plan.config._spans
    assert held[0]() is old
    more, fc = _second_batch(sft, N if mutation == "write" else 500)
    ds.write(TYPE, fc, check_ids=False)
    if mutation == "compact":
        assert isinstance(ds.table(TYPE, plan.index), TieredTable)
        ds.compact(TYPE)
    new = ds.table(TYPE, plan.index)
    assert new is not old and not isinstance(new, TieredTable)
    with obs.tracer().trace("query"):
        got = ds.planner.execute(plan)
    assert np.array_equal(_ids(got), _reference_ids([cols, more], FILTER))
    assert len(got) > len(_reference_ids([cols], FILTER))
    (d,) = _dispatches(traced()[-1])
    assert d.attrs["spans_reused"] == 0
    assert plan.config._spans[0]() is new and plan.config._spans[1] is not held[1]
    # and again on the table it now holds: found
    with obs.tracer().trace("query"):
        ds.planner.execute(plan)
    assert _dispatches(traced()[-1])[0].attrs["spans_reused"] == 1


def test_old_plans_through_query_many_recompute_on_the_swapped_table(traced, eager_compaction):
    ds, sft, cols = _store()
    filters = _cell_filters("analyst", 2_600_000_011)[:12]  # bbox AND DURING
    with obs.tracer().trace("query_many"):
        plans = [ds.planner.plan(TYPE, f) for f in filters]
        fresh = ds.planner.execute_many(plans)
    (d,) = [s for s in _dispatches(traced()[-1]) if "members" in s.attrs]
    nested = len(_dispatches(traced()[-1])) - 1
    assert d.attrs["spans_reused"] == len(plans) - nested and d.attrs["groups"] >= 1
    for f, got in zip(filters, fresh):
        assert np.array_equal(_ids(got), _reference_ids([cols], f))
    more, fc = _second_batch(sft)
    ds.write(TYPE, fc, check_ids=False)
    with obs.tracer().trace("query_many"):
        stale = ds.planner.execute_many(plans)
    for s in _dispatches(traced()[-1]):
        assert s.attrs["spans_reused"] == 0
    for f, got in zip(filters, stale):
        assert np.array_equal(_ids(got), _reference_ids([cols, more], f))


def test_a_delta_tier_keeps_its_mains_spans(traced):
    """A write under the compaction threshold leaves the main table in
    place behind a host delta: the spans over the main table stay valid
    (the same object), the delta's rows come on top, and ``cost()`` adds
    the delta as one pseudo-span."""
    ds, sft, cols = _store()
    plan = ds.planner.plan(TYPE, FILTER)
    main = ds.table(TYPE, plan.index)
    more, fc = _second_batch(sft, 700)
    ds.write(TYPE, fc, check_ids=False)
    tiered = ds.table(TYPE, plan.index)
    assert isinstance(tiered, TieredTable) and tiered.main is main
    with obs.tracer().trace("query"):
        got = ds.planner.execute(plan)  # the old plan: its slot still names main
    assert _dispatches(traced()[-1])[0].attrs["spans_reused"] == 1
    want = _reference_ids([cols, more], FILTER)
    assert np.array_equal(_ids(got), want) and len(want) > len(_reference_ids([cols], FILTER))
    assert np.array_equal(_ids(ds.query(TYPE, FILTER)), want)
    spans = tiered.candidate_spans(plan.config)
    under = main.candidate_spans(plan.config)
    assert _pairs(spans) == _pairs(under) + [(main.n, main.n + 700)]
    assert _pairs(under) == _oracle_union(main, plan.config)
    cost = ds.planner.cost(TYPE, plan.index, plan.config)
    assert cost == (under.n_rows() + 700 + 1) * index_priority(plan.index)


def test_count_and_density_share_the_slot(traced):
    ds, sft, cols = _store()
    box = (-30.0, -20.0, 10.0, 20.0)
    c0, r0 = _counted()
    n = ds.count(TYPE, FILTER)
    assert n == len(_reference_ids([cols], FILTER))
    grid = ds.density(TYPE, FILTER, box, 64, 64)
    assert int(round(float(np.asarray(grid).sum()))) == n
    c1, r1 = _counted()
    assert c1 - c0 == 2  # the first plan's two cost() calls; everything after found them
    assert r1 - r0 >= 4
    for t in traced()[-2:]:
        for d in _dispatches(t):
            assert d.attrs["spans_reused"] == 1
