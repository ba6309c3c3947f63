"""Track history by vehicle (benchmark configuration ``tdrive-tracks-1chip``,
cell ``tdrive.track-history``; PR 49) at a small size on the CPU:

(a) the configuration's file against ``BENCHMARK.json`` and the issue's
    sizes; the reference imports nothing of the program;
(b) every class of the mix, the program against the plain reference
    (``harness/reference_attr.py``) through ``query`` and ``query_many``,
    under three seeds of data and traffic;
(c) the strategy each class plans (the attribute index for the id-bound
    classes, a z index where 256 taxis meet a junction for two hours), and
    that every other index that can serve the filter gives the same answer;
(d) ids that share a prefix never leak into each other's answers, and an
    id no taxi has answers nothing;
(e) a taxi whose rows cross several scan blocks and one inside a block both
    clip exactly, alone and in one ``IN``, in a ``query`` and fused;
(f) the spans and counters PR 49 added are there and add up;
(g) the three controls: ``drop-row`` turns the comparison false; ``loose``
    and ``swap-attr`` cannot reach this cell (the traffic file's
    ``controls`` says why), and what each would have broken is broken by
    hand through the op's ``compare``;
(h) ``datagen/tdrive.py``'s stated statistics under three seeds;
(i) every seed's round is the issue's multiset; the warm ladder's rungs;
(j) the six new readers over hand-made spans, None on a program without
    the attributes;
(k) the cell itself through ``benchmark/rehearse.py``.
"""

import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import conf, obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, TAXIS = 1 << 16, 512
SEEDS = (1, 2_600_000_011, 4_900_000_019)
CELL = "tdrive.track-history"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
ROUND = {"track-day": 4, "track-week": 2, "latest": 2, "taxi-box-hour": 1,
         "fleet-32-day": 3, "fleet-256-area": 2, "tracks-many-32": 2}
CLASSES = tuple(ROUND)
NEW_METRICS = ("attr_plan_ms", "plan_lost_ms", "attr_chosen_pct", "attr_scan_ms",
               "attr_clip_keep_pct", "sort_ms")
DAY_MS = 86_400_000


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, store, generators, op and reference, imported
    as the benchmark imports them (tests/test_ais_cell.py's fixture)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        import importlib

        from datagen import tdrive
        from generators import attr_ladder, track_history
        from harness import check, controls, reference_attr
        from harness import requests as rq
        from ops import query_attr
        from stores import datastore

        yield types.SimpleNamespace(
            tdrive=tdrive, ladder=attr_ladder, mix=track_history, check=check,
            controls=controls, ref=reference_attr, rq=rq, op=query_attr, stores=datastore,
            readers={m: importlib.import_module("layer_metrics." + m) for m in NEW_METRICS})
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config(entry):
    cfg = next(c for c in entry["configs"] if c["name"] == "tdrive-tracks-1chip")
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def small(config):
    """The configuration at the tests' size: 2^16 rows of 512 taxis."""
    out = copy.deepcopy(config)
    out["rows"], out["data"]["taxis"] = N, TAXIS
    return out


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "track-history.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module", params=SEEDS)
def loaded(request, bench, small, tmp_path_factory):
    """(seed, columns, store) under each seed: the store loaded as the
    benchmark loads it."""
    cols = bench.tdrive.make(small, N, request.param)
    store = bench.stores.build(small, cols, str(tmp_path_factory.mktemp("run")))
    assert [i.name for i in store.ds._indexes[store.type_name]] == ["z3", "z2", "attr_taxiId"]
    yield request.param, cols, store
    store.close()


@pytest.fixture(scope="module")
def first(bench, small, tmp_path_factory):
    """The first seed's columns and store, for the tests that need one."""
    cols = bench.tdrive.make(small, N, SEEDS[0])
    store = bench.stores.build(small, cols, str(tmp_path_factory.mktemp("run")))
    yield cols, store
    store.close()


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _requests(bench, mix, cols, seed, n):
    return bench.rq.generate(mix["roles"][0], (seed, 100), n, cols.context() | {"seed": seed})


def _compared(bench, cols, store, req, answer=None):
    tally = bench.check.new_tally()
    if answer is None:
        answer = bench.op.embedded(store, req)
    bench.op.compare(tally, cols, req, answer)
    return tally, answer


def _sound(bench, tally):
    return all(tally[n] == 0 for n in bench.check.LIMITS)


def _of_class(bench, mix, cols, seed, klass, k=2):
    got = [r for r in _requests(bench, mix, cols, seed, 128) if r["klass"] == klass][:k]
    assert len(got) == k
    return got


# ------------------------------------------------------------ (a) the files


def test_the_configuration_is_the_issues(config, entry):
    cfg = next(c for c in entry["configs"] if c["name"] == "tdrive-tracks-1chip")
    assert cfg["reduced"] == config["reduced"]
    assert len(cfg["source"]) <= 200 and "T-Drive" in cfg["source"] and "tdrive" in cfg["source"]
    assert config["schema"] == "taxiId:String:index=true,dtg:Date,*geom:Point:srid=4326"
    assert config["indices"] == ["z3", "z2", "attr_taxiId"] and config["z3_interval"] == "week"
    assert config["store"] == "datastore" and config["type_name"] == "tdrive"
    assert config["chips"] == 1 and config["properties"] == {} and config["span_days"] == 7
    assert (config["rows"], config["data"]["taxis"], config["reduced"]) in (
        (1 << 24, 10_357, []), (1 << 23, 5_179, ["rows"]))
    assert config["data"]["generator"] == "tdrive" and config["data"]["city_seed"] == 49
    assert config["data"]["t0"] == "2008-02-02T00:00:00"
    assert "as a string" in config["guarantees"]["answers"]
    assert len(config["about"]["assumed"]) >= 8
    cell = next(w for w in entry["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tdrive-tracks-1chip", "track-history", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in entry["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) | {"scan_roofline", "plan_batched_pct", "many_plan_ms"} <= listed


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "harness", "reference_attr.py")) as fh:
        imports = [ln for ln in fh.read().splitlines() if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import numpy as np"]


def test_the_ops_comparison_has_its_key_and_limit(bench):
    assert bench.check.LIMITS["wrong_sequences"] == 0
    assert bench.check.new_tally()["wrong_sequences"] == 0


# ------------------------------------------------- (b) the plain reference


@pytest.mark.parametrize("klass", CLASSES)
def test_a_class_answers_as_the_plain_reference(klass, bench, mix, loaded):
    seed, cols, store = loaded
    rows = 0
    # a junction's two hours hold few of 2^16 rows: more requests of the classes with a box
    for req in _of_class(bench, mix, cols, seed, klass, 8 if "box" in klass or "area" in klass else 2):
        tally, answer = _compared(bench, cols, store, req)
        assert _sound(bench, tally), (klass, seed, tally)
        assert tally["witnesses"] >= (1 if bench.op.size(answer) else 0)
        rows += tally["rows_compared"]
    assert rows > 0 or klass == "taxi-box-hour"  # one cab at one junction in one hour: often none


def test_the_singles_of_a_batch_answer_as_the_batch(bench, mix, loaded):
    """``query_many`` of 32 track-day filters and the 32 ``query`` calls."""
    seed, cols, store = loaded
    req = _of_class(bench, mix, cols, seed, "tracks-many-32", 1)[0]
    assert len(req["members"]) == 32 == bench.op.members(req)
    many = bench.op.embedded(store, req)
    for member, got in zip(req["members"], many):
        alone = bench.op.embedded(store, member)
        assert np.array_equal(np.sort(alone["ids"]), np.sort(got["ids"]))
        assert np.array_equal(np.sort(got["ids"]), bench.ref.answer(cols, member))


def test_a_sorted_answer_is_a_sequence_and_a_limit_follows_the_sort(bench, first):
    cols, store = first
    taxi = int(np.argmax(cols.rows)) + 1
    lo, n = int(cols.first[taxi - 1]), int(cols.rows[taxi - 1])
    up = bench.op.embedded(store, {"ids": [str(taxi)], "sort": "dtg"})
    assert np.array_equal(up["ids"], np.arange(lo, lo + n))  # a taxi's rows come in time order
    down = bench.op.embedded(store, {"ids": [str(taxi)], "sort": "-dtg", "limit": 3})
    assert down["ids"].tolist() == [lo + n - 1, lo + n - 2, lo + n - 3]
    newest = bench.op.embedded(store, {"ids": [str(taxi)], "sort": "-dtg", "limit": 1})
    assert newest["ids"].tolist() == [lo + n - 1]
    assert newest["witness"]["row"]["taxiId"] == str(taxi)


# ---------------------------------------------------------- (c) the decider

#: at 2^16 rows of 512 taxis a list of 32 is a sixteenth of the fleet against a
#: seventh of the week: the deployment's 32 of 10,357 is two taxis here
SCALED = {"fleet-32-day": 2}


def _scaled(req, klass):
    if klass in SCALED:
        req = dict(req, ids=req["ids"][: SCALED[klass]])
    return req


@pytest.mark.parametrize("klass", CLASSES)
def test_the_strategy_of_a_class_and_every_index_agrees(klass, bench, mix, first):
    from geomesa_tpu.planning.planner import QueryPlan

    cols, store = first
    planner = store.ds.planner
    req = _of_class(bench, mix, cols, SEEDS[0], klass, 1)[0]
    members = [_scaled(m, klass) for m in req.get("members", [req])]
    for member in members[:4]:
        plan = planner.plan(store.type_name, bench.op.ecql(member))
        answer = np.sort(np.asarray(planner.execute(plan).ids))
        assert np.array_equal(answer, bench.ref.answer(cols, dict(member, sort=None, limit=None)))
        costs = {}
        for idx in store.ds.indexes(store.type_name):
            cfg = idx.scan_config(plan.filter)
            if cfg is None:
                continue
            costs[idx.name] = planner.cost(store.type_name, idx.name, cfg)
            forced = QueryPlan(store.type_name, plan.filter, idx.name, cfg)
            assert np.array_equal(np.sort(np.asarray(planner.execute(forced).ids)), answer), idx.name
        # a bare id is the attribute index's alone; a window brings z3 in, a box z2 too
        assert sorted(costs) == (["attr_taxiId"] if klass in ("track-week", "latest") else
                                 ["attr_taxiId", "z2", "z3"] if "box" in member else
                                 ["attr_taxiId", "z3"])
        assert plan.strategy == min(costs, key=costs.get)  # the decider takes the cheapest
        if klass == "fleet-256-area":
            assert plan.strategy in ("z2", "z3"), costs
        elif klass != "taxi-box-hour":  # one cab, one junction, one hour: either, by the data
            assert plan.strategy == "attr_taxiId", (klass, costs)


# ------------------------------------------------ (d) prefixes, absent ids


@pytest.mark.parametrize("taxi", ["1", "10", "100", "11", "5", "51", "512"])
def test_an_id_never_answers_for_one_it_is_a_prefix_of(taxi, bench, first):
    cols, store = first
    got = bench.op.embedded(store, {"ids": [taxi]})
    v = int(taxi)
    assert np.array_equal(np.sort(got["ids"]),
                          np.arange(cols.first[v - 1], cols.first[v - 1] + cols.rows[v - 1]))
    assert set(cols.attrs["taxiId"][got["ids"]]) == {taxi}
    day = [cols.t0 + 2 * DAY_MS, cols.t0 + 3 * DAY_MS]
    tally, _ = _compared(bench, cols, store, {"ids": [taxi], "win": day, "sort": "dtg"})
    assert _sound(bench, tally) and tally["rows_compared"] > 0


@pytest.mark.parametrize("taxi", ["1000", "10000", "0", "513", "007", "1 ", "12a", "", "-1"])
def test_an_id_no_taxi_has_answers_nothing(taxi, bench, first):
    cols, store = first
    week = [cols.t0, cols.t0 + 7 * DAY_MS]
    for req in ({"ids": [taxi]}, {"ids": [taxi], "win": week},
                {"ids": [taxi], "win": week, "box": [116.0, 39.6, 116.8, 40.3]}):
        assert len(bench.op.embedded(store, req)["ids"]) == 0
        assert len(bench.ref.answer(cols, req)) == 0
    both = bench.op.embedded(store, {"ids": [taxi, "7"], "win": week})
    assert np.array_equal(np.sort(both["ids"]), bench.ref.answer(cols, {"ids": ["7"]}))


# ------------------------------------------------- (e) the clip across blocks


@pytest.fixture(scope="module")
def lopsided(bench, small):
    """2^16 hand-made rows: taxi 7 holds 40,000 of them (three scan blocks of
    16,384 in the attribute table), taxi 8 forty, 98 others the rest."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    rng = np.random.default_rng(49)
    rows = np.full(100, (N - 40_040) // 98, np.int64)
    rows[6], rows[7] = 40_000, 40
    rows[-1] += N - rows.sum()
    taxi = np.repeat(np.arange(1, 101, dtype=np.int32), rows)
    t0 = int(np.datetime64(small["data"]["t0"], "ms").astype(np.int64))
    first = np.concatenate([[0], np.cumsum(rows)[:-1]])
    t = t0 + (np.arange(N) - first[taxi - 1]) * (7 * DAY_MS // rows[taxi - 1] // 1000 * 1000)
    cols = types.SimpleNamespace(
        taxi=taxi, t=t.astype(np.int64), x=rng.uniform(116.2, 116.55, N),
        y=rng.uniform(39.75, 40.03, N), t0=t0)
    sft = FeatureType.from_spec("tdrive", small["schema"])
    sft.user_data["geomesa.indices.enabled"] = ",".join(small["indices"])
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("tdrive", FeatureCollection.from_columns(
        sft, np.arange(N, dtype=np.int64),
        {"taxiId": taxi.astype("<U5"), "dtg": cols.t, "geom": (cols.x.copy(), cols.y.copy())}),
        check_ids=False)
    table = ds.table("tdrive", "attr_taxiId")
    assert table.block == 16_384 and table.n_blocks == 4
    return cols, types.SimpleNamespace(ds=ds, type_name="tdrive")


def _forced(store, text):
    """The attribute index's plan of a filter, whatever the decider would take."""
    from geomesa_tpu.planning.planner import QueryPlan

    planner = store.ds.planner
    f = planner.plan("tdrive", text).filter
    idx = next(i for i in store.ds.indexes("tdrive") if i.name == "attr_taxiId")
    cfg = idx.scan_config(f)
    assert cfg.clip_rows
    return QueryPlan("tdrive", f, idx.name, cfg)


@pytest.mark.parametrize("ids", [["7"], ["8"], ["7", "8"], ["8", "9", "70"], ["6", "7", "71"]])
@pytest.mark.parametrize("shape", ["day", "box-day", "box"])
def test_a_values_rows_are_clipped_out_of_the_blocks_the_kernel_hit(ids, shape, bench, lopsided,
                                                                    traced):
    cols, store = lopsided
    req = {"ids": ids}
    if "day" in shape:
        req["win"] = [cols.t0 + 3 * DAY_MS, cols.t0 + 4 * DAY_MS]
    if "box" in shape:
        req["box"] = [116.3, 39.8, 116.45, 39.95]
    planner = store.ds.planner
    want = bench.ref.answer(cols, req)
    with traced.trace("query"):
        got = planner.execute(_forced(store, bench.op.ecql(req)))
    assert len(want) > 0 and np.array_equal(np.sort(np.asarray(got.ids)), want)
    scan = next(s for s in traced.traces()[-1].spans if s.name == "scan")
    # the kernel masks whole blocks by box and window: it hits the neighbours' rows too
    assert scan.attrs["index"] == "attr_taxiId"
    assert scan.attrs["clip_in"] > scan.attrs["clip_kept"] >= len(want)
    if shape == "day":  # whole-second bounds: the mask is precise, so the clip leaves the answer
        assert scan.attrs["clip_kept"] == len(want)
    others = [dict(req, ids=["9"]), dict(req, ids=["70", "7"])]
    fused = planner.execute_many([_forced(store, bench.op.ecql(r)) for r in [req] + others])
    for r, fc in zip([req] + others, fused):
        assert np.array_equal(np.sort(np.asarray(fc.ids)), bench.ref.answer(cols, r))


# ------------------------------------------------- (f) spans and counters


def _spans(trace, name):
    return [s for s in trace.spans if s.name == name]


def test_a_plan_says_which_index_won_and_how_many_it_costed(bench, first, traced):
    cols, store = first
    day = [cols.t0 + DAY_MS, cols.t0 + 2 * DAY_MS]
    bench.op.embedded(store, {"ids": ["42"]})
    bench.op.embedded(store, {"ids": ["42"], "win": day, "sort": "dtg"})
    box = [116.3, 39.85, 116.34, 39.87]
    many_ids = [str(v) for v in range(1, 257)]
    bench.op.embedded(store, {"ids": many_ids, "box": box,
                              "win": [cols.t0 + 36_000_000, cols.t0 + 43_200_000]})
    bare, dayq, area = (_spans(t, "plan")[0] for t in traced.traces()[-3:])
    assert (bare.attrs["index"], bare.attrs["costed"]) == ("attr_taxiId", 1)
    assert (bare.attrs["attr_offered"], bare.attrs["attr_won"]) == (1, 1)
    assert (dayq.attrs["index"], dayq.attrs["costed"]) == ("attr_taxiId", 2)  # z3 costed, lost
    assert area.attrs["index"] in ("z2", "z3") and area.attrs["costed"] == 3
    assert (area.attrs["attr_offered"], area.attrs["attr_won"]) == (1, 0)
    lost = [s for s in traced.traces()[-2].spans
            if s.name in ("plan.probe", "plan.decompose") and s.attrs["index"] != "attr_taxiId"]
    assert {s.attrs["index"] for s in lost} == {"z3", "z2"}  # z2 offers no plan for a window alone
    # the residual: the IN of 256 strings over the z candidates, on the decode span
    decode = _spans(traced.traces()[-1], "decode")[0]
    assert decode.attrs["residual_rows"] == decode.attrs["candidates"]


def test_a_batchs_plan_counts_its_members(bench, mix, first, traced):
    cols, store = first
    req = _of_class(bench, mix, cols, SEEDS[0], "tracks-many-32", 1)[0]
    bench.op.embedded(store, req)
    plan = _spans(traced.traces()[-1], "plan")[0]
    assert plan.attrs["members"] == 32 and plan.attrs["index"] == "attr_taxiId"
    assert plan.attrs["batched"] == 32  # every index of the type offers scan_configs: the arrays took all
    decomposed = [s for s in _spans(traced.traces()[-1], "plan.decompose")
                  if s.attrs["index"] == "attr_taxiId"]
    # one pass over the members the memo lacks (a taxi drawn twice: once), not 32 spans of one
    assert len(decomposed) == 1 and 16 < decomposed[0].attrs["members"] <= 32
    assert (plan.attrs["attr_offered"], plan.attrs["attr_won"]) == (32, 32)
    assert plan.attrs["costed"] == 64  # the attribute index and z3, a member


def test_a_sort_span_counts_what_it_ordered_and_kept(bench, first, traced):
    cols, store = first
    taxi = str(int(np.argmax(cols.rows)) + 1)
    n = int(cols.rows.max())
    bench.op.embedded(store, {"ids": [taxi], "sort": "-dtg", "limit": 1})
    bench.op.embedded(store, {"ids": [taxi], "sort": "dtg"})
    bench.op.embedded(store, {"ids": [taxi]})
    latest, track, bare = traced.traces()[-3:]
    assert [(s.attrs["rows"], s.attrs["kept"]) for s in _spans(latest, "sort")] == [(n, 1)]
    assert [(s.attrs["rows"], s.attrs["kept"]) for s in _spans(track, "sort")] == [(n, n)]
    assert _spans(bare, "sort") == []
    decode = _spans(latest, "decode")[0]
    assert _spans(latest, "sort")[0].parent_id == decode.span_id
    # a pure range scan runs no kernel and clips nothing
    assert "clip_in" not in _spans(latest, "scan")[0].attrs


def test_nothing_is_counted_where_nothing_is_traced(bench, first):
    cols, store = first
    got = bench.op.embedded(store, {"ids": ["42"], "sort": "-dtg", "limit": 2})
    assert len(got["ids"]) == 2


# ------------------------------------------------------------ (g) controls


def test_the_traffic_file_says_which_controls_reach_the_cell(mix):
    said = mix["controls"]
    assert "drop-row" in said and "loose" in said and "swap-attr" in said
    assert "cannot reach" in said and "tests/test_tdrive_cell.py" in said


def test_a_dropped_row_is_not_correct(bench, mix, first):
    cols, store = first
    undo = bench.controls.arm("drop-row")
    try:
        tally = bench.check.new_tally()
        for klass in ("track-day", "track-week", "fleet-32-day", "tracks-many-32"):
            req = _of_class(bench, mix, cols, SEEDS[0], klass, 1)[0]
            bench.op.compare(tally, cols, req, bench.op.embedded(store, req))
    finally:
        undo()
    assert tally["wrong_sequences"] >= 2 and tally["wrong_answers"] >= 2


def test_loose_cannot_reach_a_filter_that_names_taxis(bench, mix, first):
    """Every filter of the mix holds an attribute predicate, so the device's
    mask never decides it and the ``loose`` hint changes nothing."""
    cols, store = first
    undo = bench.controls.arm("loose")
    try:
        for klass in ("track-day", "taxi-box-hour", "fleet-32-day", "fleet-256-area"):
            for req in _of_class(bench, mix, cols, SEEDS[0], klass):
                tally, _ = _compared(bench, cols, store, req)
                assert _sound(bench, tally), klass
    finally:
        undo()


def test_swap_attr_has_no_integer_to_swap(bench, first):
    cols, store = first
    undo = bench.controls.arm("swap-attr")
    try:
        with pytest.raises((StopIteration, RuntimeError)):
            bench.op.embedded(store, {"ids": ["42"]})
    finally:
        undo()


FAULTS = {
    "a row outside the window let in": ("wrong_answers", False),  # what loose would break
    "a row outside the box let in": ("wrong_answers", False),
    "a neighbour's row let in": ("wrong_answers", False),
    "a row lost": ("wrong_answers", False),
    "a row twice": ("doubled_rows", False),
    "another taxi's id on the witness": ("wrong_attributes", False),  # what swap-attr would break
    "another row's time on the witness": ("wrong_attributes", False),
    "two rows out of order": ("wrong_sequences", True),
    "the limit cut before the sort": ("wrong_sequences", True),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_answer_is_not_correct(fault, bench, first):
    cols, store = first
    key, sorted_request = FAULTS[fault]
    taxi = int(np.argmax(cols.rows)) + 1
    lo, n = int(cols.first[taxi - 1]), int(cols.rows[taxi - 1])
    day = [cols.t0 + 2 * DAY_MS, cols.t0 + 3 * DAY_MS]
    req = {"ids": [str(taxi)], "win": day, "box": [116.0, 39.6, 116.8, 40.3]}
    if sorted_request:
        req["sort"] = "dtg"
    if fault == "the limit cut before the sort":
        req = {"ids": [str(taxi)], "sort": "-dtg", "limit": 1}
    tally, answer = _compared(bench, cols, store, req)
    assert _sound(bench, tally) and len(answer["ids"]) >= (1 if "limit" in req else 3)
    ids, witness = answer["ids"].copy(), copy.deepcopy(answer["witness"])
    inside = np.isin(np.arange(lo, lo + n), ids)
    if fault == "a row outside the window let in":
        ids = np.append(ids, lo + int(np.flatnonzero(~inside)[0]))
    elif fault == "a row outside the box let in":
        ids = np.append(ids, int(np.argmax(np.abs(cols.x - 116.4))))  # a mislocated fix
    elif fault == "a neighbour's row let in":
        ids = np.append(ids, lo + n)  # the next taxi's first row
    elif fault == "a row lost":
        ids = ids[:-1]
    elif fault == "a row twice":
        ids = np.append(ids, ids[0])
    elif fault == "another taxi's id on the witness":
        witness["row"]["taxiId"] = str(taxi + 1)
    elif fault == "another row's time on the witness":
        witness["row"]["dtg"] = int(cols.t[witness["id"] - 1])
    elif fault == "two rows out of order":
        ids[[0, 1]] = ids[[1, 0]]
    elif fault == "the limit cut before the sort":
        ids = np.asarray([lo])  # the first of the taxi's rows, not the newest
    tally, _ = _compared(bench, cols, store, req, {"ids": ids, "witness": witness})
    assert tally[key] > 0, (fault, tally)
    assert not _sound(bench, tally)


# ------------------------------------------------ (h) the generator's statistics


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fleet_reports_as_the_release_does(seed, bench, config):
    """At 2^20 rows: 647 taxis, a track as long as the deployment's."""
    n = 1 << 20
    cols = bench.tdrive.make(config, n, seed)
    assert len(cols) == n == int(cols.rows.sum()) and cols.fleet == 647
    assert np.array_equal(cols.taxi, np.repeat(np.arange(1, 648), cols.rows))
    assert np.array_equal(cols.attrs["taxiId"], cols.taxi.astype("<U5"))
    same = cols.taxi[1:] == cols.taxi[:-1]
    gaps = np.diff(cols.t)[same] / 1000.0
    assert gaps.min() >= 1.0 and np.all(cols.t % 1000 == 0)  # whole seconds, distinct, ascending
    assert cols.t.min() >= cols.t0 and cols.t.max() < cols.t0 + 7 * DAY_MS
    on_duty = gaps[gaps < 1800.0]  # a shift's end is a gap of hours
    assert 170.0 <= on_duty.mean() <= 185.0
    true_fix = np.ones(n, bool)
    true_fix[cols.junk] = False
    lon_m = 111_320.0 * np.cos(np.radians(39.9))
    step = np.hypot(np.diff(cols.x) * lon_m, np.diff(cols.y) * 111_320.0)
    step = step[same & true_fix[1:] & true_fix[:-1]]
    assert 550.0 <= step.mean() <= 700.0
    assert cols.rows.max() >= 10 * np.median(cols.rows)
    assert len(cols.junk) == round(n / 2000)
    x, y = cols.x[true_fix], cols.y[true_fix]
    ring, outer = bench.tdrive.RING, bench.tdrive.OUTER
    in_ring = (x >= ring[0]) & (x <= ring[2]) & (y >= ring[1]) & (y <= ring[3])
    assert 0.87 <= in_ring.mean() <= 0.93
    assert np.all((x >= outer[0]) & (x <= outer[2]) & (y >= outer[1]) & (y <= outer[3]))
    assert len(np.unique(np.stack([cols.x, cols.y], 1), axis=0)) == n  # f64 and free
    heavy = cols.context()["heavy"]
    assert len(heavy) == 64 and cols.rows[heavy[0] - 1] == cols.rows.max()
    assert np.all(np.diff(cols.rows[np.asarray(heavy) - 1]) <= 0)


def test_the_city_is_the_deployments_and_a_seed_gives_the_same_columns(bench, small):
    a, b, c = (bench.tdrive.make(small, N, s) for s in (SEEDS[0], SEEDS[0], SEEDS[1]))
    assert np.array_equal(a.x, b.x) and np.array_equal(a.t, b.t) and np.array_equal(a.rows, b.rows)
    assert not np.array_equal(a.rows, c.rows)
    assert a.context()["cx"] == c.context()["cx"] and a.context()["w"] == c.context()["w"]
    assert a.fleet == TAXIS and a.row(5) == {"dtg": int(a.t[5]), "taxiId": "1",
                                             "geom": [float(a.x[5]), float(a.y[5])]}


def test_a_smaller_table_holds_fewer_taxis_not_thinner_tracks(bench, config):
    assert bench.tdrive.fleet_size(config, 1 << 24) == 10_357
    assert bench.tdrive.fleet_size(config, 1 << 23) == 5_179  # the one rule's every second taxi
    assert bench.tdrive.fleet_size(config, 1 << 20) == 647
    assert bench.tdrive.fleet_size(config, 1 << 16) == 512  # under that the tracks thin


# --------------------------------------------------- (i) the mix, the ladder


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_is_the_issues_multiset(seed, bench, mix, first):
    cols, _ = first
    role = mix["roles"][0]
    assert role["params"]["round"] == ROUND and sum(ROUND.values()) == 16
    assert role["requests_per_client"] % 4000 == 0 and role["requests_per_client"] >= 16_000
    reqs = _requests(bench, mix, cols, seed, 64)
    ctx = cols.context()
    for r in range(4):
        one = reqs[16 * r:16 * r + 16]
        assert sorted(q["klass"] for q in one) == sorted(k for k, c in ROUND.items()
                                                          for _ in range(c))
        assert sum(bench.op.members(q) for q in one) == 78
        weeks = [q for q in one if q["klass"] == "track-week"]
        assert int(weeks[1]["ids"][0]) in ctx["heavy"]  # the round's second is a heavy taxi
    for q in reqs:
        k = q["klass"]
        for m in q.get("members", [q]):
            assert len(set(m["ids"])) == len(m["ids"]) == {
                "fleet-32-day": 32, "fleet-256-area": 256}.get(k, 1)
            assert all(1 <= int(s) <= TAXIS and str(int(s)) == s for s in m["ids"])
            hours = {"track-day": 24, "taxi-box-hour": 1, "fleet-32-day": 24,
                     "fleet-256-area": 2, "tracks-many-32": 24}.get(k)
            assert ("win" in m) == (hours is not None)
            if hours:
                lo, hi = m["win"]
                assert hi - lo == hours * 3_600_000 and (lo - ctx["t0"]) % 3_600_000 == 0
                assert ctx["t0"] <= lo and hi <= ctx["t0"] + ctx["span_ms"]
                assert hours != 24 or (lo - ctx["t0"]) % DAY_MS == 0
            assert ("box" in m) == (k in ("taxi-box-hour", "fleet-256-area"))
            if "box" in m:
                x0, y0, x1, y1 = m["box"]
                assert abs((x1 - x0) - 0.04) < 1e-9 and abs((y1 - y0) - 0.02) < 1e-9
            assert m.get("sort") == {"track-day": "dtg", "track-week": "dtg",
                                     "latest": "-dtg"}.get(k)
            assert m.get("limit") == (1 if k == "latest" else None)
        if k == "tracks-many-32":
            assert len(q["members"]) == 32
            assert len({m["ids"][0] for m in q["members"]}) == 32
            assert len({tuple(m["win"]) for m in q["members"]}) == 1


def test_the_warm_ladder_asks_every_rung(bench, mix, first):
    cols, _ = first
    warm = mix["warm"]
    assert warm[0] == {"requests": 160} and warm[1]["generator"] == "attr_ladder"
    params = warm[1]["params"]
    assert params["ids"] == [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024]
    reqs = bench.ladder.generate(params, np.random.default_rng(3), 0, cols.context())
    sizes = sorted({len(q["ids"]) for q in reqs if "ids" in q})
    assert sizes == [1, 2, 4, 8, 16, 32, 64, 128, 256, 512]  # 1,024 is the whole of 512 taxis
    by = {}
    for q in reqs:
        by.setdefault(q["klass"], []).append(q)
    assert len(by["warm-ids"]) == len(by["warm-ids-day"]) == len(by["warm-ids-box-hour"]) == 11
    assert all("win" not in q and "box" not in q for q in by["warm-ids"])
    assert all("win" in q and "box" in q for q in by["warm-ids-box-hour"])
    assert [len(q["members"]) for q in by["warm-many"]] == params["many"]
    assert len(by["warm-area"]) == len(params["areas"])
    heavy = str(cols.context()["heavy"][0])
    assert all(heavy in q["ids"] for q in by["warm-ids"])


# ------------------------------------------------------------- (j) readers


def _span(i, trace, root, name, dur_ms, parent=None, self_ms=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": (dur_ms if self_ms is None else self_ms) / 1e3,
            "attrs": attrs}


def _view(with_attrs=True):
    def plan(i, trace, root, dur, parent, **a):
        return _span(i, trace, root, "plan", dur, parent, **(a if with_attrs else {"members": 1}))

    day = _span(1, 1, "query", "query", 6.0)
    area = _span(20, 2, "query", "query", 40.0)
    many = _span(40, 3, "query_many", "query_many", 60.0, members=2)
    bare = _span(60, 4, "query", "query", 1.0)
    spans = [
        day, dict(day),  # roots twice, as the harness lists them
        plan(2, 1, "query", 5.0, 1, members=1, index="attr_taxiId", costed=2, attr_offered=1,
             attr_won=1),
        _span(3, 1, "query", "plan.probe", 0.1, 2, index="z3"),
        _span(4, 1, "query", "plan.decompose", 3.0, 2, index="z3", ranges=216),
        _span(5, 1, "query", "plan.probe", 0.1, 2, index="z2"),
        _span(6, 1, "query", "plan.decompose", 0.02, 2, index="z2", ranges=0),
        _span(7, 1, "query", "plan.probe", 0.1, 2, index="attr_taxiId"),
        _span(8, 1, "query", "plan.decompose", 0.3, 2, index="attr_taxiId", ranges=1),
        _span(9, 1, "query", "scan", 0.5, 1, self_ms=0.4, index="attr_taxiId",
              **({"clip_in": 2000, "clip_kept": 200} if with_attrs else {})),
        _span(10, 1, "query", "decode", 0.4, 1, candidates=200),
        *([_span(11, 1, "query", "sort", 0.05, 10, rows=200, kept=200)] if with_attrs else []),
        area, dict(area),
        plan(21, 2, "query", 12.0, 20, members=1, index="z2", costed=3, attr_offered=1,
             attr_won=0),
        _span(22, 2, "query", "plan.decompose", 6.0, 21, index="z3"),
        _span(23, 2, "query", "plan.decompose", 2.0, 21, index="z2"),
        _span(24, 2, "query", "plan.decompose", 1.0, 21, index="attr_taxiId"),
        _span(25, 2, "query", "scan", 2.0, 20, index="z2"),
        many, dict(many),
        plan(41, 3, "query_many", 30.0, 40, members=2, index="attr_taxiId", costed=4,
             attr_offered=2, attr_won=2),
        _span(42, 3, "query_many", "plan.decompose", 4.0, 41, index="z3"),
        _span(43, 3, "query_many", "plan.decompose", 0.2, 41, index="attr_taxiId"),
        _span(44, 3, "query_many", "plan.decompose", 5.0, 41, index="z3"),
        _span(45, 3, "query_many", "plan.decompose", 0.2, 41, index="attr_taxiId"),
        _span(46, 3, "query_many", "scan", 1.0, 40, index="attr_taxiId", member=0,
              **({"clip_in": 1000, "clip_kept": 100} if with_attrs else {})),
        _span(47, 3, "query_many", "scan", 0.2, 40, index="attr_taxiId", member=1,
              **({"clip_in": 1000, "clip_kept": 300} if with_attrs else {})),
        bare, dict(bare),
        plan(61, 4, "query", 0.4, 60, members=1, index="attr_taxiId", costed=1, attr_offered=1,
             attr_won=1),
        _span(62, 4, "query", "scan", 0.01, 60, index="attr_taxiId"),  # a pure range: no kernel
        _span(63, 4, "query", "decode", 0.5, 60, candidates=1600),
        *([_span(64, 4, "query", "sort", 0.25, 63, rows=1600, kept=1)] if with_attrs else []),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [6.0, 40.0, 60.0, 1.0], "between_s": [0.0001]}}


def test_the_readers_read_the_new_spans_and_counters(bench):
    read = {m: r.read(_view()) for m, r in bench.readers.items()}
    assert read["attr_plan_ms"] == pytest.approx(5.0)  # of 5.0, 30.0 and 0.4
    # the day request lost z3's and z2's 3.22 ms, the area 7.0 (z3's and the attribute
    # index's), the batch z3's 9.0; the bare id costed one index and is no sample
    assert read["plan_lost_ms"] == pytest.approx(7.0)
    assert read["attr_chosen_pct"] == pytest.approx(100.0 * 4 / 5)
    assert read["attr_scan_ms"] == pytest.approx(0.3)  # of 0.4, 1.0, 0.2, 0.01 self
    assert read["attr_clip_keep_pct"] == pytest.approx(100.0 * 600 / 4000)
    assert read["sort_ms"] == pytest.approx(0.15)  # query roots: 0.05 and 0.25


def test_the_readers_find_nothing_on_a_program_before_pr_49(bench):
    """The parent opens the same spans without the attributes: None, not a raise."""
    read = {m: r.read(_view(with_attrs=False)) for m, r in bench.readers.items()}
    assert read == dict.fromkeys(NEW_METRICS) | {"attr_scan_ms": pytest.approx(0.3)}
    empty = {"workload": CELL, "spans": [], "device": None, "client": {}}
    assert all(r.read(empty) is None for r in bench.readers.values())


def _configs_equal(got, want):
    import dataclasses

    assert (got is None) == (want is None)
    for fld in dataclasses.fields(got) if got is not None else ():
        a, b = getattr(got, fld.name), getattr(want, fld.name)
        if fld.name == "_spans":
            assert np.array_equal(a[1].union.lo, b[1].union.lo) and \
                np.array_equal(a[1].union.hi, b[1].union.hi)
        elif isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape and np.array_equal(a, b), fld.name
        else:
            assert type(a) is type(b) and a == b, fld.name


def test_every_request_of_the_mix_plans_as_an_index_at_a_time(bench, mix, loaded):
    """``plan`` and ``plan_many`` through the array stages against
    ``_select``, each index decomposing and costing the filter on its own:
    the same index, ranges, sub-words, boxes, windows, flags, estimate and
    explain trail, for every class of the mix."""
    from geomesa_tpu.planning.explain import Explainer

    seed, cols, store = loaded
    ds, name = store.ds, store.type_name
    pl = ds.planner
    seen = set()
    for req in _requests(bench, mix, cols, seed, 64):
        filters = [bench.op.ecql(m) for m in req.get("members", [req])]
        seen.add(req["klass"])
        want, trails = [], []
        pl.invalidate_config_memo()
        for f in filters:
            trails.append(Explainer())
            prepared = pl._prepare(name, f, True)
            trails[-1](f"Planning query on '{name}': {type(prepared).__name__}")
            want.append(pl._select(name, prepared, req.get("limit"), trails[-1]))
            pl._estimate_rows([want[-1]], [trails[-1]])
        pl.invalidate_config_memo()
        got = pl.plan_many(name, filters, limit=req.get("limit"))
        pl.invalidate_config_memo()
        for f, g, w, trail in zip(filters, got, want, trails):
            exp = Explainer()
            one = pl.plan(name, f, limit=req.get("limit"), explain=exp)
            assert exp.lines == trail.lines, f
            for p in (g, one):
                assert (p.index, p.strategy, p.limit, p.estimated_rows, repr(p.filter)) == (
                    w.index, w.strategy, w.limit, w.estimated_rows, repr(w.filter)), f
                _configs_equal(p.config, w.config)
    assert seen == set(CLASSES)


# ---------------------------------------------------------------- (k) the cell


def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL, "--rows",
         str(1 << 17), "--seconds", "5", "--seed", "4900000019", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    read = line["rehearsal_metrics"]
    assert set(NEW_METRICS) | {"query_p50_ms", "plan_ms", "many_plan_ms", "plan_batched_pct",
                               "load_rows_per_s"} <= set(read)
    assert 80.0 < read["attr_chosen_pct"]["value"] <= 100.0 * 76 / 78 + 1e-9
    assert 0 < read["attr_clip_keep_pct"]["value"] < 100
    assert read["plan_batched_pct"]["value"] == 100.0
    assert read["plan_lost_ms"]["value"] > 0 and read["sort_ms"]["value"] > 0
    window = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "window"' in s)
    assert window["compile_requests_in_window"] == 0
    latency = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "latency"' in s)
    assert set(latency["by_class"]) == set(CLASSES)
    compared = {json.loads(s)["number"] for s in out.stdout.splitlines()
                if '"phase": "compared"' in s}
    assert "wrong_sequences" in compared
