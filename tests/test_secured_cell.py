"""A secured store (benchmark configuration ``gdelt-secured-1chip``, cell
``gdelt-secured.analyst``; PR 53) at a small size on the CPU:

(a) the configuration's file against ``BENCHMARK.json``, the issue's sizes
    and ``gdelt-events-1chip``; the reference imports nothing of the program;
    the traffic is ``analyst-notebook``'s key for key and draw for draw;
(b) the reference's evaluator of the label grammar against
    ``security.visible`` over seeded random expressions (every production,
    blanks, the empty label, depth to 4) and over labels that do not parse;
(c) every class of the mix, the program against the plain reference
    (``harness/reference_secured.py``), under three auth sets: ``user,ops``,
    the empty set (public rows only) and all six tokens (every row);
(d) the paths that had not been held to a reference under auths:
    ``query_many``'s members each masked, an ``Or`` across indexes (the
    union's branches skip visibility; the merge applies it once), a limit
    applied after the mask, ``count`` and ``density`` by the host route;
(e) the control: the same table opened WITHOUT auths is refused by the
    secured comparison with ``vis_leaks`` > 0, and its ``count`` and
    ``density`` are refused too;
(f) the density comparison admits both semantics the configuration states
    (the exact f64 host route, the device aggregation's f32 one) and refuses
    a grid that holds one row the caller may not read;
(g) the ``vis`` span's ``rows``, ``kept``, ``labels`` and ``coded``
    against NumPy counts, under an embedded query (inside ``decode``, before
    its gather: PR 54) and under the served handler's per-request auths;
    ``vis_fallback`` on the roots of ``count``, ``density``, ``bounds`` and a
    ``Count()`` estimate; nothing without auths;
(h) ``datagen/gdelt_secured.py``: ``datagen/gdelt.py``'s columns value for
    value, the replayed groups, the label shares the configuration states;
(i) the new readers (PR 53's four, PR 54's ``vis_coded_pct``) over hand-made
    spans, None where there is nothing;
(j) the cell itself through ``benchmark/rehearse.py``.
"""

import copy
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import conf, obs, security

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N = 1 << 16
SEEDS = (1, 2_600_000_011)
CELL = "gdelt-secured.analyst"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
ROUND = {"z3": 14, "z2": 8, "pip": 3, "raster": 3, "count": 4, "density": 4, "query_many": 4}
CLASSES = tuple(ROUND)
NEW_METRICS = ("vis_ms", "vis_keep_pct", "vis_share_pct", "agg_vis_fallback_pct",
               "vis_coded_pct")
TOKENS = ("user", "ops", "intel", "admin", "partner", "legal")
AUTH_SETS = {"user-ops": ("user", "ops"), "nobody": (), "everybody": TOKENS}
#: (data seed, auth set) of the stores the classes are asked of
STORES = ((SEEDS[0], "user-ops"), (SEEDS[1], "user-ops"), (SEEDS[0], "nobody"),
          (SEEDS[0], "everybody"))


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, store, generators, ops and reference,
    imported as the benchmark imports them (tests/test_tdrive_cell.py's)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        import importlib

        from datagen import gdelt, gdelt_secured
        from generators import notebook, notebook_secured
        from harness import check, check_secured, reference, reference_secured
        from harness import requests as rq
        from stores import datastore, datastore_secured

        yield types.SimpleNamespace(
            gdelt=gdelt, data=gdelt_secured, notebook=notebook, mix=notebook_secured,
            check=check, check_secured=check_secured, plain=reference, ref=reference_secured,
            rq=rq, open_store=datastore, stores=datastore_secured,
            readers={m: importlib.import_module("layer_metrics." + m) for m in NEW_METRICS})
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _config(entry, name):
    cfg = next(c for c in entry["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def config(entry):
    return _config(entry, "gdelt-secured-1chip")


@pytest.fixture(scope="module")
def sibling(entry):
    return _config(entry, "gdelt-events-1chip")


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def mix():
    return _traffic("secured-notebook")


def _build(bench, config, seed, auths, tmp):
    small = copy.deepcopy(config)
    small["rows"], small["auths"] = N, list(auths)
    cols = bench.data.make(small, N, seed)
    return cols, bench.stores.build(small, cols, str(tmp.mktemp("run")))


@pytest.fixture(scope="module", params=STORES, ids=lambda p: f"{p[0]}-{p[1]}")
def loaded(request, bench, config, tmp_path_factory):
    """(seed, columns, store): the table loaded as the benchmark loads it,
    opened under one of the three auth sets."""
    seed, who = request.param
    cols, store = _build(bench, config, seed, AUTH_SETS[who], tmp_path_factory)
    assert store.ds.auths == AUTH_SETS[who] == cols.auths
    yield seed, cols, store
    store.close()


@pytest.fixture(scope="module")
def first(bench, config, tmp_path_factory):
    """The first seed's columns and its store under ``user,ops``."""
    cols, store = _build(bench, config, SEEDS[0], AUTH_SETS["user-ops"], tmp_path_factory)
    yield cols, store
    store.close()


@pytest.fixture(scope="module")
def opened(bench, config, first, tmp_path_factory):
    """The control: the first seed's table in a store opened WITHOUT auths
    (``stores/datastore.py``: every row is everybody's)."""
    cols, _ = first
    small = copy.deepcopy(config)
    small["rows"] = N
    store = bench.open_store.build(small, cols, str(tmp_path_factory.mktemp("open")))
    assert store.ds.auths is None
    yield store
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


def _of_class(bench, mix, cols, seed, klass, k=2):
    """The ``k`` requests of a class with the largest answers among the
    first ten rounds (at 2^16 rows a six-hour window over a small box is
    empty: a test that needs rows asks where there are some)."""
    got = [r for r in _requests(bench, mix, cols, seed, 400) if r["klass"] == klass]

    def rows(req):
        return sum(len(bench.plain.ref_ids(cols, m["box"], m.get("win"), m.get("ring")))
                   for m in req.get("members", [req]))

    got.sort(key=rows, reverse=True)
    assert len(got) >= k
    return got[:k]


def _compared(bench, cols, store, req, answer=None):
    op = bench.rq.op_of(req)
    tally = bench.check.new_tally()
    if answer is None:
        answer = op.embedded(store, req)
    op.compare(tally, cols, req, answer)
    return tally, answer


def _sound(bench, tally):
    return all(tally[n] == 0 for n in bench.check.LIMITS)


def _seen(cols, auths=None):
    """NumPy's own word on which rows ``auths`` may read: the generator's
    label codes against a table made with ``security.visible``."""
    auths = cols.auths if auths is None else auths
    from_code = np.array([security.visible(e, auths) for e in _expressions()])
    return from_code[cols.label_code]


def _expressions():
    return ("", "user", "ops", "user|ops", "user&ops", "(ops|intel)&user", "user&(ops|partner)",
            "intel", "ops&intel", "admin", "admin&intel", "partner|admin")


# ------------------------------------------------------------ (a) the files


def test_the_configuration_is_the_issues(config, sibling, entry):
    cfg = next(c for c in entry["configs"] if c["name"] == "gdelt-secured-1chip")
    assert cfg["reduced"] == config["reduced"] == ["rows", "span_days"]
    assert set(config["reduced_why"]) == {"rows", "span_days"}
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    assert "Data Security" in cfg["source"] and "geomesa.security.auths" in cfg["source"]
    spec, user_data = config["schema"].split(";")
    assert user_data == "geomesa.vis.field=visibility"
    attrs = spec.split(",")
    assert "visibility:String" in attrs
    assert ",".join(a for a in attrs if a != "visibility:String") == sibling["schema"]
    assert len(attrs) == 28
    for key in ("rows", "span_days", "indices", "z3_interval", "type_name", "chips", "properties",
                "jax"):
        assert config[key] == sibling[key], key
    assert config["rows"] == 1 << 21 and config["span_days"] == 16
    assert config["store"] == "datastore_secured" and config["auths"] == ["user", "ops"]
    assert config["data"] == dict(sibling["data"], generator="gdelt_secured", label_seed=53)
    assert config["guarantees"]["answers"] == sibling["guarantees"]["answers"]
    said = config["guarantees"]["visibility"]
    assert "in no answer" in said and "no count" in said and "no pixel" in said
    assert "empty label is everybody's" in said and "exact, not estimated" in said
    labels = config["about"]["labels"]
    assert tuple(labels["tokens"]) == TOKENS and tuple(labels["expressions"]) == _expressions()
    assert len(config["about"]["assumed"]) >= 6
    cell = next(w for w in entry["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gdelt-secured-1chip", "secured-notebook", 1)
    assert len(cell["why"]) <= 200 and "50 MB" in cell["why"] and "host" in cell["why"]
    assert len(entry["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in entry["workloads"]) == 1
    listed = {m["name"] for m in entry["per_layer"] if CELL in m.get("workloads", ())}
    assert set(NEW_METRICS) | {"decode_ms", "plan_ms", "many_plan_ms", "gather_ms"} <= listed
    assert not listed & {"agg_wait_ms", "agg_pull_ms", "density_roofline", "density_slot_us",
                         "density_windowed_pct", "density_rows_per_s"}
    for m in entry["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == (
                "query_p95_ms" if m["name"] == "vis_coded_pct" else "queries_per_s")
    ends = {m["name"] for m in entry["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert ends == {"queries_per_s", "query_p95_ms", "single_mean_ms", "setup_s"}


def test_the_reference_imports_nothing_of_the_program():
    import ast

    def imported(name):
        with open(os.path.join(BENCH, "harness", name)) as fh:
            tree = ast.parse(fh.read())
        return sorted({a.name if isinstance(n, ast.Import) else n.module
                       for n in ast.walk(tree) if isinstance(n, (ast.Import, ast.ImportFrom))
                       for a in n.names})

    assert imported("reference_secured.py") == ["__future__", "harness", "numpy"]
    assert imported("reference.py") == ["__future__", "numpy"]


def test_the_comparison_has_its_key_and_limit(bench):
    assert bench.data.TOKENS == TOKENS and bench.data.EXPRESSIONS == _expressions()
    assert bench.check.LIMITS["vis_leaks"] == 0
    assert bench.check.new_tally()["vis_leaks"] == 0


def test_the_traffic_is_the_analysts_key_for_key(mix):
    theirs = _traffic("analyst-notebook")
    assert set(mix) == set(theirs)
    for key in theirs:
        if key != "roles":
            assert mix[key] == theirs[key], key
    (mine,), (role,) = mix["roles"], theirs["roles"]
    assert set(mine) == set(role)
    assert mine["generator"] == "notebook_secured" and role["generator"] == "notebook"
    for key in role:
        if key != "generator":
            assert mine[key] == role[key], key
    assert mine["params"]["round"] == ROUND and mine["requests_per_client"] == 20_000


@pytest.mark.parametrize("seed", SEEDS + (4_900_000_019,))
def test_a_seed_draws_the_analysts_requests(seed, bench, mix, first):
    cols, _ = first
    theirs = _traffic("analyst-notebook")["roles"][0]
    ctx = cols.context() | {"seed": seed}
    want = bench.rq.generate(theirs, (seed, 100), 400, ctx)
    got = bench.rq.generate(mix["roles"][0], (seed, 100), 400, ctx)
    assert [dict(r, op=r["op"] + "_secured") for r in want] == got
    per_round = sum(ROUND.values())  # 40: the issue's "36" leaves the four query_many out
    for r in range(10):
        one = got[per_round * r: per_round * (r + 1)]
        assert {k: sum(q["klass"] == k for q in one) for k in ROUND} == ROUND
    assert {q["op"] for q in got} == {"query_secured", "count_secured", "density_secured",
                                      "query_many_secured"}


# ----------------------------------------------------- (b) the two evaluators


def _random_label(rng, depth):
    """A seeded expression of the grammar: a token, ``|``, ``&``, parentheses
    on either side, blanks anywhere between symbols."""
    def blank():
        return " " * int(rng.integers(0, 3)) if rng.random() < 0.3 else ""

    def token():
        return str(rng.choice(TOKENS + ("a.b", "x-1", "ns:role", "_u")))

    def expr(d):
        kind = rng.integers(0, 4) if d > 0 else 0
        if kind == 0:
            return blank() + token() + blank()
        if kind == 3:
            return blank() + "(" + expr(d - 1) + ")" + blank()
        parts = [expr(d - 1) for _ in range(int(rng.integers(2, 4)))]
        return ("|" if kind == 1 else "&").join(parts)

    return expr(depth)


@pytest.mark.parametrize("seed", range(8))
def test_the_references_evaluator_agrees_with_the_programs(seed, bench):
    rng = np.random.default_rng([53, seed])
    kinds = set()
    for k in range(400):
        label = _random_label(rng, int(rng.integers(0, 5)))
        held = [t for t in TOKENS + ("a.b", "ns:role") if rng.random() < 0.4]
        assert bench.ref.visible(label, held) is security.visible(label, held), (label, held)
        kinds |= {c for c in "|&( " if c in label}
    assert kinds == set("|&( ")


@pytest.mark.parametrize("label,held,want", [
    ("", (), True), ("  ", (), True), ("user", (), False), ("user", ("user",), True),
    ("user|ops", ("ops",), True), ("user&ops", ("ops",), False),
    ("user&ops|intel", ("intel",), True), ("user|ops&intel", ("ops",), False),
    ("user|ops&intel", ("user",), True), ("(user|ops)&intel", ("user",), False),
    ("(ops|intel)&user", ("user", "ops"), True), ("user&(ops|partner)", ("user", "ops"), True),
    ("user&(ops|partner)", ("user",), False), ("((admin))", ("admin",), True),
    (" ( ops | intel ) & user ", ("user", "intel"), True), ("partner|admin", ("user", "ops"), False),
])
def test_the_grammar_case_by_case(label, held, want, bench):
    assert bench.ref.visible(label, held) is want
    assert security.visible(label, held) is want


@pytest.mark.parametrize("label", ["user&", "(user", "user)", "&user", "user ops", "a||b", "()",
                                   "a,b", "a&&b", "(a|b", "a|", "|", "a b|c"])
def test_a_label_that_does_not_parse_is_refused_by_both(label, bench):
    with pytest.raises(ValueError):
        bench.ref.visible(label, TOKENS)
    with pytest.raises(ValueError):
        security.visible(label, TOKENS)


def test_every_rows_label_is_parsed(bench, first):
    """One evaluation a row, equal to NumPy's word on the label codes, and
    kept on the columns by the auths asked."""
    cols, _ = first
    for auths in AUTH_SETS.values():
        got = bench.ref.visible_rows(cols, auths)
        assert got.dtype == bool and np.array_equal(got, _seen(cols, auths))
        assert bench.ref.visible_rows(cols, tuple(reversed(auths))) is got
    assert bench.ref.visible_rows(cols, ()).sum() == (cols.label_code == 0).sum()
    assert bench.ref.visible_rows(cols, TOKENS).all()


# ------------------------------------------------- (c) the plain reference


@pytest.mark.parametrize("klass", CLASSES)
def test_a_class_answers_as_the_plain_reference(klass, bench, mix, loaded):
    seed, cols, store = loaded
    rows = 0
    for req in _of_class(bench, mix, cols, seed, klass, 3):
        tally, answer = _compared(bench, cols, store, req)
        assert _sound(bench, tally), (klass, req, tally)
        rows += bench.rq.op_of(req).size(answer)
    if cols.auths:  # the public rows alone may leave a small polygon empty
        assert rows > 0


def test_the_auth_sets_see_what_they_should(bench, mix, loaded):
    """Nobody's answers hold public rows only, everybody's are the open
    reference's, and ``user,ops`` lies between."""
    seed, cols, store = loaded
    (req,) = _of_class(bench, mix, cols, seed, "z3", 1)
    got = np.sort(bench.rq.op_of(req).embedded(store, req)["ids"])
    everything = bench.plain.ref_ids(cols, req["box"], req["win"])
    assert len(everything) > 50
    assert np.array_equal(got, everything[_seen(cols)[everything]])
    if cols.auths == ():
        assert (cols.label_code[got] == 0).all() and 0 < len(got) < len(everything)
    elif cols.auths == TOKENS:
        assert np.array_equal(got, everything)
    else:
        assert (cols.label_code[got] < 7).all() and 0 < len(got) < len(everything)


# ------------------------------------------- (d) paths not held before PR 53


def test_every_member_of_a_batch_is_masked(bench, mix, loaded):
    seed, cols, store = loaded
    for req in _of_class(bench, mix, cols, seed, "query_many", 2):
        batch = bench.rq.op_of(req).embedded(store, req)
        assert len(batch) == len(req["members"]) == 32
        for member, got in zip(req["members"], batch):
            want = bench.ref.ref_ids(cols, cols.auths, member["box"], member["win"])
            assert np.array_equal(np.sort(got["ids"]), want)
            assert bench.ref.leaks(cols, cols.auths, got["ids"]) == 0
            alone = store.ds.query(store.type_name, bench.rq.ecql(member))
            assert np.array_equal(np.sort(np.asarray(alone.ids)), want)


def test_a_union_and_an_id_lookup_are_masked(bench):
    """An ``Or`` across kinds of index plans as a union: the branches skip
    visibility and the merge applies it once. The GDELT type has no
    attribute index, so this path gets a table of its own; an id lookup goes
    past the scan and is masked too."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    n = 6000
    sft = FeatureType.from_spec(
        "u", "name:String:index=true,visibility:String,dtg:Date,*geom:Point:srid=4326;"
        "geomesa.vis.field=visibility")
    rng = np.random.default_rng(53)
    labels = np.asarray(_expressions())[rng.integers(0, 12, n)]
    names = np.array([f"n{i % 17}" for i in range(n)])
    x, y = rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)
    ds = DataStore(auths=("user", "ops"))
    ds.create_schema(sft)
    ds.write("u", FeatureCollection.from_columns(
        sft, [f"f{i}" for i in range(n)],
        {"name": names, "visibility": labels, "geom": (x, y),
         "dtg": np.datetime64("2024-01-01", "ms").astype(np.int64) + rng.integers(0, 10 ** 9, n)}))
    seen = np.array([bench.ref.visible(s, ("user", "ops")) for s in labels.tolist()])
    box = (x >= -20) & (x <= 10) & (y >= -15) & (y <= 10)
    cql = "bbox(geom, -20, -15, 10, 10) OR name = 'n3' OR name = 'n11'"
    plan = ds.planner.plan("u", cql)
    assert plan.union is not None and len(plan.union) == 3
    want = np.flatnonzero(seen & (box | (names == "n3") | (names == "n11")))
    got = np.sort([int(i[1:]) for i in ds.query("u", cql).ids])
    assert np.array_equal(got, want) and 0 < len(want) < (box | (names == "n3")).sum()
    assert ds.count("u", cql) == len(want)
    page = [int(i[1:]) for i in ds.query("u", cql, limit=25).ids]
    assert len(page) == 25 and np.isin(page, want).all()
    asked = [f"f{i}" for i in range(0, 400, 7)]
    got = sorted(int(i[1:]) for i in ds.query("u", "IN (" + ", ".join(
        f"'{i}'" for i in asked) + ")").ids)
    assert got == [i for i in range(0, 400, 7) if seen[i]] and 0 < len(got) < len(asked)
    ds.close()


@pytest.mark.parametrize("limit", [1, 7, 200, 10 ** 6])
def test_a_limit_is_applied_after_the_mask(limit, bench, mix, first):
    """A page of ``limit`` rows holds that many VISIBLE rows where the
    visible answer has them: the mask does not eat into the page."""
    cols, store = first
    reqs = _of_class(bench, mix, cols, SEEDS[0], "z3", 6)
    for req in reqs:
        want = bench.ref.ref_ids(cols, cols.auths, req["box"], req["win"])
        got = np.asarray(store.ds.query(store.type_name, bench.rq.ecql(req), limit=limit).ids)
        assert len(got) == min(limit, len(want)) and len(np.unique(got)) == len(got)
        assert np.isin(got, want).all()
    many = store.ds.query_many(store.type_name, [bench.rq.ecql(r) for r in reqs], limit=limit)
    for req, fc in zip(reqs, many):
        want = bench.ref.ref_ids(cols, cols.auths, req["box"], req["win"])
        got = np.asarray(fc.ids)
        assert len(got) == min(limit, len(want)) and np.isin(got, want).all()


def test_an_aggregation_by_the_host_route_counts_visible_rows_only(bench, mix, loaded):
    seed, cols, store = loaded
    for req in _of_class(bench, mix, cols, seed, "count", 3):
        want = bench.ref.ref_ids(cols, cols.auths, req["box"], req["win"])
        assert bench.rq.op_of(req).embedded(store, req) == len(want)
    for req in _of_class(bench, mix, cols, seed, "density", 2):
        grid = bench.rq.op_of(req).embedded(store, req)
        want = bench.ref.ref_ids(cols, cols.auths, req["box"], req["win"])
        assert grid.shape == (256, 256) and int(grid.sum()) == len(want)


# ------------------------------------------------------------ (e) the control


def test_a_store_opened_without_auths_is_refused(bench, mix, first, opened):
    cols, _ = first
    leaked = 0
    for klass in ("z3", "z2", "pip", "raster", "query_many"):
        for req in _of_class(bench, mix, cols, SEEDS[0], klass, 2):
            tally, answer = _compared(bench, cols, opened, req)
            if bench.rq.op_of(req).size(answer) > 20:
                assert tally["vis_leaks"] > 0 and tally["wrong_answers"] > 0, (klass, tally)
            assert tally["doubled_rows"] == tally["wrong_attributes"] == 0
            leaked += tally["vis_leaks"]
    assert leaked > 1000
    for req in _of_class(bench, mix, cols, SEEDS[0], "count", 3):
        tally, answer = _compared(bench, cols, opened, req)
        assert tally["wrong_answers"] == (1 if answer > 20 else tally["wrong_answers"])
    for req in _of_class(bench, mix, cols, SEEDS[0], "density", 2):
        tally, _ = _compared(bench, cols, opened, req)
        assert tally["density_sum_gap"] > 0 and tally["density_bad_pixels"] > 0


def test_the_sound_store_passes_where_the_control_fails(bench, mix, first, opened):
    """The same requests, the same comparison: only the auths differ."""
    cols, store = first
    (req,) = _of_class(bench, mix, cols, SEEDS[0], "z3", 1)
    sound, answer = _compared(bench, cols, store, req)
    assert _sound(bench, sound) and sound["witnesses"] == 1
    broken, leaky = _compared(bench, cols, opened, req)
    hidden = ~_seen(cols)[np.asarray(leaky["ids"])]
    assert broken["vis_leaks"] == hidden.sum() > 0
    assert len(leaky["ids"]) - len(answer["ids"]) == hidden.sum()


@pytest.mark.parametrize("fault", ["one-leak", "one-lost", "twice", "witness-label"])
def test_a_broken_answer_is_not_correct(fault, bench, mix, first):
    cols, store = first
    (req,) = _of_class(bench, mix, cols, SEEDS[0], "z3", 1)
    answer = bench.rq.op_of(req).embedded(store, req)
    ids = np.asarray(answer["ids"])
    if fault == "one-leak":
        inside = bench.plain.ref_ids(cols, req["box"], req["win"])
        answer["ids"] = np.append(ids, inside[~_seen(cols)[inside]][0])
        want = {"vis_leaks": 1, "wrong_answers": 1}
    elif fault == "one-lost":
        answer["ids"] = ids[:-1]
        want = {"wrong_answers": 1}
    elif fault == "twice":
        answer["ids"] = np.append(ids, ids[0])
        want = {"doubled_rows": 1, "wrong_answers": 1}
    else:
        answer["witness"]["row"]["visibility"] = "admin"
        want = {"wrong_attributes": 1}
    tally, _ = _compared(bench, cols, store, req, answer)
    assert {k: tally[k] for k in bench.check.LIMITS if tally[k]} == want


# ------------------------------------------------- (f) the density comparison


def _grid(x, y, box, size, kind):
    """A heat map of the points by NumPy: ``f64`` as the host route bins
    them, ``f32`` as the device aggregation does (f32 columns and envelope)."""
    f = np.float64 if kind == "f64" else np.float32
    x0, y0, x1, y1 = (f(v) for v in box)
    x, y = x.astype(f), y.astype(f)
    m = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
    px = np.clip(((x[m] - x0) / (x1 - x0) * f(size)).astype(np.int64), 0, size - 1)
    py = np.clip(((y[m] - y0) / (y1 - y0) * f(size)).astype(np.int64), 0, size - 1)
    return np.bincount(py * size + px, minlength=size * size).reshape(size, size).astype(np.float32)


@pytest.mark.parametrize("kind", ["f64", "f32"])
def test_the_density_comparison_admits_both_stated_semantics(kind, bench, mix, first):
    cols, _ = first
    seen = _seen(cols)
    for req in _of_class(bench, mix, cols, SEEDS[0], "density", 4):
        lo, hi = np.searchsorted(cols.t, req["win"])
        keep = np.flatnonzero(seen[lo:hi]) + lo
        grid = _grid(cols.x[keep], cols.y[keep], req["box"], req["grid"], kind)
        tally = bench.check.new_tally()
        bench.check_secured.density(tally, cols, req, grid)
        assert _sound(bench, tally) and grid.sum() > 20, (kind, tally)


def test_the_density_comparison_refuses_one_hidden_row(bench, mix, first):
    cols, store = first
    seen = _seen(cols)
    for req in _of_class(bench, mix, cols, SEEDS[0], "density", 3):
        grid = bench.rq.op_of(req).embedded(store, req)
        inside = bench.plain.ref_ids(cols, req["box"], req["win"])
        hidden = inside[~seen[inside]]
        one = _grid(cols.x[hidden[:1]], cols.y[hidden[:1]], req["box"], req["grid"], "f64")
        assert one.sum() == 1
        tally = bench.check.new_tally()
        bench.check_secured.density(tally, cols, req, grid + one)
        assert tally["density_sum_gap"] == 1 and tally["density_bad_pixels"] == 1
        moved = grid.copy().ravel()
        src = int(np.flatnonzero(moved)[0])
        moved[src] -= 1
        moved[(src + 1000) % moved.size] += 1
        tally = bench.check.new_tally()
        bench.check_secured.density(tally, cols, req, moved.reshape(grid.shape))
        assert tally["density_sum_gap"] == 0 and tally["density_bad_pixels"] >= 1
        tally = bench.check.new_tally()
        bench.check_secured.density(tally, cols, req, grid[:-1])
        assert tally["density_bad_pixels"] == 256 * 256


# ------------------------------------------------ (g) the spans and counters


def _vis_spans(tr):
    return [s for s in tr.spans if s.name == "vis"]


def test_the_vis_span_counts_rows_kept_and_labels(bench, mix, first, traced):
    """The span lies inside ``decode``, BEFORE its gather: ``rows`` are the
    scan's candidates (the filter's rows and the boundary band the
    refinement then drops), ``kept`` those of them the auths may read."""
    cols, store = first
    seen = _seen(cols)
    exact = 0
    for req in _of_class(bench, mix, cols, SEEDS[0], "z3", 3) + \
            _of_class(bench, mix, cols, SEEDS[0], "raster", 1):
        bench.rq.op_of(req).embedded(store, req)
        tr = traced.traces()[-1]
        inside = bench.plain.ref_ids(cols, req["box"], req.get("win"), req.get("ring"))
        (span,) = _vis_spans(tr)
        decode = next(s for s in tr.spans if s.name == "decode")
        assert tr.name == "query" and span.parent_id == decode.span_id
        got = span.attrs
        assert got["coded"] == 1 and "segments" not in got  # no strings sorted, no copy
        assert got["rows"] == decode.attrs["candidates"] >= len(inside)
        band = got["rows"] - len(inside)
        assert 0 <= got["kept"] - seen[inside].sum() <= band
        assert len(np.unique(cols.label_code[inside])) <= got["labels"] <= 12
        if band == 0:
            exact += 1
            assert got["labels"] == len(np.unique(cols.label_code[inside]))
        segments = decode.attrs["segments"]  # the mask, then the gather of the rows kept
        assert list(segments) == ["vis", "gather", "refine", "post"]
        assert decode.t0 <= span.t0
        assert span.t0 + span.dur_s <= decode.t0 + segments["vis"] + 1e-4
    assert exact >= 1


def test_every_member_and_every_aggregation_opens_its_vis_span(bench, mix, first, traced):
    cols, store = first
    (req,) = _of_class(bench, mix, cols, SEEDS[0], "query_many", 1)
    bench.rq.op_of(req).embedded(store, req)
    tr = traced.traces()[-1]
    inside = [bench.plain.ref_ids(cols, m["box"], m["win"]) for m in req["members"]]
    spans = _vis_spans(tr)
    decodes = {s.span_id: s for s in tr.spans if s.name == "decode"}
    reached = sorted(d.attrs["candidates"] for d in decodes.values() if d.attrs["candidates"])
    assert tr.name == "query_many" and len(spans) == len(reached) >= sum(len(i) > 0 for i in inside)
    assert sorted(s.attrs["rows"] for s in spans) == reached
    assert all(s.attrs["coded"] == 1 and s.parent_id in decodes for s in spans)
    band = sum(reached) - sum(map(len, inside))
    assert 0 <= sum(s.attrs["kept"] for s in spans) - sum(
        _seen(cols)[i].sum() for i in inside) <= band
    for klass in ("count", "density"):
        (req,) = _of_class(bench, mix, cols, SEEDS[0], klass, 1)
        bench.rq.op_of(req).embedded(store, req)
        tr = traced.traces()[-1]
        (span,) = _vis_spans(tr)
        decode = next(s for s in tr.spans if s.name == "decode")
        assert tr.name == klass and span.attrs["coded"] == 1
        assert span.attrs["rows"] == decode.attrs["candidates"] >= len(
            bench.plain.ref_ids(cols, req["box"], req["win"]))


def test_an_empty_answer_opens_no_vis_span(bench, first, traced):
    _, store = first
    got = store.ds.query(store.type_name, "bbox(geom, 0.0, 0.0, 0.000001, 0.000001) AND "
                         "dtg DURING 2024-01-01T00:00:00Z/2024-01-01T00:00:01Z")
    assert len(got) == 0 and not _vis_spans(traced.traces()[-1])


def test_vis_fallback_is_on_the_root_that_paid(bench, mix, first, traced):
    """1 where visibility alone took a device path away (a density, a bounds
    and a Count() estimate of a box and a window; a count of a polygon the
    raster tier would have taken), 0 where no device path was eligible anyway
    (the exact count of a box and a window, with or without auths)."""
    from geomesa_tpu.metrics import MetricsRegistry

    cols, store = first
    ds, name = store.ds, store.type_name
    ds.metrics, before = MetricsRegistry(), ds.metrics
    try:
        (req,) = _of_class(bench, mix, cols, SEEDS[0], "density", 1)
        cql = bench.rq.ecql(req)
        asked = {"density": lambda: ds.density(name, cql, envelope=tuple(req["box"])),
                 "bounds": lambda: ds.bounds(name, cql),
                 "stats": lambda: ds.stats_query(name, "Count()", cql, estimate=True),
                 "count": lambda: ds.count(name, cql)}
        for root, ask in asked.items():
            ask()
            tr = traced.traces()[-1]
            assert tr.name == root and tr.root.attrs["vis_fallback"] == int(root != "count"), root
        assert ds.metrics.snapshot()["counters"]["geomesa.query.vis_fallback"] == 3
        # a polygon the raster tier serves: the count loses its push-down
        polys = _of_class(bench, mix, cols, SEEDS[0], "raster", 3)
        lost = 0
        for poly in polys:
            plan = ds.planner.plan(name, bench.rq.ecql(poly))
            ds.count(name, bench.rq.ecql(poly))
            tr = traced.traces()[-1]
            would = plan.config is not None and plan.config.rast is not None
            assert tr.name == "count" and tr.root.attrs["vis_fallback"] == int(would)
            lost += would
        assert lost >= 1
        assert ds.metrics.snapshot()["counters"]["geomesa.query.vis_fallback"] == 3 + lost
        # a weighted density has no device path with or without auths
        ds.density(name, cql, envelope=tuple(req["box"]), weight="numMentions")
        assert traced.traces()[-1].root.attrs["vis_fallback"] == 0
        assert ds.metrics.snapshot()["counters"]["geomesa.query.vis_fallback"] == 3 + lost
    finally:
        ds.metrics = before


def test_a_store_without_auths_writes_neither(bench, mix, first, opened, traced):
    cols, _ = first
    ds, name = opened.ds, opened.type_name
    for klass in CLASSES:
        (req,) = _of_class(bench, mix, cols, SEEDS[0], klass, 1)
        bench.rq.op_of(req).embedded(opened, req)
    (req,) = _of_class(bench, mix, cols, SEEDS[0], "density", 1)
    ds.bounds(name, bench.rq.ecql(req))
    ds.stats_query(name, "Count()", bench.rq.ecql(req), estimate=True)
    seen = traced.traces()
    assert {t.name for t in seen} == {"query", "query_many", "count", "density", "bounds", "stats"}
    assert not any(_vis_spans(t) for t in seen)
    assert not any("vis_fallback" in (t.root.attrs or {}) for t in seen)


def test_nothing_is_counted_where_nothing_is_traced(bench, mix, first):
    cols, store = first
    assert obs.tracer().current() is None
    for klass in ("z3", "count", "density"):
        (req,) = _of_class(bench, mix, cols, SEEDS[0], klass, 1)
        tally, _ = _compared(bench, cols, store, req)
        assert _sound(bench, tally)


def test_the_served_handlers_mask_opens_the_same_span(traced):
    """Per-request auths (``X-Geomesa-Auths``) narrow a served store's
    answer in the handler: a ``vis`` span under the ``http`` root."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.serving import DataClient
    from geomesa_tpu.sft import FeatureType

    n = 400
    sft = FeatureType.from_spec(
        "v", "name:String,visibility:String,dtg:Date,*geom:Point:srid=4326;"
        "geomesa.vis.field=visibility")
    rng = np.random.default_rng(53)
    labels = np.array(["", "user", "user&ops", "admin"])[rng.integers(0, 4, n)]
    ds = DataStore(auths=("user", "ops"))
    ds.create_schema(sft)
    ds.write("v", FeatureCollection.from_columns(
        sft, [f"f{i}" for i in range(n)],
        {"name": np.array(["x"] * n), "visibility": labels,
         "dtg": np.datetime64("2024-01-01", "ms").astype(np.int64) + rng.integers(0, 10 ** 9, n),
         "geom": (rng.uniform(-50, 50, n), rng.uniform(-40, 40, n))}))
    try:
        with ds.serve(port=0) as srv:
            rows = DataClient(srv.url, auths=("user",)).query(
                "v", cql="BBOX(geom, -60, -45, 60, 45)")["features"]
        assert len(rows) == np.isin(labels, ["", "user"]).sum()
        http = next(t for t in traced.traces() if t.name == "http")
        (span,) = _vis_spans(http)
        assert span.parent_id == http.root.span_id
        assert {k: span.attrs[k] for k in ("rows", "kept", "labels", "coded")} == {
            "rows": int((labels != "admin").sum()), "kept": len(rows), "labels": 3, "coded": 0}
        assert set(span.attrs["segments"]) == {"labels", "copy"}  # an answer's strings
        query = next(t for t in traced.traces() if t.name == "query")
        (inner,) = _vis_spans(query)  # the store's own auths, on the scan's ordinals
        assert (inner.attrs["rows"], inner.attrs["kept"], inner.attrs["labels"]) == (
            n, span.attrs["rows"], 4)
        assert inner.attrs["coded"] == 1
    finally:
        ds.close()


# --------------------------------------------------------------- (h) the data


@pytest.mark.parametrize("seed", SEEDS)
def test_the_columns_are_the_open_stores_and_one_more(seed, bench, config, sibling):
    small = dict(copy.deepcopy(config), rows=N)
    mine = bench.data.make(small, N, seed)
    theirs = bench.gdelt.make(dict(sibling, rows=N), N, seed)
    assert np.array_equal(mine.x, theirs.x) and np.array_equal(mine.y, theirs.y)
    assert np.array_equal(mine.t, theirs.t)
    assert np.array_equal(mine.cx, theirs.cx) and np.array_equal(mine.cy, theirs.cy)
    assert set(mine.attrs) == set(theirs.attrs) | {"visibility"}
    assert all(np.array_equal(mine.attrs[k], theirs.attrs[k]) for k in theirs.attrs)
    assert [a for a, _ in mine.schema if a != "visibility"] == [a for a, _ in theirs.schema]
    assert mine.context() == theirs.context()
    row = mine.row(N - 1)
    assert set(row) == {a for a, _ in mine.schema} and isinstance(row["visibility"], str)
    assert np.array_equal(mine.attrs["visibility"], np.asarray(_expressions())[mine.label_code])


@pytest.mark.parametrize("seed", SEEDS)
def test_a_rows_group_is_where_it_lies(seed, bench, config):
    """The replay of ``gdelt_points``' draws: a clustered row lies within its
    cluster's reach (6 sigma of 3 x 2 degrees, or on the clipped edge), and
    the background is uniform over the world."""
    cols = bench.data.make(dict(copy.deepcopy(config), rows=N), N, seed)
    g = cols.group
    cl = g > 0
    assert 0.49 < cl.mean() < 0.51 and set(np.unique(g)) == set(range(65))
    dx = np.abs(cols.x[cl] - cols.cx[g[cl] - 1])
    dy = np.abs(cols.y[cl] - cols.cy[g[cl] - 1])
    assert (dx <= 18.0).all() and (dy <= 12.0).all()
    assert 2.8 < dx.std() / 0.6028 < 3.2  # a half-normal's spread, sigma 3
    bx = cols.x[~cl]
    assert -180 <= bx.min() < -179 and 179 < bx.max() <= 180 and abs(bx.mean()) < 2.0


@pytest.mark.parametrize("seed", SEEDS + (4_900_000_019,))
def test_the_labels_shares_are_the_configurations(seed, bench, config):
    cols = bench.data.make(dict(copy.deepcopy(config), rows=N), N, seed)
    got, said = cols.shares(), config["about"]["labels"]["measured"]
    assert 0.55 <= got["visible"] <= 0.65 and 0.20 <= got["public"] <= 0.30
    assert got["cluster_visible_min"] < 0.15 and got["cluster_visible_max"] > 0.90
    assert got["distinct_labels"] == 12
    assert abs(got["visible"] - said["visible"]) < 0.01
    assert abs(got["public"] - said["public"]) < 0.01
    assert abs(got["background_visible"] - 0.60) < 0.012
    assert abs(got["cluster_visible_min"] - said["cluster_visible_min"]) < 0.06
    assert abs(got["cluster_visible_max"] - said["cluster_visible_max"]) < 0.03


def test_the_mixes_are_the_deployments(bench, config):
    """From ``data.label_seed`` alone: cluster k is as open under every
    seed, and the stated shares follow from the mixes."""
    mixes = bench.data.label_mixes(config["data"]["label_seed"])
    assert mixes.shape == (65, 12) and np.allclose(mixes.sum(axis=1), 1.0)
    assert np.allclose(mixes[0], config["about"]["labels"]["uniform_mix"])
    assert np.array_equal(mixes, bench.data.label_mixes(53))
    assert not np.array_equal(mixes[1:], bench.data.label_mixes(54)[1:])
    open_share = mixes[1:, :7].sum(axis=1)
    said = config["about"]["labels"]["measured"]
    assert abs(0.5 * mixes[0, :7].sum() + 0.5 * open_share.mean() - said["visible"]) < 0.005
    assert abs(0.5 * mixes[0, 0] + 0.5 * mixes[1:, 0].mean() - said["public"]) < 0.005
    assert open_share.min() < 0.15 and open_share.max() > 0.90
    a = bench.data.make(dict(copy.deepcopy(config), rows=N), N, SEEDS[0])
    b = bench.data.make(dict(copy.deepcopy(config), rows=N), N, SEEDS[1])
    for k in (int(open_share.argmin()), int(open_share.argmax())):
        for cols in (a, b):
            rows = cols.group == 1 + k
            assert abs((cols.label_code[rows] < 7).mean() - open_share[k]) < 0.08


# ------------------------------------------------------------- (i) the readers


def _span(i, trace, root, name, ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": ms / 1e3, "self_s": ms / 1e3, "attrs": attrs}


def _view(with_vis=True, coded=True):
    """Four requests: a query (10 ms, 2 of them ``vis``), a ``query_many`` of
    two members (30 ms; 3 + 1), a count and a density (20 ms each, 4 + 6),
    and a density whose filter had no device path (5 ms). ``coded``: the
    ``vis`` spans say how they decided (PR 54), as PR 53's do not."""
    def vis(i, trace, root, ms, parent, rows, kept):
        how = {"coded": 1} if coded else {}
        return [_span(i, trace, root, "vis", ms, parent, rows=rows, kept=kept, labels=12,
                      **how)] if with_vis else []

    fb = (lambda v: {"vis_fallback": v}) if with_vis else (lambda v: {})
    q, many = _span(1, 1, "query", "query", 10.0), _span(10, 2, "query_many", "query_many", 30.0)
    cnt = _span(20, 3, "count", "count", 20.0, **fb(0))
    den = _span(30, 4, "density", "density", 20.0, **fb(1))
    odd = _span(40, 5, "density", "density", 5.0, **fb(0))
    spans = [
        q, dict(q), _span(2, 1, "query", "decode", 6.0, 1), *vis(3, 1, "query", 2.0, 2, 1000, 600),
        many, dict(many), _span(11, 2, "query_many", "decode", 8.0, 10, member=0),
        *vis(12, 2, "query_many", 3.0, 11, 4000, 2000),
        _span(13, 2, "query_many", "decode", 4.0, 10, member=1),
        *vis(14, 2, "query_many", 1.0, 13, 1000, 900),
        cnt, dict(cnt), _span(21, 3, "count", "decode", 12.0, 20),
        *vis(22, 3, "count", 4.0, 21, 3000, 1500),
        den, dict(den), _span(31, 4, "density", "decode", 12.0, 30),
        *vis(32, 4, "density", 6.0, 31, 1000, 1000),
        odd, dict(odd),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [10.5, 31.0, 20.5, 20.5, 5.5], "between_s": [0.0001]}}


def test_the_readers_read_the_new_spans_and_counters(bench):
    read = {m: r.read(_view()) for m, r in bench.readers.items()}
    assert read["vis_ms"] == pytest.approx(3.0)  # of 2, 3, 1, 4, 6
    assert read["vis_keep_pct"] == pytest.approx(100.0 * 6000 / 10000)
    assert read["vis_share_pct"] == pytest.approx(100.0 * 16.0 / 85.0)
    assert read["agg_vis_fallback_pct"] == pytest.approx(100.0 / 3)
    assert read["vis_coded_pct"] == 100.0
    # PR 53's program has the span and not the word on how it decided
    before = {m: r.read(_view(coded=False)) for m, r in bench.readers.items()}
    assert before == dict(read, vis_coded_pct=None)


def test_the_readers_find_nothing_on_a_program_before_pr_53(bench):
    """The parent masks without a span and marks no root: None, not a raise;
    so does a store without auths, and an empty window."""
    assert {m: r.read(_view(with_vis=False)) for m, r in bench.readers.items()} == \
        dict.fromkeys(NEW_METRICS)
    empty = {"workload": CELL, "spans": [], "device": None, "client": {}}
    assert all(r.read(empty) is None for r in bench.readers.values())


# ---------------------------------------------------------------- (j) the cell


def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL, "--rows",
         str(N), "--seconds", "5", "--seed", "5300000017", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 72
    read = line["rehearsal_metrics"]
    assert set(NEW_METRICS) | {"query_p50_ms", "plan_ms", "many_plan_ms", "decode_ms",
                               "gather_ms", "load_rows_per_s"} <= set(read)
    assert 55.0 <= read["vis_keep_pct"]["value"] <= 65.0
    assert 0 < read["vis_share_pct"]["value"] < 100 and read["vis_ms"]["value"] > 0
    assert read["vis_coded_pct"]["value"] == 100.0  # every route of the mix has ordinals
    # a round holds four counts (no device path to lose) and four densities (lost)
    assert 40.0 <= read["agg_vis_fallback_pct"]["value"] <= 60.0
    window = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "window"' in s)
    assert window["compile_requests_in_window"] == 0
    latency = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "latency"' in s)
    assert set(latency["by_class"]) == set(CLASSES)
    compared = {json.loads(s)["number"]: json.loads(s) for s in out.stdout.splitlines()
                if '"phase": "compared"' in s}
    assert compared["vis_leaks"]["value"] == compared["vis_leaks"]["limit"] == 0
    assert all(c["value"] == 0 for c in compared.values()) and len(compared) == 6
