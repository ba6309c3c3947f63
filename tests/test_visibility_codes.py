"""Row visibility decided from label codes on a route's ordinals, before
the gather (PR 54; ``security.LabelCodes``, ``security.mask_ordinals``,
``DataStore.label_codes``, ``QueryPlanner._visible``), held to the definition
it encodes: ``security.visibility_mask`` over the same rows' label strings.

(a) the coded mask against the string mask: the benchmark configuration's
    twelve expressions and the grammar cases of tests/test_secured_cell.py,
    under three auth sets, in one chunk and across several;
(b) every route that turns ordinals into rows (an index scan, ``query_many``'s
    members, an id lookup, the full host scan, a union across two indexes)
    answers as the same store opened WITHOUT auths does once its answer is
    masked by strings, row for row and in order;
(c) several chunks: a second ``write``, a flush's appended chunk, a label
    first seen in a later chunk, an object column with ``None``;
(d) renumbering: after a delete, a fold and a compaction the dictionaries
    are the new chunks'; a scan pinned before a fold masks the rows it pinned;
(e) a label that does not parse fails the answers that contain it and no
    other; a limit pages the visible rows;
(f) the accuracy record counts the rows the FILTER matched whatever their
    label, as the open store's does;
(g) a store without auths, and a type without a label field, build nothing
    and open no ``vis`` span; ``_post`` still masks a collection that came
    without ordinals.
"""

import numpy as np
import pytest

from geomesa_tpu import conf, obs, security
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.sft import FeatureType

EXPRESSIONS = ("", "user", "ops", "user|ops", "user&ops", "(ops|intel)&user",
               "user&(ops|partner)", "intel", "ops&intel", "admin", "admin&intel",
               "partner|admin")
GRAMMAR = ("  ", "user&ops|intel", "user|ops&intel", "(user|ops)&intel", "((admin))",
           " ( ops | intel ) & user ")
TOKENS = ("user", "ops", "intel", "admin", "partner", "legal")
AUTH_SETS = {"user-ops": ("user", "ops"), "nobody": (), "everybody": TOKENS}
SPEC = ("name:String:index=true,visibility:String,dtg:Date,*geom:Point:srid=4326;"
        "geomesa.vis.field=visibility")
T0 = int(np.datetime64("2024-01-01", "ms").astype(np.int64))
N = 6000

BOX = "bbox(geom, -20, -15, 10, 10)"
WINDOW = BOX + " AND dtg DURING 2024-01-02T00:00:00Z/2024-01-09T00:00:00Z"
FILTERS = {
    "z2": BOX,
    "z3": WINDOW,
    "attribute": "name = 'n3'",
    "residual": BOX + " AND name = 'n5'",
    "ids": "IN (" + ", ".join(f"'f{i}'" for i in range(0, 900, 7)) + ")",
    "full": "name LIKE '%1'",
    "union": BOX + " OR name = 'n3' OR name = 'n11'",
}


def _sft(name="u", spec=SPEC):
    return FeatureType.from_spec(name, spec)


def _rows(sft, lo, hi, seed, labels=EXPRESSIONS + GRAMMAR, as_object=False):
    """Rows ``f<lo>`` .. ``f<hi - 1>``: a label each from ``labels``."""
    n = hi - lo
    rng = np.random.default_rng(seed)
    vis = np.asarray(labels, dtype=object if as_object else None)[rng.integers(0, len(labels), n)]
    return FeatureCollection.from_columns(
        sft, [f"f{i}" for i in range(lo, hi)],
        {"name": np.array([f"n{i % 17}" for i in range(lo, hi)]), "visibility": vis,
         "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)),
         "dtg": T0 + rng.integers(0, 10 ** 9, n)})


def _store(auths, *batches, spec=SPEC):
    ds = DataStore(auths=auths)
    sft = ds.create_schema(_sft(spec=spec))
    for b in batches:
        ds.write("u", b if isinstance(b, FeatureCollection) else _rows(sft, *b))
    return ds


def _by_strings(fc, auths):
    """An open store's answer as ``_post`` masked it before PR 54."""
    keep = security.visibility_mask(fc.columns["visibility"], auths)
    return np.asarray(fc.ids)[keep]


def _same(secured, opened, cql, **kw):
    got = np.asarray(secured.query("u", cql, **kw).ids)
    want = _by_strings(opened.query("u", cql), secured.auths)
    assert np.array_equal(got, want), cql
    return want


@pytest.fixture(scope="module", params=sorted(AUTH_SETS))
def pair(request):
    """The same 6000 rows in one chunk: under an auth set, and open."""
    auths = AUTH_SETS[request.param]
    stores = _store(auths, (0, N, 54)), _store(None, (0, N, 54))
    yield stores
    for ds in stores:
        ds.close()


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _vis_spans(tr):
    return [s for s in tr.spans if s.name == "vis"]


# ------------------------------------------------- (a) codes against strings


@pytest.mark.parametrize("who", sorted(AUTH_SETS))
@pytest.mark.parametrize("label", EXPRESSIONS + GRAMMAR)
def test_a_labels_code_reads_as_its_string(label, who):
    auths = frozenset(AUTH_SETS[who])
    column = np.array(["", label, "admin", label, "user"])
    codes = security.LabelCodes(column)
    assert sorted(codes.labels) == sorted(set(column.tolist()))
    assert np.array_equal(np.asarray(codes.labels)[codes.codes], column)
    want = security.visibility_mask(column, auths)
    assert want[1] == security.visible(label, auths)
    for rows in (np.arange(5), np.array([3, 1, 1, 0]), np.zeros(0, np.int64)):
        seen = codes.visible(rows, auths)
        assert seen.dtype == bool and np.array_equal(seen, want[rows])
    assert np.array_equal(security.mask_ordinals([codes], np.arange(5), auths), want)


@pytest.mark.parametrize("who", sorted(AUTH_SETS))
def test_ordinals_across_chunks_find_their_chunks_codes(who):
    """Three chunks whose dictionaries differ (the second's labels come in
    another order, the third is an object column with ``None``): ordinals in
    any order, repeats among them."""
    auths = AUTH_SETS[who]
    rng = np.random.default_rng(7)
    parts = [np.asarray(EXPRESSIONS)[rng.integers(0, 12, 500)],
             np.asarray(EXPRESSIONS[::-1] + GRAMMAR)[rng.integers(0, 18, 300)],
             np.array([None, "user", None, "admin&intel", "ops"], dtype=object)[
                 rng.integers(0, 5, 200)]]
    codes = [security.LabelCodes(p) for p in parts]
    assert [len(c) for c in codes] == [500, 300, 200]
    assert codes[2].codes.dtype == np.uint8 and "" in codes[2].labels
    whole = np.concatenate([p.astype(object) for p in parts])
    want = security.visibility_mask(whole, auths)
    for ordinals in (np.arange(1000), rng.permutation(1000)[:400], np.array([999, 0, 500, 0]),
                     np.arange(500, 800)):
        assert np.array_equal(security.mask_ordinals(codes, ordinals, auths), want[ordinals])


def test_the_codes_are_as_narrow_as_the_labels_allow():
    many = np.array([f"t{i}" for i in range(300)])
    assert security.LabelCodes(many[:256]).codes.dtype == np.uint8
    wide = security.LabelCodes(np.tile(many, 3))
    assert wide.codes.dtype == np.uint16 and len(wide.labels) == 300
    assert np.array_equal(np.asarray(wide.labels)[wide.codes], np.tile(many, 3))
    assert sorted(wide.present(np.array([299, 7, 599, 7]))) == sorted(many[[299, 7]].tolist())
    assert wide.present(np.zeros(0, np.int64)) == []
    sliced = security.LabelCodes(np.tile(many, 500))  # several slices, one dictionary
    assert len(sliced) == 150_000 and len(sliced.labels) == 300
    assert np.array_equal(np.asarray(sliced.labels)[sliced.codes], np.tile(many, 500))


@pytest.mark.parametrize("kept", ["all", "mask", "none"])
@pytest.mark.parametrize("n_parts", [1, 3])
def test_joined_dictionaries_read_as_the_rows_strings(n_parts, kept):
    """What a fold, a delete or a compaction makes of its chunks' dictionaries
    (``LabelCodes.joined``: code arrays alone) decodes to the moved rows'
    strings, whatever order each part met its labels in."""
    rng = np.random.default_rng(3)
    parts = [np.asarray(EXPRESSIONS)[rng.integers(0, 12, 400)],
             np.asarray(GRAMMAR[::-1] + EXPRESSIONS[:3])[rng.integers(0, 9, 250)],
             np.array([None, "legal", "user"], dtype=object)[rng.integers(0, 3, 150)]][:n_parts]
    whole = np.concatenate([security._label_strings(p) for p in parts])
    keep = {"all": None, "mask": rng.random(len(whole)) < 0.6,
            "none": np.zeros(len(whole), dtype=bool)}[kept]
    codes = [security.LabelCodes(p) for p in parts]
    auths = frozenset(("user", "ops"))
    codes[0].visible(np.arange(5), auths)  # a table is memoised on the first part
    got = security.LabelCodes.joined(codes, keep)
    want = whole if keep is None else whole[keep]
    assert len(got) == len(want) and got.codes.dtype == np.uint8
    assert np.array_equal(np.asarray(got.labels)[got.codes], want)
    assert set(got.labels) == set(whole.tolist())
    rows = np.arange(len(want))
    assert np.array_equal(got.visible(rows, auths), security.visibility_mask(want, auths))
    # one part keeps its labels, and so the table already evaluated for them
    assert (got._table is codes[0]._table) == (n_parts == 1)


def test_a_label_is_evaluated_once_an_auth_set(monkeypatch):
    calls = []
    real = security.visible
    monkeypatch.setattr(security, "visible", lambda e, a: calls.append(e) or real(e, a))
    codes = security.LabelCodes(np.asarray(EXPRESSIONS)[np.arange(6000) % 12])
    for _ in range(3):
        codes.visible(np.arange(6000), frozenset(("user", "ops")))
    assert sorted(calls) == sorted(EXPRESSIONS)
    codes.visible(np.arange(10), frozenset(("ops", "user")))  # the same set
    codes.visible(np.arange(10), frozenset(("intel",)))
    assert len(calls) == 24


# ------------------------------------------------------------ (b) every route


@pytest.mark.parametrize("route", sorted(FILTERS))
def test_a_route_answers_as_the_string_mask_did(route, pair, traced):
    secured, opened = pair
    cql = FILTERS[route]
    plan = secured.planner.plan("u", cql)
    assert (plan.union is not None) == (route == "union")
    assert (plan.ids is not None) == (route == "ids")
    assert (plan.strategy == "full-scan") == (route == "full")
    got = np.asarray(secured.query("u", cql).ids)
    tr = traced.traces()[-1]
    everything = opened.query("u", cql)
    assert not _vis_spans(traced.traces()[-1])  # the open store's
    want = _by_strings(everything, secured.auths)
    assert np.array_equal(got, want)
    if secured.auths == TOKENS:
        assert len(want) == len(everything) > 0
    else:
        assert 0 < len(want) < len(everything)
    spans = _vis_spans(tr)
    assert tr.name == "query" and len(spans) == (3 if route == "union" else 1)
    assert all(s.attrs["coded"] == 1 and "segments" not in s.attrs for s in spans)
    assert sum(s.attrs["kept"] for s in spans) >= len(want)
    if route == "full":
        assert spans[0].attrs["rows"] == len(everything)
    assert secured.count("u", cql) == len(want)


def test_every_member_of_a_batch_is_masked_on_its_ordinals(pair, traced):
    secured, opened = pair
    asked = [FILTERS[k] for k in ("z2", "z3", "residual", "union", "attribute", "ids")]
    batch = secured.query_many("u", asked)
    tr = traced.traces()[-1]
    assert tr.name == "query_many"
    assert all(s.attrs["coded"] == 1 for s in _vis_spans(tr)) and len(_vis_spans(tr)) >= 6
    for cql, got in zip(asked, batch):
        assert np.array_equal(np.asarray(got.ids), _by_strings(opened.query("u", cql),
                                                               secured.auths))


def test_a_hidden_attribute_is_projected_out_after_the_row_mask():
    spec = SPEC.replace("name:String:index=true", "name:String:index=true:vis=admin")
    secured, opened = _store(("user",), (0, 2000, 3), spec=spec), _store(None, (0, 2000, 3))
    for cql in (FILTERS["z2"], BOX + " OR " + WINDOW, "IN ('f1', 'f2', 'f3', 'f4', 'f5')"):
        out = secured.query("u", cql)
        assert "name" not in out.columns and "visibility" in out.columns
        assert np.array_equal(np.asarray(out.ids), _by_strings(opened.query("u", cql), ("user",)))
    secured.close(), opened.close()


# --------------------------------------------------------- (c) several chunks


@pytest.fixture(scope="module")
def chunked():
    """5000 rows compacted, then three appended chunks: a second ``write``,
    a flush's (``fold_upsert`` of ids the store has not) whose labels are new
    to the table, and an object column with ``None``."""
    stores = []
    for auths in (("user", "ops"), None):
        ds = DataStore(auths=auths)
        sft = ds.create_schema(_sft())
        ds.write("u", _rows(sft, 0, 5000, 1, labels=EXPRESSIONS))
        ds.write("u", _rows(sft, 5000, 5300, 2, labels=EXPRESSIONS))
        ds.fold_upsert("u", _rows(sft, 5300, 5600, 3, labels=GRAMMAR + ("legal|user",)))
        ds.write("u", _rows(sft, 5600, 5900, 4, labels=(None, "user", "admin"), as_object=True))
        stores.append(ds)
    assert [len(c) for c in stores[0].chunk_snapshot("u")] == [5000, 300, 300, 300]
    yield stores
    for ds in stores:
        ds.close()


@pytest.mark.parametrize("route", sorted(FILTERS))
def test_a_route_over_several_chunks(route, chunked, traced):
    secured, opened = chunked
    got = np.asarray(secured.query("u", FILTERS[route]).ids)
    spans = _vis_spans(traced.traces()[-1])
    assert len(got) and np.array_equal(
        got, _by_strings(opened.query("u", FILTERS[route]), secured.auths))
    # the concatenation of several chunks is no chunk: the full scan's rows
    # are masked by their strings, every other route on its ordinals
    assert [s.attrs["coded"] for s in spans] == ([0] if route == "full" else [1] * len(spans))


def test_each_chunk_has_its_own_dictionary_built_at_write(chunked, monkeypatch):
    secured, opened = chunked
    codes = secured.label_codes("u")
    assert [len(c) for c in codes] == [5000, 300, 300, 300]
    assert sorted(codes[0].labels) == sorted(EXPRESSIONS)
    assert "legal|user" in codes[2].labels and "legal|user" not in codes[0].labels
    assert set(codes[3].labels) == {"", "user", "admin"}
    assert all(a is b for a, b in zip(codes, secured.label_codes("u")))
    assert opened.label_codes("u") is None and not opened._label_codes
    # nothing is built while a query is answered
    monkeypatch.setattr(security.LabelCodes, "__init__", None)
    assert len(secured.query("u", FILTERS["z2"]))
    seen = np.asarray(secured.query("u", "name = 'n4'").ids)
    assert {int(i[1:]) // 1000 for i in seen} >= {0, 5}  # rows of the first chunk and the later


def test_a_later_chunks_new_label_is_evaluated(chunked):
    secured, _ = chunked
    got = secured.query("u", "IN (" + ", ".join(f"'f{i}'" for i in range(5300, 5600)) + ")")
    labels = np.asarray(got.columns["visibility"])
    assert "legal|user" in labels and "((admin))" not in labels and len(got) < 300


# ------------------------------------------------------------ (d) renumbering


def _agrees(secured, opened):
    for route in ("z2", "attribute", "ids", "union"):
        _same(secured, opened, FILTERS[route])


def test_after_a_delete_a_fold_and_a_compaction_the_dictionaries_are_the_new_chunks():
    secured, opened = _store(("user", "ops"), (0, N, 9)), _store(None, (0, N, 9))
    sft = secured.get_schema("u")
    # an id's label flips from hidden to visible and back: a stale code would show
    flip = _rows(sft, 100, 400, 11, labels=("admin",))
    flop = _rows(sft, 100, 400, 11, labels=("",))
    steps = [
        lambda ds: ds.delete_features("u", "IN ('f3', 'f10', 'f17', 'f5000')"),
        lambda ds: ds.fold_upsert("u", flip),
        lambda ds: ds.write("u", _rows(sft, N, N + 200, 12)),
        lambda ds: ds.compact("u"),
        lambda ds: ds.fold_upsert("u", flop),
        lambda ds: ds.modify_features("u", {"name": "n3"}, "name = 'n2'"),
    ]
    before = secured.label_codes("u")
    for step in steps:
        # the open store deletes and modifies what ITS auths see: all rows; so
        # does an administrator's handle on the secured table
        secured.auths, held = None, secured.auths
        step(secured)
        secured.auths = held
        step(opened)
        assert secured.row_count("u") == opened.row_count("u")
        _agrees(secured, opened)
        now = secured.label_codes("u")
        assert [len(c) for c in now] == [len(c) for c in secured.chunk_snapshot("u")]
        assert len(secured._label_codes["u"]) == len(now)  # the old chunks' are gone
    assert not any(a is b for a in before for b in now)
    hidden = secured.query("u", "IN ('f100', 'f399')")
    assert len(hidden) == 2 and set(hidden.columns["visibility"]) == {""}
    secured.close(), opened.close()


def _truth(ds, cql):
    """The ids ``cql`` answers under the store's auths, from the table's own
    rows: the filter and the label STRINGS of every stored row."""
    full = ds.features("u")
    keep = ds.planner.plan("u", cql).filter.evaluate(full.batch)
    keep = keep & security.visibility_mask(full.columns["visibility"], ds.auths)
    return sorted(np.asarray(full.ids)[keep].tolist())


MUTATIONS = {
    # name: (what it does, the most label strings it may read)
    "fold-replace": (lambda ds, sft: ds.fold_upsert(
        "u", _rows(sft, 100, 400, 11, labels=("admin", ""))), 300),
    "fold-append": (lambda ds, sft: ds.fold_upsert("u", _rows(sft, N, N + 150, 12)), 150),
    "fold-sliced": (lambda ds, sft: ds.fold_upsert(
        "u", _rows(sft, 50, 650, 13, labels=("admin", "user", "legal")), slice_rows=128), 600),
    "fold-twice": (lambda ds, sft: (
        ds.fold_upsert("u", _rows(sft, 100, 400, 11, labels=("admin",))),
        ds.fold_upsert("u", _rows(sft, 300, 500, 14, labels=("", "intel")))), 500),
    "delete": (lambda ds, sft: ds.delete_features("u", "IN ('f3', 'f10', 'f17', 'f5000')"), 0),
    "write-compact": (lambda ds, sft: (
        ds.write("u", _rows(sft, N, N + 200, 15)), ds.compact("u")), 200),
    "modify": (lambda ds, sft: ds.modify_features("u", {"name": "n3"}, "name = 'n2'"), N // 17 + 1),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_a_mutation_carries_the_codes_and_the_query_after_it_builds_nothing(name, monkeypatch):
    """A fold, a delete, a compaction and a modify swap in fresh chunk
    objects under the write lock, and leave them their dictionaries: the
    survivors' from the old chunks' codes (no label string of the table is
    read: only the batch's), so the first query afterwards builds nothing."""
    ds = _store(("user", "ops"), (0, N, 9))
    mutate, may_read = MUTATIONS[name]
    read = []
    real = security.LabelCodes.__init__
    monkeypatch.setattr(
        security.LabelCodes, "__init__", lambda self, col: read.append(len(col)) or real(self, col))
    mutate(ds, ds.get_schema("u"))
    assert sum(read) <= may_read, read
    chunks = ds.chunk_snapshot("u")
    held = ds._label_codes["u"]
    assert len(held) == len(chunks) and all(e[0] is c for e, c in zip(held, chunks))
    for chunk, (_, codes) in zip(chunks, held):  # no stale code: each decodes to its row's string
        strings = security._label_strings(chunk.columns["visibility"])
        assert np.array_equal(np.asarray(codes.labels)[codes.codes], strings)
    monkeypatch.setattr(security.LabelCodes, "__init__", None)
    monkeypatch.setattr(security.LabelCodes, "joined", None)
    for route in ("z2", "attribute", "ids", "union", "full"):
        got = sorted(np.asarray(ds.query("u", FILTERS[route]).ids).tolist())
        assert got == _truth(ds, FILTERS[route]) and got, route
    ds.close()


def test_a_scan_pinned_before_a_fold_masks_the_rows_it_pinned():
    """The ordinals of a dispatched scan number the chunks pinned with it:
    a fold that publishes before the pull renumbers the live table, and the
    pinned scan still reads its own rows' labels."""
    secured, opened = _store(("user", "ops"), (0, N, 13)), _store(None, (0, N, 13))
    sft = secured.get_schema("u")
    want = _by_strings(opened.query("u", BOX), ("user", "ops"))
    plan = secured.planner.plan("u", BOX)
    finish = secured.planner.submit(plan)
    pinned = secured.chunk_snapshot("u")
    # every row of the box's first ids replaced by a hidden one, elsewhere
    moved = _rows(sft, 0, 3000, 14, labels=("admin",))
    secured.fold_upsert("u", moved)
    assert secured.chunk_snapshot("u")[0] is not pinned[0]
    got = finish()
    assert np.array_equal(np.asarray(got.ids), want)
    assert security.visibility_mask(got.columns["visibility"], ("user", "ops")).all()
    # the pinned chunks' dictionary served the pull and the live ones stayed
    assert len(secured.label_codes("u")) == len(secured.chunk_snapshot("u"))
    opened.fold_upsert("u", moved)
    _agrees(secured, opened)
    secured.close(), opened.close()


# -------------------------------------------- (e) malformed labels, the limit


def test_a_label_that_does_not_parse_fails_the_answers_that_contain_it():
    ds = DataStore(auths=("user",))
    sft = ds.create_schema(_sft())
    rows = _rows(sft, 0, 2000, 21, labels=("", "user", "admin"))
    x, y = rows.columns["geom"].x, rows.columns["geom"].y
    vis = np.asarray(rows.columns["visibility"]).astype("<U8")
    bad = np.flatnonzero((x > 40) & (y > 30))[:3]
    vis[bad] = "user&"
    rows.columns["visibility"] = vis
    ds.write("u", rows)  # the dictionary is built whole: the label is an entry, not an error
    assert "user&" in ds.label_codes("u")[0].labels
    west = ds.query("u", "bbox(geom, -60, -45, 0, 45)")
    assert len(west) and "user&" not in set(west.columns["visibility"])
    assert len(ds.query_many("u", ["bbox(geom, -60, -45, 0, 0)", "name = 'n1' AND " + BOX])) == 2
    for cql in ("bbox(geom, 39, 29, 60, 45)", f"IN ('f{bad[0]}')", "name LIKE 'n%'",
                "bbox(geom, 39, 29, 60, 45) OR name = 'n3'"):
        with pytest.raises(security.VisibilityError, match="user&"):
            ds.query("u", cql)
    with pytest.raises(security.VisibilityError):
        security.visibility_mask(vis, ("user",))  # as the strings always did
    ds.close()


@pytest.mark.parametrize("limit", [1, 7, 200, 10 ** 6])
def test_a_limit_pages_the_visible_rows(limit, pair):
    secured, opened = pair
    for route in ("z2", "union", "ids", "full"):
        want = _by_strings(opened.query("u", FILTERS[route]), secured.auths)
        got = np.asarray(secured.query("u", FILTERS[route], limit=limit).ids)
        assert len(got) == min(limit, len(want)) and np.isin(got, want).all()
        if route != "union":  # a union's page follows its branches' order
            assert np.array_equal(got, want[:limit])


# ------------------------------------------------- (f) the accuracy record


#: more disjuncts than one scan takes (``filter/dnf.py`` MAX_DISJUNCTS): the
#: planner cuts them into time-ordered groups, a union whose branches each
#: carry an estimate
GROUPS = " OR ".join(
    f"(bbox(geom, {-55 + 2 * k}, -30, {-35 + 2 * k}, 20) AND dtg DURING "
    f"2024-01-{2 + k % 9:02d}T{k % 24:02d}:00:00Z/2024-01-{3 + k % 9:02d}T00:00:00Z)"
    for k in range(24))


def _explained(ds, cql):
    exp = Explainer()
    plan = ds.planner.plan("u", cql, explain=exp)
    out = ds.planner.execute(plan, explain=exp)
    lines = [ln.strip() for ln in exp.render().splitlines()]
    candidates = [int(s.split(":")[1]) for s in lines if s.startswith("Candidates:")]
    assert any(s.startswith("Estimate vs actual:") for s in lines)
    return plan, len(out), sum(candidates)


@pytest.mark.parametrize("route", ["z2", "z3", "residual", "groups"])
def test_the_estimates_actual_is_the_filters_whatever_the_labels(route, pair):
    """``Estimate vs actual`` of a secured store beside the open store's on
    the same filter: the sketches count rows whatever their label, so the
    candidates hidden before refinement are counted as the filter would have
    decided them (those the device mask was certain of as matched, the
    others at the share the visible ones kept)."""
    secured, opened = pair
    mine, answered, _ = _explained(secured, FILTERS.get(route, GROUPS))
    theirs, matched, candidates = _explained(opened, FILTERS.get(route, GROUPS))
    assert (mine.union is not None) == (route == "groups")
    assert mine.estimated_rows == theirs.estimated_rows is not None
    if secured.auths != TOKENS:
        assert answered < matched
    band = candidates - matched  # what the refinement had to decide
    if route == "residual":  # every hidden candidate at the visible rows' keep share
        assert abs(mine.actual_rows - matched) <= 0.25 * matched + 3
    elif route == "groups":  # a row two groups matched is counted once among the visible
        assert theirs.actual_rows == matched
        assert abs(mine.actual_rows - matched) <= band + 0.02 * matched
    else:
        assert theirs.actual_rows == matched and abs(mine.actual_rows - matched) <= band
    if secured.auths == TOKENS:
        assert mine.actual_rows == matched == answered


def test_a_secured_stores_estimates_do_not_look_stale(pair):
    secured, opened = pair
    for ds in pair:
        ds.accuracy.reset("u")
        for k in range(12):
            ds.query("u", f"bbox(geom, {-50 + 5 * k}, -30, {-20 + 5 * k}, 20)")
    mine, theirs = (ds.accuracy.report()["indexes"] for ds in pair)
    assert [(r["index"], r["count"]) for r in mine] == [(r["index"], r["count"]) for r in theirs]
    assert mine and sum(r["count"] for r in mine) == 12
    for a, b in zip(mine, theirs):
        assert a["p90_error"] == pytest.approx(b["p90_error"], rel=0.05)
        assert a["worst_error"] == pytest.approx(b["worst_error"], rel=0.05)
    assert not secured.accuracy.stale(min_count=1)


# --------------------------------------------------- (g) nothing without auths


def test_a_store_without_auths_builds_nothing_and_opens_no_vis_span(pair, traced, monkeypatch):
    _, opened = pair
    monkeypatch.setattr(security, "LabelCodes", None)
    monkeypatch.setattr(security, "mask_ordinals", None)
    sft = opened.get_schema("u")
    opened.write("u", _rows(sft, 10 * N, 10 * N + 50, 31))
    opened.warmup("u")
    for cql in FILTERS.values():
        opened.query("u", cql)
    opened.query_many("u", list(FILTERS.values()))
    assert opened.label_codes("u") is None and not opened._label_codes
    seen = traced.traces()
    assert len(seen) == len(FILTERS) + 1 and not any(_vis_spans(t) for t in seen)
    for t in seen:
        for s in t.spans:
            if s.name == "decode":
                assert "vis" not in (s.attrs or {}).get("segments", {})


def test_a_type_without_a_label_field_builds_nothing(traced, monkeypatch):
    monkeypatch.setattr(security, "LabelCodes", None)
    ds = _store(("user",), (0, 500, 1), spec=SPEC.split(";")[0])
    assert ds.label_codes("u") is None and len(ds.query("u", BOX)) > 0
    assert len(ds.query("u", "IN ('f1', 'f2')")) == 2
    assert not any(_vis_spans(t) for t in traced.traces())
    ds.close()


def test_warmup_builds_the_dictionaries_of_a_store_opened_over_chunks(traced):
    """Chunks that no ``write`` of this store made (a reload hands the store
    its tables): ``warmup`` builds, and the first query finds them."""
    ds = _store(("user", "ops"), (0, 3000, 5))
    ds._label_codes.clear()
    ds.warmup("u")
    (entry,) = ds._label_codes["u"]
    assert entry[0] is ds.chunk_snapshot("u")[0] and len(entry[1]) == 3000
    ds.close()


def test_post_masks_by_strings_a_collection_that_came_without_ordinals(pair, traced):
    """The default of ``_post`` is the safe one: rows no route decided are
    masked by their label strings (``coded`` 0), and rows a route decided
    are not masked again."""
    secured, opened = pair
    plan = secured.planner.plan("u", BOX)
    raw = opened.query("u", BOX)
    want = _by_strings(raw, secured.auths)
    with traced.trace("query"):
        out = secured.planner._post(raw, plan, None, Explainer())
        assert secured.planner._post(raw, plan, None, Explainer(), rows_masked=True) is raw
    assert np.array_equal(np.asarray(out.ids), want)
    (span,) = _vis_spans(traced.traces()[-1])
    assert span.attrs["coded"] == 0 and span.attrs["rows"] == len(raw)
    assert span.attrs["kept"] == len(want) and set(span.attrs["segments"]) == {"labels", "copy"}


def test_rows_that_land_beside_the_empty_table_route_stay_hidden(pair, traced, monkeypatch):
    """``_execute`` reads ``row_count() == 0`` and then ``features()`` with
    no lock: a first write between the two hands it rows that came by no
    ordinals. They are refined and then masked by their strings in ``_post``."""
    secured, _ = pair
    want = _truth(secured, BOX)
    plan = secured.planner.plan("u", BOX)
    matched = int(plan.filter.evaluate(secured.features("u").batch).sum())
    monkeypatch.setattr(secured, "row_count", lambda type_name: 0)
    with traced.trace("query"):
        got = secured.planner.execute(plan)
    assert sorted(np.asarray(got.ids).tolist()) == want
    assert security.visibility_mask(got.columns["visibility"], secured.auths).all()
    assert len(want) < matched or len(secured.auths) == len(TOKENS)  # rows were there to hide
    assert [s.attrs["coded"] for s in _vis_spans(traced.traces()[-1])] == [0]


def test_an_open_stores_full_scan_keeps_its_span_as_it_was(pair, traced):
    """No ``vis`` and no ``post`` segment on a full host scan's ``decode``
    where the store has no auths (``refine_ms`` samples spans that carry
    ``refine`` or ``post``); a secured store's carries both."""
    secured, opened = pair
    for ds, want in ((opened, set()), (secured, {"vis", "post"})):
        ds.query("u", FILTERS["full"])
        (decode,) = [s for s in traced.traces()[-1].spans if s.name == "decode"]
        assert set((decode.attrs or {}).get("segments", {})) == want


def test_readers_never_see_a_hidden_row_while_the_table_is_renumbered():
    """More reader threads than cores ask by every route while a writer
    flips 300 ids between a hidden and a public label (a fold each: fresh
    chunk objects, renumbered ordinals, published under the seqlock that
    ``pin_scan_state`` reads). An answered row carries its own label string,
    so a code read from a stale or a neighbouring chunk's dictionary would
    show as a row the auths may not read. (The writer does not append or
    compact: a reader's ``DataStore.table()`` beside a compaction is not
    under that seqlock, with or without auths.)"""
    import os
    import sys
    import threading
    import time
    import traceback

    auths = ("user", "ops")
    ds = _store(auths, (0, N, 41))
    sft = ds.get_schema("u")
    flips = [_rows(sft, 100, 400, 42, labels=(label,)) for label in ("admin", "")]
    asked = [FILTERS[k] for k in ("z2", "z3", "attribute", "ids", "union", "full")]
    stop, seen, failures = threading.Event(), [0], []

    def read(k):
        try:
            while not stop.is_set():
                for out in (ds.query("u", asked[k % len(asked)]), *ds.query_many("u", asked[:3])):
                    ok = security.visibility_mask(out.columns["visibility"], auths)
                    if not ok.all():
                        failures.append(np.asarray(out.ids)[~ok][:3].tolist())
                    seen[0] += len(out)
        except Exception as e:  # a reader that dies proves nothing: report it
            failures.append(traceback.format_exc())

    readers = [threading.Thread(target=read, args=(k,)) for k in range(2 * (os.cpu_count() or 4))]
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in readers:
            t.start()
        deadline, k = time.monotonic() + 4.0, 0
        while time.monotonic() < deadline and not failures:
            ds.fold_upsert("u", flips[k % 2])
            k += 1
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=60)
        sys.setswitchinterval(before)
    assert not any(t.is_alive() for t in readers)
    assert not failures, failures[:3]
    assert k >= 2 and seen[0] > 0
    assert len(ds.label_codes("u")) == len(ds.chunk_snapshot("u"))
    ds.close()
