"""The durable Lambda deployment (benchmark configuration
``gdelt-lambda-1chip``, cell ``gdelt.ingest-reads``) at a small size on the
CPU, with a cut schema:

(a) a ``LambdaStore`` with a write-ahead log, under interleaved writes,
    flushes and queries, gives the benchmark's plain NumPy reference's
    answers for every request class of the cell's mix: before a batch,
    with it hot, inside the commit-to-evict window of a flush, after it,
    and with both tiers holding rows; every acknowledged batch reads back
    whole;
(b) the must/may comparison accepts what it should and no more;
(c) the check's own controls: the program with ``hot.upsert`` dropping a
    row, an eviction before the cold commit, a row answered from both
    tiers, and an attribute changed on the way, each counted by the
    comparison that names it;
(d) the spans and attributes the deployment adds are there on retained
    traces (docs/observability.md) and cost no clock read on unsampled
    ones;
(e) a batch is the same bytes in whichever process makes it;
(f) the cell itself runs through ``benchmark/rehearse.py``.

The benchmark's directories are not packages of the program: they go on
the path for this module alone and leave it, with their modules, after.
"""

import itertools
import json
import math
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.io.geojson import read_geojson
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.streaming.cache import StreamingFeatureCache
from geomesa_tpu.streaming.store import LambdaStore

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED, TILE, BATCH, WRITERS = 1 << 15, 3_000_000_019, 4096, 400, 2
TYPE = "gdelt"
SPEC = "actor1Name:String,numMentions:Integer,avgTone:Double,dtg:Date,*geom:Point:srid=4326"
CONFIG = {"schema": SPEC, "span_days": 16,
          "data": {"generator": "gdelt_live", "t0": "2024-01-01T00:00:00", "live_hours": 24}}
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
STAGES = ("before", "hot", "commit_to_evict", "after", "both_tiers")
COUNTS = ("wrong_answers", "doubled_rows", "wrong_attributes", "missing_acked_rows",
          "unknown_rows", "acked_rows_lost", "acked_rows_changed", "count_gap")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's data set, generators, ops and reference of the new
    cell (NumPy alone), imported as the benchmark imports them."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import gdelt_live
        from generators import ingest_batches, viewports_live
        from harness import check, reference
        from harness import requests as rq
        from harness.data import sub_rng
        from ops import ingest, query_live

        yield types.SimpleNamespace(
            gdelt_live=gdelt_live, ingest_batches=ingest_batches, viewports_live=viewports_live,
            check=check, reference=reference, rq=rq, sub_rng=sub_rng, ingest=ingest,
            query_live=query_live)
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def cols(bench):
    return bench.gdelt_live.make(CONFIG, N, SEED)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "append-beside-reads.json")) as fh:
        return json.load(fh)


def _lambda(cols, wal_dir):
    """The cold store bulk-loaded with int64 ids as ``stores/datastore.py``
    loads it, a Lambda store with a log over it."""
    sft = FeatureType.from_spec(TYPE, SPEC)
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    sft.user_data["geomesa.z3.interval"] = "week"
    ds = DataStore(tile=TILE)
    ds.create_schema(sft)
    columns = dict(cols.attrs, dtg=cols.t, geom=(cols.x.copy(), cols.y.copy()))
    ds.write(TYPE, FeatureCollection.from_columns(sft, np.arange(N, dtype=np.int64), columns),
             check_ids=False)
    return LambdaStore(ds, TYPE, wal_dir=str(wal_dir))


def _spec(bench, cols, writer, k):
    ctx = cols.context() | {"seed": SEED}
    return bench.gdelt_live.batch_spec(ctx, writer, k, WRITERS, BATCH)


def _post(bench, lam, spec):
    """What ``DataServer.handle_post`` does with the batch's body."""
    fc = read_geojson(bench.gdelt_live.geojson_body(spec), type_name=TYPE,
                      sft=lam.cold.get_schema(TYPE))
    rows = fc.to_rows()
    ids = [r.pop("__id__") for r in rows]
    return lam.write(rows, ids=ids)


def _reader_requests(bench, cols, mix, n):
    """The cell's own reader role: half of its windows end at the newest
    event, where the appended rows are."""
    role = next(r for r in mix["roles"] if r["name"] == "reader")
    assert role["generator"] == "viewports_live"
    ctx = cols.context() | {"seed": SEED, "client_index": 0}
    reqs = bench.viewports_live.generate(role["params"], bench.sub_rng(SEED, 100), n, ctx)
    assert {r["op"] for r in reqs} == {"query_live"} and {r["fmt"] for r in reqs} == {
        "geojson", "arrow"}
    return reqs


def _wide(cols):
    """A request every appended row matches: the world over the last day."""
    hi = cols.t0 + cols.span_ms
    return {"op": "query_live", "klass": "viewport", "fmt": "geojson",
            "box": [-180.0, -90.0, 180.0, 90.0], "win": [hi - 24 * 3_600_000, hi]}


# ------------------------------------------- (a) the plain reference, by stage


@pytest.fixture(scope="module")
def timeline(bench, cols, mix, tmp_path_factory):
    """One scripted run on a logical clock: a tick a request sent, a tick
    an answer read. {"answers": {stage: [(req, sent, done, answer)]},
    "batches": [(spec, sent, acked)], "lam"}. The writer's class of
    request is a batch posted and acknowledged; the readers' a viewport."""
    lam = _lambda(cols, tmp_path_factory.mktemp("wal"))
    tick = itertools.count(1)
    reqs = _reader_requests(bench, cols, mix, 48) + [_wide(cols)]
    answers, batches = {s: [] for s in STAGES}, []

    def ask(stage):
        for req in reqs:
            sent = next(tick)
            fc = lam.query(bench.rq.ecql(req))
            answers[stage].append((req, sent, next(tick), bench.rq.collection_answer(fc)))

    def post(writer, k):
        spec, sent = _spec(bench, cols, writer, k), next(tick)
        assert _post(bench, lam, spec) == BATCH
        batches.append((spec, sent, next(tick)))

    ask("before")
    post(0, 0)
    post(1, 0)
    ask("hot")
    evict = lam.hot.evict

    def in_the_window(pairs):
        # the cold commit has landed, the hot rows are not evicted yet
        assert lam.cold.row_count(TYPE) == N + 2 * BATCH and len(lam.hot) == 2 * BATCH
        ask("commit_to_evict")
        return evict(pairs)

    lam.hot.evict = in_the_window
    assert lam.flush() == 2 * BATCH
    lam.hot.evict = evict
    assert len(lam.hot) == 0 and answers["commit_to_evict"]
    ask("after")
    post(0, 1)
    ask("both_tiers")
    yield {"answers": answers, "batches": batches, "lam": lam, "reqs": reqs}
    lam.close()


@pytest.mark.parametrize("stage", STAGES)
def test_lambda_store_answers_as_the_plain_reference(stage, bench, cols, timeline):
    """No request overlaps a write here, so an answer may hold nothing
    besides what it must: exactly the preloaded matches and the matches of
    the batches acknowledged before it, once each, ids as int64 or text."""
    appended = bench.query_live.Appended(cols, timeline["batches"])
    tally = bench.check.new_tally()
    hits = from_batches = 0
    for req, sent, done, answer in timeline["answers"][stage]:
        bench.query_live.compare(tally, cols, req, answer,
                                 {"sent": sent, "done": done, "appended": appended})
        must, may = appended.must_may(req, sent, done)
        assert len(may) == 0
        base = bench.reference.ref_ids(cols, req["box"], req.get("win"))
        got = np.sort(np.asarray(answer["ids"]).astype(np.int64))
        assert np.array_equal(got, np.sort(np.concatenate([base, must])))
        hits += len(base)
        from_batches += len(must)
    assert hits > 0 and tally["witnesses"] > 0
    assert from_batches == {"before": 0}.get(stage, from_batches)
    if stage != "before":
        assert from_batches >= (3 if stage == "both_tiers" else 2) * BATCH  # the wide request's
    assert {k: tally[k] for k in COUNTS} == dict.fromkeys(COUNTS, 0), tally


def test_every_acknowledged_batch_reads_back_whole(bench, cols, timeline):
    lam, tally = timeline["lam"], bench.check.new_tally()
    store = types.SimpleNamespace(lam=lam)
    for spec, _, _ in timeline["batches"]:  # two cold, one hot
        req = {"op": "ingest", "spec": spec}
        bench.ingest.compare(tally, cols, req, bench.ingest.embedded(store, req))
    bench.ingest.count(tally, lam.cold.row_count(TYPE) + len(lam.hot), N, 3 * BATCH)
    assert tally["rows_compared"] == 3 * BATCH
    assert {k: tally[k] for k in COUNTS} == dict.fromkeys(COUNTS, 0), tally


def test_the_writers_requests_are_the_mixs(bench, cols, mix):
    role = next(r for r in mix["roles"] if r["name"] == "writer")
    assert role["generator"] == "ingest_batches" and role["clients"] == role["params"]["writers"]
    ctx = cols.context() | {"seed": SEED, "client_index": 1}
    reqs = bench.ingest_batches.generate(role["params"], None, 3, ctx)
    rows = role["params"]["batch_rows"]
    assert [r["spec"]["first_id"] for r in reqs] == [N + rows, N + 3 * rows, N + 5 * rows]
    method, path, body, headers = bench.ingest.http(reqs[0], TYPE)
    feats = json.loads(body)["features"]
    assert (method, path) == ("POST", "/ingest/gdelt") and len(feats) == rows == 1000
    assert set(feats[0]["properties"]) == {"actor1Name", "numMentions", "avgTone", "dtg"}
    assert feats[0]["id"] == str(N + rows) and bench.ingest.members(reqs[0]) == 1
    ack = bench.ingest.parse(reqs[0], b'{"acked": 1000, "durable": true, "type": "gdelt"}')
    assert bench.ingest.sound(reqs[0], ack) and bench.ingest.size(ack) == 1000
    assert not bench.ingest.sound(reqs[0], dict(ack, durable=False))
    assert not bench.ingest.sound(reqs[0], dict(ack, acked=999))


# --------------------------------------------- (b) what must and what may be


def _answer(cols, bench, req, extra=(), drop=()):
    base = bench.reference.ref_ids(cols, req["box"], req["win"])
    ids = np.setdiff1d(np.concatenate([base, np.asarray(extra, np.int64)]),
                       np.asarray(drop, np.int64))
    return {"ids": ids, "witness": None}


@pytest.mark.parametrize("case,missing,unknown", [
    ("acked_before_and_answered", 0, 0),
    ("acked_before_and_left_out", BATCH, 0),
    ("in_flight_and_answered", 0, 0),      # sent before the answer was read: may
    ("in_flight_and_left_out", 0, 0),
    ("in_flight_half_answered", 0, 0),     # a batch applies in chunks: some rows may show
    ("sent_after_the_answer_was_read", 0, BATCH),
    ("acked_while_the_request_was_out", 0, 0),   # the 200 read after the request was sent
    ("never_acknowledged_and_answered", 0, 0),   # a 599: sent, so it may be there
    ("an_id_nobody_sent", 0, 1),
    ("a_preloaded_row_left_out", 1, 0),
])
def test_the_must_may_comparison_accepts_what_it_should_and_no_more(
        case, missing, unknown, bench, cols):
    req = _wide(cols)
    spec = _spec(bench, cols, 0, 0)
    ids = cols.batch(spec)["ids"]
    sent, done = 10.0, 12.0  # of the reader's request
    when = {
        "acked_before_and_answered": (5.0, 6.0, ids, ()),
        "acked_before_and_left_out": (5.0, 6.0, (), ()),
        "in_flight_and_answered": (11.0, 13.0, ids, ()),
        "in_flight_and_left_out": (11.0, 13.0, (), ()),
        "in_flight_half_answered": (9.0, 11.5, ids[::2], ()),
        "sent_after_the_answer_was_read": (12.5, 13.0, ids, ()),
        "acked_while_the_request_was_out": (9.0, 10.5, (), ()),
        "never_acknowledged_and_answered": (9.0, math.inf, ids, ()),
        "an_id_nobody_sent": (5.0, 6.0, list(ids) + [N + 10 * BATCH], ()),
        "a_preloaded_row_left_out": (5.0, 6.0, ids, [int(
            bench.reference.ref_ids(cols, req["box"], req["win"])[0])]),
    }[case]
    b_sent, b_acked, extra, drop = when
    appended = bench.query_live.Appended(cols, [(spec, b_sent, b_acked)])
    tally = bench.check.new_tally()
    bench.query_live.compare(tally, cols, req, _answer(cols, bench, req, extra, drop),
                             {"sent": sent, "done": done, "appended": appended})
    assert (tally["missing_acked_rows"], tally["unknown_rows"]) == (missing, unknown)
    assert tally["wrong_answers"] == int(bool(missing or unknown)) and tally["doubled_rows"] == 0


def test_an_appended_witness_row_is_held_to_the_generators(bench, cols):
    req, spec = _wide(cols), _spec(bench, cols, 1, 2)
    fid = spec["first_id"] + 7
    appended = bench.query_live.Appended(cols, [(spec, 1.0, 2.0)])
    row = cols.appended_row(spec, fid)
    assert row == bench.gdelt_live.batch_rows(spec)[7]
    wire = dict(row, dtg=f"{np.datetime64(row['dtg'], 'ms')}Z", __geom__=row["geom"])
    del wire["geom"]
    for change, wrong in ((None, 0), ("numMentions", 1), ("actor1Name", 1)):
        sent_row = dict(wire)
        if change is not None:
            sent_row[change] = (sent_row[change] + 1 if change == "numMentions"
                                else sent_row[change] + "X")
        answer = _answer(cols, bench, req, cols.batch(spec)["ids"])
        answer["witness"] = {"id": fid, "row": sent_row}
        tally = bench.check.new_tally()
        bench.query_live.compare(tally, cols, req, answer,
                                 {"sent": 5.0, "done": 6.0, "appended": appended})
        assert tally["witnesses"] == 1 and tally["wrong_attributes"] == wrong


# ------------------------------------------------ (c) the check's own controls


def _compared(bench, cols, lam, specs, reqs):
    """Every batch posted, flushed where ``flush`` says, every request
    asked after; then the read-back and the count: the tally."""
    tally = bench.check.new_tally()
    batches = [(spec, float(2 * j), float(2 * j + 1)) for j, spec in enumerate(specs)]
    appended = bench.query_live.Appended(cols, batches)
    now = float(2 * len(specs))
    for req in reqs:
        fc = lam.query(bench.rq.ecql(req))
        bench.query_live.compare(tally, cols, req, bench.rq.collection_answer(fc),
                                 {"sent": now, "done": now + 1.0, "appended": appended})
    store = types.SimpleNamespace(lam=lam)
    for spec in specs:
        req = {"op": "ingest", "spec": spec}
        bench.ingest.compare(tally, cols, req, bench.ingest.embedded(store, req))
    bench.ingest.count(tally, lam.cold.row_count(TYPE) + len(lam.hot), N, len(specs) * BATCH)
    return tally


@pytest.mark.parametrize("control,counted", [
    ("none", ()),
    ("upsert_drops_the_last_row", ("acked_rows_lost", "missing_acked_rows", "count_gap")),
    ("evict_before_the_cold_commit", ("acked_rows_lost", "missing_acked_rows", "count_gap")),
    ("a_row_from_both_tiers", ("doubled_rows", "count_gap")),  # the rows stay hot: counted twice
    ("an_attribute_changed_on_the_way", ("acked_rows_changed", "wrong_attributes")),
])
def test_the_new_comparisons_catch_a_broken_store(control, counted, bench, cols, tmp_path,
                                                  monkeypatch):
    lam = _lambda(cols, tmp_path / "wal")
    specs = [_spec(bench, cols, 0, 0), _spec(bench, cols, 1, 0)]
    if control == "upsert_drops_the_last_row":
        real = StreamingFeatureCache.upsert
        monkeypatch.setattr(StreamingFeatureCache, "upsert", lambda self, rows, ids=None: real(
            self, rows[:-1], None if ids is None else ids[:-1]) + 1)
    elif control == "evict_before_the_cold_commit":
        # the flusher's publish fails once and the store evicts all the same
        monkeypatch.setattr(lam.flusher, "flush", lambda batch, **kw: len(batch))
    elif control == "a_row_from_both_tiers":
        # the flush commits and never evicts; the merge neither shadows nor dedups
        monkeypatch.setattr(lam.hot, "evict", lambda pairs: 0)
        monkeypatch.setattr(LambdaStore, "_merge", staticmethod(
            lambda hot, live, cold: (FeatureCollection.concat([hot, cold]), 0, 0)))
    elif control == "an_attribute_changed_on_the_way":
        real_rows = FeatureCollection.to_rows

        def one_too_high(self):
            rows = real_rows(self)
            for r in rows:
                r["numMentions"] += 1
            return rows

        monkeypatch.setattr(FeatureCollection, "to_rows", one_too_high)
    try:
        for spec in specs:
            assert _post(bench, lam, spec) == BATCH  # the acknowledgement is sound every time
        lam.flush()
        tally = _compared(bench, cols, lam, specs, [_wide(cols)])
    finally:
        monkeypatch.undo()
        lam.close()
    for name in COUNTS:
        assert (tally[name] > 0) == (name in counted or (
            name == "wrong_answers" and "missing_acked_rows" in counted)), (name, tally)


# ------------------------------------------------------------------ (d) spans


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


def _spans(trace, name):
    return [s for s in [trace.root] + list(trace.spans) if s.name == name]


@pytest.fixture()
def served(bench, cols, tmp_path):
    """The deployment's normal path: scheduler, data plane over the Lambda
    store; one keep-alive connection."""
    import http.client

    lam = _lambda(cols, tmp_path / "wal")
    lam.serve()
    srv = lam.serve(port=0)
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60.0)

    def send(req):
        op = bench.rq.op_of(req) if req["op"] != "ingest" else bench.ingest
        method, path, body, headers = op.http(req, TYPE)
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        assert resp.status == 200, data[:200]
        return op.parse(req, data)

    sys.path.insert(0, BENCH)  # ``requests.op_of`` imports an op by its name
    try:
        yield types.SimpleNamespace(lam=lam, send=send)
    finally:
        sys.path.remove(BENCH)
        conn.close()
        lam.close()
        lam.cold.scheduler.close()


def test_the_served_paths_carry_the_new_spans(bench, cols, served, traced):
    spec0, spec1 = _spec(bench, cols, 0, 0), _spec(bench, cols, 1, 0)
    assert bench.ingest.sound({"spec": spec0}, served.send({"op": "ingest", "spec": spec0}))
    assert served.lam.flush() == BATCH          # batch 0 into the delta tier
    served.send({"op": "ingest", "spec": spec1})  # batch 1 hot
    answer = served.send(_wide(cols))
    assert len(answer["ids"]) >= 2 * BATCH
    deadline = time.monotonic() + 10.0
    while len([t for t in traced() if t.name == "http"]) < 3:  # a root ends after its last byte
        assert time.monotonic() < deadline
        time.sleep(0.01)
    by = {}
    for tr in traced():
        by.setdefault(tr.name, []).append(tr)
    posts = [t for t in by["http"] if t.root.attrs["method"] == "POST"]
    (get,) = [t for t in by["http"] if t.root.attrs["method"] == "GET"]
    assert len(posts) == 2 and len(by["write"]) == 2 and len(by["flush"]) == 1
    for tr in posts:  # the request's root: body, parse, rows; the write names it
        (read,), (parse,), (rows,) = (_spans(tr, n) for n in (
            "ingest.read", "ingest.parse", "ingest.rows"))
        # ``ingest.parse`` carries no ``rows`` since PR 35: the ``write`` root below does
        assert read.attrs["bytes"] > 30_000 and "rows" not in (parse.attrs or {})
        assert tr.root.attrs["status"] == 200
        write = next(w for w in by["write"] if w.trace_id == tr.root.attrs["write_trace"])
        assert write.root.attrs["http_trace"] == tr.trace_id and write.root.attrs["rows"] == BATCH
        (append,), (sync,), (upsert,) = (_spans(write, n) for n in (
            "wal.append", "wal.sync", "hot.upsert"))
        assert sync.parent_id == append.span_id and (sync.attrs["fsync"], sync.attrs["covered"]) == (1, 1)
        assert upsert.parent_id == write.root.span_id and upsert.t0 >= sync.t0 + sync.dur_s
        covered = sum(s.dur_s for n in ("ingest.read", "ingest.parse", "ingest.rows")
                      for s in _spans(tr, n)) + write.root.dur_s
        assert covered <= tr.root.dur_s
    # the read: hot and merge under the request's root, the delta scan in the cold scan
    (wait,), (hot,), (merge,) = (_spans(get, n) for n in ("http.wait", "hot", "merge"))
    assert hot.parent_id == merge.parent_id == wait.span_id
    # (named keys, not the whole dict: a collection under a span stamps ``gc_s`` on it)
    assert (hot.attrs["hot_rows"], hot.attrs["hits"]) == (BATCH, BATCH)
    assert merge.attrs["cold_rows"] == len(answer["ids"]) - BATCH
    assert merge.attrs["shadowed"] == 0 and merge.attrs["deduped"] == 0
    query = next(q for q in by["query"] if q.trace_id == get.root.attrs["query_trace"])
    (scan,) = _spans(query, "scan")
    assert scan.attrs["delta_rows"] == BATCH == scan.attrs["delta_hits"]
    assert {"bits", "delta"} <= set(scan.attrs["segments"])
    # the flush: what the publish did
    (flush,) = by["flush"]
    assert {k: flush.root.attrs[k] for k in ("rows", "appended", "updated", "delta_rows")} == {
        "rows": BATCH, "appended": BATCH, "updated": 0, "delta_rows": BATCH}
    (watermark,) = [s for s in _spans(flush, "wal.append") if s.attrs["kind"] == "w"]
    assert watermark.dur_s > 0


def test_a_flush_that_replaces_rows_counts_them_updated(bench, cols, tmp_path, traced):
    lam = _lambda(cols, tmp_path / "wal")
    try:
        spec = _spec(bench, cols, 0, 0)
        _post(bench, lam, spec)
        lam.flush()
        _post(bench, lam, spec)      # the same ids again: updates of persisted rows
        assert lam.flush(full=True) == BATCH
        first, second = [t for t in traced() if t.name == "flush"]
        assert (first.root.attrs["appended"], first.root.attrs["updated"]) == (BATCH, 0)
        assert (second.root.attrs["appended"], second.root.attrs["updated"]) == (0, BATCH)
        assert second.root.attrs["delta_rows"] == 0  # the fold compacts the delta first
    finally:
        lam.close()


@pytest.mark.parametrize("slow_log", [False, True])
def test_unsampled_requests_read_no_clock_for_the_new_spans(slow_log, bench, cols, served,
                                                            monkeypatch):
    """Nothing sampled. Disarmed, the sites this PR added (``hot``, ``merge``,
    ``hot.upsert``, ``ingest.*``, the delta segment, the flush's counts)
    open the null span and mark nothing. With the slow log alone armed (the
    default), the roots it can take build their trees as before, the
    transport's root and what hangs under it (``ingest.*``, ``hot``,
    ``merge``) are not built, and nobody reads the thread's CPU clock."""
    from geomesa_tpu.obs import trace as tr

    conf.OBS_SLOW_MS.set(60_000.0 if slow_log else 0.0)
    try:
        assert obs.tracer().armed is slow_log and conf.OBS_TRACE_SAMPLE.get() == 0
        served.send({"op": "ingest", "spec": _spec(bench, cols, 0, 0)})
        served.lam.flush()
        served.send(_wide(cols))  # warm: every lazy import done
        opened, marks, cpu_reads = [], [], []
        real_span, real_event, real_cpu = tr.Span.__init__, tr.Span.event, time.thread_time
        monkeypatch.setattr(tr.Span, "__init__", lambda self, *a, **kw: (
            opened.append(a[1]), real_span(self, *a, **kw))[1])
        monkeypatch.setattr(tr.Span, "event", lambda self, name: (
            marks.append(name), real_event(self, name))[1])
        monkeypatch.setattr(time, "thread_time", lambda: (cpu_reads.append(1), real_cpu())[1])
        served.send({"op": "ingest", "spec": _spec(bench, cols, 1, 0)})
        served.send(_wide(cols))
        served.lam.flush()
        monkeypatch.undo()
    finally:
        conf.OBS_SLOW_MS.clear()
    assert cpu_reads == []
    if not slow_log:
        assert opened == [] and marks == []
        assert obs.span("hot") is tr.NULL_SPAN and tr.event("delta") is False
        return
    assert {"write", "hot.upsert", "wal.sync", "query", "scan", "flush"} <= set(opened)
    assert not {"http", "http.wait", "hot", "merge", "ingest.read", "ingest.parse",
                "ingest.rows", "encode"} & set(opened)
    assert "delta" in marks


# ------------------------------------------------------------------ (e), (f)


def test_a_batch_is_the_same_in_every_process(bench, cols):
    """A writer's process makes the body, the parent the columns: the
    vocabulary's order may not follow the process's string hashing."""
    spec = _spec(bench, cols, 1, 3)
    code = ("import sys, json, hashlib; sys.path.insert(0, sys.argv[1]);"
            "from datagen import gdelt_live;"
            "print(hashlib.sha256(gdelt_live.geojson_body(json.loads(sys.argv[2]))).hexdigest())")
    digests = set()
    for hash_seed in ("1", "2"):
        out = subprocess.run([sys.executable, "-c", code, BENCH, json.dumps(spec)],
                             env=dict(os.environ, PYTHONHASHSEED=hash_seed),
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-1000:]
        digests.add(out.stdout.strip())
    import hashlib

    digests.add(hashlib.sha256(bench.gdelt_live.geojson_body(spec)).hexdigest())
    assert len(digests) == 1
    ids = cols.batch(spec)["ids"]
    assert ids[0] == N + (3 * WRITERS + 1) * BATCH and len(ids) == BATCH
    assert cols.batch(spec)["t"].min() >= cols.t0 + cols.span_ms - 24 * 3_600_000


def test_the_preloaded_rows_are_gdelts_letter_for_letter(bench, cols):
    sys.path.insert(0, BENCH)
    try:
        from datagen import gdelt
    finally:
        sys.path.remove(BENCH)
    plain = gdelt.make(CONFIG, N, SEED)
    assert len(cols) == N and cols.row(N - 1) == plain.row(N - 1)
    for a in ("x", "y", "t"):
        assert np.array_equal(getattr(cols, a), getattr(plain, a))
    for a, col in plain.attrs.items():
        assert np.array_equal(cols.attrs[a], col)


def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/rehearse.py`` is the chip run's ``run_cell`` with the look
    for the chip skipped: the configuration at all 27 attributes, its store
    module, both roles over HTTP from their own processes, the persist
    loop, the check against the plain reference and the new readers."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", "gdelt.ingest-reads",
         "--rows", "32768", "--seconds", "5", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.strip().splitlines() if ln.startswith("{")]
    line = lines[-1]
    assert line["workload"] == "gdelt.ingest-reads" and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    compared = {ln["number"]: (ln["value"], ln["limit"]) for ln in lines
                if ln.get("phase") == "compared"}
    for name in ("missing_acked_rows", "unknown_rows", "doubled_rows", "acked_rows_lost",
                 "acked_rows_changed", "count_gap", "wrong_attributes"):
        assert compared[name] == (0, 0)
    wal = next(ln for ln in lines if ln.get("phase") == "wal")
    assert wal["sync"] == "always" and wal["fs_type"]
    ingest = next(ln for ln in lines if ln.get("phase") == "ingest")
    assert ingest["batches_late"] == 0 and ingest["batches_sound"] == ingest["batches_sent"] > 0
    got = line["rehearsal_metrics"]
    for name in ("ack_p95_ms", "ingest_rows_per_s", "ingest_parse_ms", "wal_append_ms",
                 "wal_sync_ms", "hot_upsert_ms", "flush_ms", "flush_commit_ms", "hot_merge_ms",
                 "delta_scan_ms", "span_coverage_pct", "lock_wait_pct"):
        assert name in got, sorted(got)
    assert line["metrics"] == {}  # a CPU run yields no device number
