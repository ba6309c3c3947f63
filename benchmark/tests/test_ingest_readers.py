"""The readers PR 30 added for a Lambda store and the ``ingest`` and
``query_live`` ops, each over hand-made spans and answers with known
results; and over a view of a program that opens no such span (the parent
commit) or a mix with no writer: None, never an error."""

import json
import math
import os

import numpy as np
import pytest

from clients import ingest_http
from datagen import gdelt_live
from harness import check
from layer_metrics import (ack_p95_ms, delta_scan_ms, flush_commit_ms, flush_ms, hot_merge_ms,
                           hot_upsert_ms, ingest_parse_ms, ingest_rows_per_s, wal_append_ms,
                           wal_sync_ms)
from ops import ingest, query_live

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = {"ack_p95_ms": ack_p95_ms, "ingest_rows_per_s": ingest_rows_per_s,
           "ingest_parse_ms": ingest_parse_ms, "wal_append_ms": wal_append_ms,
           "wal_sync_ms": wal_sync_ms, "hot_upsert_ms": hot_upsert_ms, "flush_ms": flush_ms,
           "flush_commit_ms": flush_commit_ms, "hot_merge_ms": hot_merge_ms,
           "delta_scan_ms": delta_scan_ms}


def _span(i, trace, root, name, parent=None, ms=10.0, self_ms=None, segments=None, **attrs):
    if segments:
        attrs["segments"] = {k: v / 1e3 for k, v in segments.items()}
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": ms / 1e3, "self_s": (ms if self_ms is None else self_ms) / 1e3,
            "attrs": attrs}


def lambda_view():
    """Two posts (``http`` roots 10, 20) with their ``write`` roots (30, 40),
    three reads (50, 60, 70) with their ``query`` roots, two flushes. Roots
    are listed twice, as the harness lists them."""
    spans = []

    def root(i, name, ms, **attrs):
        r = _span(i, i, name, name, ms=ms, **attrs)
        spans.extend([r, dict(r)])

    root(10, "http", 120.0, method="POST")
    spans += [_span(11, 10, "http", "ingest.read", 10, 1.0, bytes=900_000),
              _span(12, 10, "http", "ingest.parse", 10, 60.0, rows=1000),
              _span(13, 10, "http", "ingest.rows", 10, 30.0)]
    root(20, "http", 140.0, method="POST")
    spans += [_span(21, 20, "http", "ingest.parse", 20, 70.0, rows=1000),
              _span(22, 20, "http", "ingest.rows", 20, 40.0)]
    for base, append, sync, upsert in ((30, 9.0, 3.0, 5.0), (40, 11.0, 5.0, 7.0)):
        root(base, "write", append + upsert + 1.0, rows=1000)
        spans += [_span(base + 1, base, "write", "wal.append", base, append, append - sync),
                  _span(base + 2, base, "write", "wal.sync", base + 1, sync, fsync=1, covered=1),
                  _span(base + 3, base, "write", "hot.upsert", base, upsert)]
    for base, hot, merge, delta in ((50, 2.0, 1.0, 0.2), (60, 4.0, 3.0, 0.4), (70, 6.0, 5.0, None)):
        root(base, "http", 90.0, method="GET")
        spans += [_span(base + 1, base, "http", "http.wait", base, 50.0),
                  _span(base + 2, base, "http", "hot", base + 1, hot, hot_rows=4000, hits=3),
                  _span(base + 3, base, "http", "merge", base + 1, merge, cold_rows=9)]
        root(base + 100, "query", 40.0)
        segs = dict(wait=1.0, pull=0.5, bits=0.3)
        if delta is not None:
            segs["delta"] = delta
        spans.append(_span(base + 101, base + 100, "query", "scan", base + 100, 2.0,
                           segments=segs, delta_rows=20_000))
    for base, whole, commit in ((80, 300.0, 20.0), (90, 500.0, 40.0)):
        root(base, "flush", whole, rows=6000, appended=6000, updated=0, delta_rows=60_000)
        spans += [_span(base + 1, base, "flush", "flush.parse", base, 100.0),
                  _span(base + 2, base, "flush", "flush.commit", base, commit),
                  # the watermark's record: not a write's
                  _span(base + 3, base, "flush", "wal.append", base, 2.0, 1.0, kind="w"),
                  _span(base + 4, base, "flush", "wal.sync", base + 3, 1.0, fsync=1, covered=1)]
    client = {"query_ms": [100.0] * 5, "between_s": [0.001], "read_ms": [90.0] * 3,
              "ack_ms": [100.0, 200.0, 300.0, 400.0, 500.0], "ingest_rows_per_s": 2466.7}
    return {"workload": "gdelt.ingest-reads", "spans": spans, "client": client, "seconds": 30.0}


def test_the_readers_over_a_lambda_stores_spans():
    view = lambda_view()
    got = {name: mod.read(view) for name, mod in READERS.items()}
    assert got["ack_p95_ms"] == pytest.approx(480.0)          # p95 of 100..500
    assert got["ingest_rows_per_s"] == 2466.7
    assert got["ingest_parse_ms"] == pytest.approx(100.0)     # (60+30, 70+40): the median
    assert got["wal_append_ms"] == pytest.approx(6.0)         # self: 9-3 and 11-5, writes alone
    assert got["wal_sync_ms"] == pytest.approx(4.0)           # 3, 5: the watermarks' 1.0 left out
    assert got["hot_upsert_ms"] == pytest.approx(6.0)
    assert got["flush_ms"] == pytest.approx(400.0) and got["flush_commit_ms"] == pytest.approx(30.0)
    assert got["hot_merge_ms"] == pytest.approx(7.0)          # 3, 7, 11 a read
    assert got["delta_scan_ms"] == pytest.approx(0.3)         # a scan without the segment: no sample


def test_a_program_that_marks_nothing_gives_none():
    """The parent of PR 30 has ``wal.*`` and ``flush*`` and none of the rest;
    a read-only cell has none of them and no writer."""
    view = lambda_view()
    old = {"ingest.read", "ingest.parse", "ingest.rows", "hot.upsert", "hot", "merge"}
    view["spans"] = [s for s in view["spans"] if s["name"] not in old]
    for s in view["spans"]:
        (s["attrs"].get("segments") or {}).pop("delta", None)
    got = {name: mod.read(view) for name, mod in READERS.items()}
    assert [n for n, v in got.items() if v is None] == [
        "ingest_parse_ms", "hot_upsert_ms", "hot_merge_ms", "delta_scan_ms"]
    assert got["wal_sync_ms"] == pytest.approx(4.0) and got["flush_ms"] == pytest.approx(400.0)
    bare = {"workload": "gdelt.dashboard", "spans": [], "seconds": 30.0,
            "client": {"query_ms": [1.0], "between_s": []}}
    assert {mod.read(bare) for mod in READERS.values()} == {None}


def test_the_manifest_lists_the_cell_and_its_readers():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next(w for w in bench["workloads"] if w["name"] == "gdelt.ingest-reads")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "gdelt-lambda-1chip", "append-beside-reads", 1)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert by[name]["workloads"] == ["gdelt.ingest-reads"]
        assert by[name]["layer"] == ("entry points" if name == "ingest_parse_ms"
                                     else "streaming tier")
    dashboard = {n for n, m in by.items() if "gdelt.dashboard" in m["workloads"]}
    assert dashboard <= {n for n, m in by.items() if "gdelt.ingest-reads" in m["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(e2e) == {"queries_per_s", "query_p95_ms", "single_mean_ms", "setup_s"}


# ------------------------------------------------------------------- the ops

CONFIG = {"schema": "actor1Name:String,numMentions:Integer,avgTone:Double,dtg:Date,"
                    "*geom:Point:srid=4326",
          "span_days": 16, "data": {"generator": "gdelt_live", "t0": "2024-01-01T00:00:00",
                                    "live_hours": 24}}


@pytest.fixture(scope="module")
def cols():
    return gdelt_live.make(CONFIG, 4096, 11)


def _spec(cols, writer, k, rows=100):
    return gdelt_live.batch_spec(cols.context() | {"seed": 11}, writer, k, 2, rows)


def test_ingest_compare_counts_rows_lost_and_changed(cols):
    spec = _spec(cols, 1, 2)
    req = {"op": "ingest", "spec": spec}
    want = cols.batch(spec)
    whole = {"ids": want["ids"][::-1].copy(), "x": want["x"][::-1].copy(),
             "y": want["y"][::-1].copy(), "t": want["t"][::-1].copy(),
             "attrs": {a: c[::-1].copy() for a, c in want["attrs"].items()}}

    def tally_of(answer):
        t = check.new_tally()
        ingest.compare(t, cols, req, answer)
        return t["acked_rows_lost"], t["acked_rows_changed"], t["doubled_rows"]

    assert tally_of(whole) == (0, 0, 0)  # in any order
    short = {k: (v[3:] if k != "attrs" else {a: c[3:] for a, c in v.items()})
             for k, v in whole.items()}
    assert tally_of(short) == (3, 0, 0)
    changed = dict(whole, attrs=dict(whole["attrs"]))
    changed["attrs"]["numMentions"] = whole["attrs"]["numMentions"].copy()
    changed["attrs"]["numMentions"][[0, 5]] += 1
    changed["x"] = whole["x"].copy()
    changed["x"][5] += 1e-9
    assert tally_of(changed) == (0, 2, 0)
    twice = {k: (np.concatenate([v, v[:4]]) if k != "attrs"
                 else {a: np.concatenate([c, c[:4]]) for a, c in v.items()})
             for k, v in whole.items()}
    assert tally_of(twice) == (0, 0, 4)
    t = check.new_tally()
    ingest.count(t, 5000, 4096, 900)
    assert t["count_gap"] == 4
    assert {"acked_rows_lost", "acked_rows_changed", "count_gap", "missing_acked_rows",
            "unknown_rows"} <= set(check.LIMITS) and not any(check.LIMITS.values())


def test_the_body_is_the_batch(cols):
    spec = _spec(cols, 0, 1)
    method, path, body, headers = ingest.http({"spec": spec}, "gdelt")
    feats = json.loads(body)["features"]
    assert [int(f["id"]) for f in feats] == cols.batch(spec)["ids"].tolist()
    f = feats[17]
    row = dict(f["properties"], geom=f["geometry"]["coordinates"])
    row["dtg"] = int(np.datetime64(row["dtg"].rstrip("Z"), "ms").astype(np.int64))
    assert row == cols.appended_row(spec, spec["first_id"] + 17)
    assert headers == {"Content-Type": "application/geo+json"} and method == "POST"


def test_appended_knows_what_must_and_may_be_there(cols):
    a, b = _spec(cols, 0, 0), _spec(cols, 1, 0)
    appended = query_live.Appended(cols, [(a, 1.0, 2.0), (b, 3.0, math.inf)])
    hi = cols.t0 + cols.span_ms
    req = {"box": [-180.0, -90.0, 180.0, 90.0], "win": [hi - 86_400_000, hi]}
    must, may = appended.must_may(req, sent=2.5, done=2.8)
    assert np.array_equal(must, cols.batch(a)["ids"]) and len(may) == 0
    must, may = appended.must_may(req, sent=2.5, done=3.5)
    assert np.array_equal(may, cols.batch(b)["ids"])      # sent, never acknowledged: may
    must, may = appended.must_may(req, sent=1.5, done=1.8)
    assert len(must) == 0 and np.array_equal(may, cols.batch(a)["ids"])
    assert appended.spec_of(a["first_id"] + 3) is a and appended.spec_of(5) is None
    assert appended.spec_of(b["first_id"] + 5000) is None
    empty = query_live.Appended(cols, [])
    assert [len(v) for v in empty.must_may(req, 1.0, 2.0)] == [0, 0]


def test_the_schedule(cols):
    traffic = {"rows_per_s": 2500}
    role = {"clients": 2, "params": {"batch_rows": 1000}}
    assert ingest_http.period_s(traffic, role) == pytest.approx(0.8)
    assert ingest_http.period_s({"rows_per_s": None}, role) is None
