"""The ten readers of the two processes' spans (PR 46;
``layer_metrics/_process.py``) over hand-made spans: a ``knn`` root of two
rounds, a ``tube`` root with its linked ``query`` root, a ``query`` no tube
asked; None on the spans of a program that opens neither root (the parent
of PR 46) or of another cell. Finds the cell's entries in ``BENCHMARK.json``
by name, wherever a later PR's entries leave them."""

import importlib
import json
import os

import pytest

CELL = "ais.vessel-proximity"
NAMES = ("knn_plan_ms", "tube_plan_ms", "knn_rounds", "knn_scan_ms", "knn_rank_ms",
         "knn_overfetch", "tube_scan_ms", "tube_refine_ms", "tube_keep_pct",
         "process_coverage_pct")
READERS = {n: importlib.import_module("layer_metrics." + n) for n in NAMES}


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view():
    knn = _span(1, 1, "knn", "knn", 20.0, members=16, k=8, rounds=2, windows=20,
                candidates=400, returned=128, short=0)
    tube = _span(20, 2, "tube", "tube", 100.0, waypoints=360, bins=256, buffer_m=2000.0,
                 boxes=256, windows=1, ranges=900, candidates=5000, rows=1000, kept=250,
                 query_trace=3)
    query = _span(30, 3, "query", "query", 80.0, tube_trace=2)
    lone = _span(40, 4, "query", "query", 5.0)
    spans = [
        knn, dict(knn),  # roots twice, as the harness lists them
        _span(2, 1, "knn", "knn.estimate", 0.5, parent=1, members=16, probes=40),
        _span(3, 1, "knn", "knn.round", 9.0, parent=1, pending=16, radius_max_m=4000.0),
        _span(4, 1, "knn", "plan", 4.0, parent=3), _span(5, 1, "knn", "dispatch", 2.0, parent=3),
        _span(6, 1, "knn", "dispatch", 1.5, parent=5),  # a lone member's own, nested
        _span(7, 1, "knn", "scan", 1.0, parent=3), _span(8, 1, "knn", "decode", 1.0, parent=3),
        _span(9, 1, "knn", "knn.rank", 0.5, parent=3),
        _span(10, 1, "knn", "knn.round", 5.0, parent=1, pending=4, radius_max_m=64000.0),
        _span(11, 1, "knn", "plan", 1.0, parent=10),
        _span(12, 1, "knn", "dispatch", 1.0, parent=10),
        _span(13, 1, "knn", "scan", 0.5, parent=10), _span(14, 1, "knn", "decode", 1.5, parent=10),
        _span(15, 1, "knn", "knn.rank", 1.0, parent=10),
        tube, dict(tube),
        _span(21, 2, "tube", "tube.bins", 4.0, parent=20),
        _span(22, 2, "tube", "tube.refine", 6.0, parent=20, rows=1000),
        query, dict(query),
        _span(31, 3, "query", "plan", 30.0, parent=30),
        _span(32, 3, "query", "plan.decompose", 20.0, parent=31),
        _span(33, 3, "query", "dispatch", 2.0, parent=30),
        _span(34, 3, "query", "scan", 3.0, parent=30),
        _span(35, 3, "query", "decode", 40.0, parent=30, candidates=5000),
        lone, dict(lone), _span(41, 4, "query", "plan", 99.0, parent=40),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [21.0, 104.0, 25.0], "between_s": [0.0001]}}


def test_the_readers_read_a_knn_and_a_tube():
    view, r = _view(), READERS
    assert r["knn_plan_ms"].read(view) == pytest.approx(5.0)
    assert r["knn_scan_ms"].read(view) == pytest.approx(3.0 + 1.5)  # the nested dispatch left out
    assert r["knn_rank_ms"].read(view) == pytest.approx(4.0)
    assert r["knn_rounds"].read(view) == pytest.approx(20 / 16)
    assert r["knn_overfetch"].read(view) == pytest.approx(400 / 128)
    assert r["tube_plan_ms"].read(view) == pytest.approx(30.0)  # the lone query's 99 is not a tube's
    assert r["tube_scan_ms"].read(view) == pytest.approx(5.0)
    assert r["tube_refine_ms"].read(view) == pytest.approx(46.0)
    assert r["tube_keep_pct"].read(view) == pytest.approx(5.0)
    assert r["process_coverage_pct"].read(view) == pytest.approx(100.0 * 120 / 150)


def test_the_medians_are_over_roots_and_the_shares_pooled():
    view = _view()
    second = _span(50, 5, "knn", "knn", 4.0, members=1, k=32, rounds=1, windows=1,
                   candidates=40, returned=32, short=0)
    view["spans"] += [second, _span(51, 5, "knn", "knn.round", 3.0, parent=50, pending=1),
                      _span(52, 5, "knn", "plan", 1.0, parent=51)]
    assert READERS["knn_plan_ms"].read(view) == pytest.approx((5.0 + 1.0) / 2)
    assert READERS["knn_rounds"].read(view) == pytest.approx(21 / 17)
    assert READERS["knn_overfetch"].read(view) == pytest.approx(440 / 160)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_the_spans_of_a_program_without_the_roots(name):
    """PR 46's parent opens neither root: a kNN leaves no span at all, a
    tube's query is a plain ``query`` root with no ``tube_trace``."""
    client = {"query_ms": [51.0], "between_s": []}
    assert READERS[name].read({"workload": CELL, "spans": [], "client": client}) is None
    parent = [dict(s, attrs={}) for s in _view()["spans"] if s["root"] == "query"]
    assert READERS[name].read({"workload": CELL, "spans": parent, "client": client}) is None


def test_a_tube_whose_query_was_sampled_out_gives_no_share():
    view = _view()
    view["spans"] = [dict(s, attrs={k: v for k, v in s["attrs"].items()
                                    if k not in ("candidates", "boxes", "windows", "ranges")})
                     for s in view["spans"] if s["root"] == "tube"]
    assert READERS["tube_keep_pct"].read(view) is None
    assert READERS["tube_refine_ms"].read(view) == pytest.approx(6.0)  # its own child alone


def test_the_cell_lists_each_reader_under_an_accepted_layer():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in READERS}
    for m in mine.values():
        assert CELL in m["workloads"] and m["layer"] in layers
        assert m["moves"] in ("queries_per_s", "query_p95_ms")
        assert m["source"] in ("program_span", "program_counter")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ais-reports-1chip", "vessel-proximity", 1)
    config = next(c for c in bench["configs"] if c["name"] == "ais-reports-1chip")
    assert config["reduced"] == ["rows", "span_days"]
