"""The eight readers of a broadcast join's spans (PR 41;
``layer_metrics/_join.py``) over hand-made spans: two ``join`` roots as
``sql/join.py``'s ``spatial_join_indexed`` leaves them (sixteen census
blocks on the device; one borough on the host's whole-table route), and
None on the spans of a program that opens no such root (the parent of
PR 41) or of another cell."""

import json
import os

import pytest

from layer_metrics import (join_coverage_pct, join_device_pct, join_host_ms, join_plan_ms,
                           join_polygon_us, join_refine_ms, join_residue_pct, join_scan_ms)

CELL = "nyc-taxi.zone-join"
READERS = {"join_plan_ms": join_plan_ms, "join_polygon_us": join_polygon_us,
           "join_scan_ms": join_scan_ms, "join_refine_ms": join_refine_ms,
           "join_host_ms": join_host_ms, "join_device_pct": join_device_pct,
           "join_residue_pct": join_residue_pct, "join_coverage_pct": join_coverage_pct}


def _span(i, trace, name, dur_ms, parent=None, root="join", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view():
    blocks = _span(10, 10, "join", 50.0, members=16, predicate="contains", pairs=900)
    boro = _span(20, 20, "join", 400.0, members=1, predicate="contains", pairs=9000)
    spans = [
        blocks, dict(blocks),  # roots listed twice, as the harness lists them
        _span(11, 10, "join.plan", 32.0, parent=10, pip=15, rast=0, bbox_only=0, host_raster=0,
              empty=1, edges=190, ranges=4000, candidate_rows=5000),
        _span(12, 10, "dispatch", 3.0, parent=10, members=15, blocks=40, slots=1024, groups=1),
        _span(13, 10, "scan", 2.0, parent=10, member=0, segments={"wait": 0.001, "pull": 0.0005}),
        _span(14, 10, "scan", 1.0, parent=10, member=1),
        _span(15, 10, "join.refine", 1.5, parent=10, member=0, rows=600, certain=540, uncertain=60),
        _span(16, 10, "join.refine", 0.5, parent=10, member=1, rows=400, certain=400, uncertain=0),
        _span(17, 10, "join.assemble", 2.0, parent=10, members=2),
        boro, dict(boro),
        _span(21, 20, "join.plan", 8.0, parent=20, pip=0, rast=0, bbox_only=0, host_raster=1,
              empty=0, edges=1084, ranges=250, candidate_rows=60000),
        _span(22, 20, "join.host", 380.0, parent=20, members=1, points=65536, decided=60000,
              residue=5536),
        _span(23, 20, "dispatch", 0.1, parent=20, members=0),
        _span(24, 20, "join.assemble", 4.0, parent=20, members=1),
        # a dispatch nested under a member's own is not a root's child
        _span(25, 20, "dispatch", 99.0, parent=23),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [51.0, 404.0], "between_s": [0.0001]}}


def test_the_readers_read_two_roots():
    view = _view()
    assert join_plan_ms.read(view) == pytest.approx(20.0)  # the median of 32 and 8
    assert join_polygon_us.read(view) == pytest.approx(40_000 / 17)
    assert join_scan_ms.read(view) == pytest.approx((6.0 + 0.1) / 2)
    assert join_refine_ms.read(view) == pytest.approx((4.0 + 4.0) / 2)
    assert join_host_ms.read(view) == pytest.approx(380.0)
    assert join_device_pct.read(view) == pytest.approx(100.0 * 15 / 17)
    assert join_residue_pct.read(view) == pytest.approx(6.0)
    assert join_coverage_pct.read(view) == pytest.approx(100.0 * 450 / 455)


def test_a_window_with_no_broad_member_has_no_host_time():
    view = _view()
    view["spans"] = [s for s in view["spans"] if s["trace"] == 10]
    assert join_host_ms.read(view) is None
    assert join_plan_ms.read(view) == pytest.approx(32.0)
    assert join_device_pct.read(view) == pytest.approx(100.0 * 15 / 16)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_the_spans_of_a_program_without_the_root(name):
    """The parent of PR 41 runs the join with no root, so no span of it is
    kept; another cell's spans hang under other roots."""
    empty = {"workload": CELL, "spans": [], "device": None,
             "client": {"query_ms": [51.0], "between_s": []}}
    assert READERS[name].read(empty) is None
    query = _span(30, 30, "query", 5.0, root="query")
    other = dict(empty, spans=[query, dict(query),
                               _span(31, 30, "dispatch", 1.0, parent=30, root="query", blocks=3,
                                     slots=32),
                               _span(32, 30, "scan", 1.0, parent=30, root="query")])
    assert READERS[name].read(other) is None


def test_the_cell_lists_each_reader_and_the_layers_are_the_accepted_ones():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(mine) == set(READERS)
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in READERS}
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["moves"] in ("queries_per_s", "query_p95_ms")
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "nyc-taxi-1chip"
