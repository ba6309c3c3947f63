"""``layer_metrics/join_host_chunked_pct.py`` over hand-made ``join.host``
spans: ``chunked`` over ``points``, summed over the spans that count both; a
``join.host`` of a program that walks the table in one sweep (the parent of
PR 42) counts no ``chunked`` and is no sample, and a window of such spans,
or one with no broad member, reads None."""

import json
import os

import pytest

from layer_metrics import join_host_chunked_pct

CELL = "nyc-taxi.zone-join"


def _span(i, trace, name, dur_ms, parent=None, root="join", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(hosts):
    spans = []
    for k, attrs in enumerate(hosts):
        base = 10 * (k + 1)
        root = _span(base, base, "join", 600.0, members=1, predicate="contains", pairs=9000)
        # roots listed twice, as the harness lists them
        spans += [root, dict(root), _span(base + 1, base, "join.host", 500.0, parent=base, **attrs)]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [600.0] * len(hosts), "between_s": []}}


WHOLE = {"members": 1, "points": 65536, "decided": 60000, "residue": 5536}
CHUNKED = dict(WHOLE, chunks=64, chunked=65536)


def test_every_point_of_two_broad_members_went_through_the_chunks():
    two = dict(CHUNKED, members=2, points=131072, decided=120000, residue=11072, chunks=128,
               chunked=131072)
    assert join_host_chunked_pct.read(_view([CHUNKED, two])) == pytest.approx(100.0)


def test_the_share_is_summed_over_the_window():
    half = dict(CHUNKED, chunked=32768)
    assert join_host_chunked_pct.read(_view([CHUNKED, half])) == pytest.approx(75.0)


def test_a_span_of_a_program_that_sweeps_the_table_is_no_sample():
    assert join_host_chunked_pct.read(_view([CHUNKED, WHOLE])) == pytest.approx(100.0)
    assert join_host_chunked_pct.read(_view([WHOLE, WHOLE])) is None


def test_none_without_a_broad_member_or_a_join_root():
    view = _view([CHUNKED])
    view["spans"] = [s for s in view["spans"] if s["name"] != "join.host"]
    assert join_host_chunked_pct.read(view) is None
    assert join_host_chunked_pct.read(dict(view, spans=[])) is None
    nested = _view([CHUNKED])
    nested["spans"][-1]["parent"] = 99  # not a root's child
    assert join_host_chunked_pct.read(nested) is None


def test_it_is_the_last_metric_of_the_cell_and_of_an_accepted_layer():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = bench["per_layer"][-1]
    assert entry == {"name": "join_host_chunked_pct", "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "tables and native tier",
                     "moves": "query_p95_ms", "workloads": [CELL]}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
    host = next(m for m in bench["per_layer"] if m["name"] == "join_host_ms")
    assert (host["layer"], host["moves"], host["workloads"]) == (
        entry["layer"], entry["moves"], entry["workloads"])
