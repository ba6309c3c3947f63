"""``layer_metrics/encode_native_pct.py`` over hand-made views: the share
of the ``encode`` spans of ``http`` roots whose ``native`` is 1; Arrow
answers (no ``native``) are no sample; None where a program counts no
``native`` (the parent of PR 38)."""

import json
import os

from layer_metrics import encode_native_pct


def _span(i, trace, name, dur_ms, parent=None, root="http", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True):
    """Five served answers: three GeoJSON by the native route, one by the
    per-feature route, one Arrow; roots listed twice, as the harness lists
    them; an ``encode`` under another root is not the entry point's."""
    spans = []
    for k, native in enumerate((1, 1, 0, 1, None)):
        base = 10 * (k + 1)
        root = _span(base, base, "http", 120.0, fmt="arrow" if native is None else "geojson")
        attrs = {"bytes": 4000, "chunks": 1, "write_s": 1e-4}
        if counted and native is not None:
            attrs["native"] = native
        spans += [root, dict(root), _span(base + 1, base, "encode", 1.5, parent=base, **attrs)]
    other = _span(90, 90, "query", 5.0, root="query")
    spans += [other, dict(other), _span(91, 90, "encode", 1.0, parent=90, root="query", native=0)]
    return {"workload": "gdelt.dashboard", "spans": spans, "device": None,
            "client": {"query_ms": [120.0] * 5, "between_s": []}}


def test_the_share_is_over_the_spans_that_say_which_route():
    assert abs(encode_native_pct.read(_view()) - 75.0) < 1e-9


def test_none_where_the_program_counts_nothing():
    assert encode_native_pct.read(_view(counted=False)) is None
    empty = {"workload": "gdelt.dashboard", "spans": [], "device": None,
             "client": {"query_ms": [], "between_s": []}}
    assert encode_native_pct.read(empty) is None


def test_it_is_a_metric_of_the_served_cells():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == "encode_native_pct")
    assert entry == {
        "name": "encode_native_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "entry points", "moves": "queries_per_s",
        "workloads": ["gdelt.dashboard", "gdelt.ingest-reads"],
    }
    served = {m["name"]: m["workloads"] for m in bench["per_layer"]}["encode_ms"]
    assert entry["workloads"] == served
