"""``layer_metrics/refine_batched_pct.py`` over hand-made ``decode`` spans:
``refine_batched`` over ``refine_exact``, summed over the spans that count
both; a span that counts the tiers but no ``refine_batched`` (the parent of
PR 40) is no sample, and a window of such spans reads None."""

import json
import os

import pytest

from layer_metrics import refine_batched_pct

CELL = "osm-buildings.intersects"


def _span(i, trace, name, dur_ms, parent=None, root="query", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


#: a viewport the accept tier decides but for three, a ring over footprints
#: (all polygons), a ring over a column that also holds 40 points the accept
#: tier left, a viewport whose every candidate the accept tier took
RECORDED = (
    {"candidates": 2351, "refine_rect": 2, "refine_accept": 2346, "refine_exact": 3,
     "refine_batched": 3, "refine_exact_s": 0.0002, "refine_hits": 2349},
    {"candidates": 2178, "refine_rect": 1, "refine_accept": 1421, "refine_exact": 756,
     "refine_batched": 756, "refine_exact_s": 0.0071, "refine_hits": 1438},
    {"candidates": 400, "refine_rect": 0, "refine_accept": 200, "refine_exact": 200,
     "refine_batched": 160, "refine_exact_s": 0.0093, "refine_hits": 230},
    {"candidates": 44, "refine_rect": 0, "refine_accept": 44, "refine_exact": 0,
     "refine_batched": 0, "refine_hits": 44},
)


def _view(counted=True):
    spans = []
    for k, attrs in enumerate(RECORDED):
        base = 10 * (k + 1)
        root = _span(base, base, "query", 30.0)
        if not counted:
            attrs = {a: v for a, v in attrs.items() if a != "refine_batched"}
        # roots listed twice, as the harness lists them
        spans += [root, dict(root), _span(base + 1, base, "decode", 25.0, parent=base, **attrs)]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [30.0] * len(RECORDED), "between_s": []}}


def test_the_share_is_summed_over_the_window():
    assert refine_batched_pct.read(_view()) == pytest.approx(100.0 * 919 / 959)


def test_a_span_of_a_program_that_counts_no_batch_is_no_sample():
    view = _view()
    view["spans"].append(_span(99, 90, "decode", 1.0, parent=90, candidates=500, refine_rect=0,
                               refine_exact=500, refine_exact_s=0.1, refine_hits=9))
    assert refine_batched_pct.read(view) == pytest.approx(100.0 * 919 / 959)


def test_none_where_the_program_counts_nothing():
    assert refine_batched_pct.read(_view(counted=False)) is None  # the parent's spans
    assert refine_batched_pct.read({"workload": CELL, "spans": [], "device": None,
                                    "client": {"query_ms": [], "between_s": []}}) is None


def test_none_where_no_geometry_reached_the_exact_tier():
    view = _view()
    view["spans"] = [s for s in view["spans"] if s["attrs"].get("refine_exact", 0) == 0]
    assert refine_batched_pct.read(view) is None


def test_it_is_a_metric_of_the_footprints_cell_alone():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == "refine_batched_pct")
    assert entry == {
        "name": "refine_batched_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tables and native tier",
        "moves": "query_p95_ms", "workloads": [CELL],
    }
    exact_us = next(m for m in bench["per_layer"] if m["name"] == "refine_exact_us")
    assert (entry["moves"], entry["workloads"]) == (exact_us["moves"], exact_us["workloads"])
