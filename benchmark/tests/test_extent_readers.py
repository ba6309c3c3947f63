"""``layer_metrics/refine_exact_pct.py``, ``refine_exact_us.py`` and
``extent_hit_pct.py`` over recorded ``decode`` spans: what PR 39's counters
(``refine_rect``, ``refine_accept``, ``refine_exact``, ``refine_exact_s``,
``refine_hits``) give, summed over the window; a ``decode`` span that
counts no tier (a store of points; the parent of PR 39) is no sample, and
a window of such spans reads None."""

import json
import os

import pytest

from layer_metrics import extent_hit_pct, refine_exact_pct, refine_exact_us

CELL = "osm-buildings.intersects"


def _span(i, trace, name, dur_ms, parent=None, root="query", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


#: three traced queries as the cell's spans came out of a CPU rehearsal:
#: a viewport of under 65 candidates (all to the exact tier), a viewport
#: the accept tier decides but for a few, a ring
RECORDED = (
    {"candidates": 44, "refine_rect": 0, "refine_exact": 44, "refine_exact_s": 0.0052,
     "refine_hits": 44, "gather_native": 0, "cpu_s": 0.006},
    {"candidates": 2351, "refine_rect": 2, "refine_accept": 2346, "refine_exact": 3,
     "refine_exact_s": 0.0004, "refine_hits": 2349, "gather_native": 1, "cpu_s": 0.01},
    {"candidates": 2178, "refine_rect": 1, "refine_accept": 1421, "refine_exact": 756,
     "refine_exact_s": 0.2444, "refine_hits": 1438, "gather_native": 1, "cpu_s": 0.25},
)


def _view(counted=True):
    spans = []
    for k, attrs in enumerate(RECORDED):
        base = 10 * (k + 1)
        root = _span(base, base, "query", 300.0)
        if not counted:
            attrs = {a: v for a, v in attrs.items() if not a.startswith("refine_")}
        # roots listed twice, as the harness lists them
        spans += [root, dict(root), _span(base + 1, base, "decode", 250.0, parent=base, **attrs)]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [300.0] * 3, "between_s": []}}


def test_the_tiers_of_the_recorded_spans_sum_to_their_candidates():
    for a in RECORDED:
        assert sum(a.get(k, 0) for k in ("refine_rect", "refine_accept", "refine_exact")) \
            == a["candidates"]


def test_the_readers_sum_over_the_window():
    view = _view()
    assert refine_exact_pct.read(view) == pytest.approx(100.0 * 803 / 4573)
    assert refine_exact_us.read(view) == pytest.approx(1e6 * 0.25 / 803)
    assert extent_hit_pct.read(view) == pytest.approx(100.0 * 3831 / 4573)


def test_a_span_that_counts_no_tier_is_no_sample():
    view = _view()
    view["spans"].append(_span(99, 90, "decode", 1.0, parent=90, candidates=10 ** 6))
    assert refine_exact_pct.read(view) == pytest.approx(100.0 * 803 / 4573)
    assert extent_hit_pct.read(view) == pytest.approx(100.0 * 3831 / 4573)


@pytest.mark.parametrize("reader", [refine_exact_pct, refine_exact_us, extent_hit_pct])
def test_none_where_the_program_counts_nothing(reader):
    assert reader.read(_view(counted=False)) is None  # the parent's spans
    assert reader.read({"workload": CELL, "spans": [], "device": None,
                        "client": {"query_ms": [], "between_s": []}}) is None


def test_no_geometry_in_the_exact_tier_is_no_time_a_geometry():
    view = _view()
    for s in view["spans"]:
        if s["name"] == "decode":
            s["attrs"].update(refine_exact=0)
            s["attrs"].pop("refine_exact_s", None)
    assert refine_exact_us.read(view) is None and refine_exact_pct.read(view) == 0.0


def test_they_are_metrics_of_the_footprints_cell_alone():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by = {m["name"]: m for m in bench["per_layer"]}
    want = {"refine_exact_pct": ("%", "lower", "program_counter", "queries_per_s"),
            "refine_exact_us": ("us", "lower", "program_span", "query_p95_ms"),
            "extent_hit_pct": ("%", "higher", "program_counter", "queries_per_s")}
    for name, (unit, better, source, moves) in want.items():
        assert by[name] == {"name": name, "unit": unit, "better": better, "source": source,
                            "layer": "tables and native tier", "moves": moves,
                            "workloads": [CELL]}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "osm-buildings-1chip"
    assert CELL not in next(m for m in bench["end_to_end"]
                            if m["name"] == "single_mean_ms")["workloads"]
