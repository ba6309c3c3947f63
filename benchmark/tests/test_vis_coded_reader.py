"""``layer_metrics/vis_coded_pct.py`` (PR 54) over hand-made spans: a
``query`` and a ``query_many`` of two members whose ``vis`` spans lie under
``decode`` and carry ``coded`` 1 (the mask looked up from label codes on the
scan's ordinals), a served request whose handler masked an answer by its
strings (``coded`` 0, under the ``http`` root); PR 53's spans, which carry
``rows``, ``kept`` and ``labels`` alone, give None, as do a store without
auths and another cell's spans; the readers PR 53 brought read the new spans
as they read the old."""

import json
import os

import pytest

from layer_metrics import vis_coded_pct, vis_keep_pct, vis_ms, vis_share_pct

CELL = "gdelt-secured.analyst"


def _span(i, trace, root, name, ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": ms / 1e3, "self_s": ms / 1e3, "attrs": attrs}


def _view(coded=(1, 1, 1, 0)):
    """``coded``: what the four ``vis`` spans carry, None for PR 53's."""
    def vis(k, i, trace, root, ms, parent, rows, kept):
        how = {} if coded is None else {"coded": coded[k]}
        return _span(i, trace, root, "vis", ms, parent, rows=rows, kept=kept, labels=12, **how)

    q, many = _span(1, 1, "query", "query", 10.0), _span(10, 2, "query_many", "query_many", 30.0)
    http = _span(20, 3, "http", "http", 20.0)
    spans = [
        q, dict(q),  # roots listed twice, as the harness lists them
        _span(2, 1, "query", "decode", 6.0, 1, candidates=1000,
              segments={"vis": 2e-5, "gather": 3e-3, "refine": 1e-3, "post": 2e-3}),
        vis(0, 3, 1, "query", 0.02, 2, 1000, 600),
        many, dict(many),
        _span(11, 2, "query_many", "decode", 8.0, 10, member=0, candidates=4000),
        vis(1, 12, 2, "query_many", 0.04, 11, 4000, 2000),
        _span(13, 2, "query_many", "decode", 4.0, 10, member=1, candidates=1000),
        vis(2, 14, 2, "query_many", 0.03, 13, 1000, 900),
        http, dict(http),
        vis(3, 21, 3, "http", 2.0, 20, 500, 100),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [10.5, 31.0, 20.5], "between_s": [0.0001]}}


def test_the_share_is_over_the_spans_that_say_how_they_decided():
    assert vis_coded_pct.read(_view()) == pytest.approx(75.0)
    assert vis_coded_pct.read(_view((1, 1, 1, 1))) == 100.0
    assert vis_coded_pct.read(_view((0, 0, 0, 0))) == 0.0
    mixed = _view()  # a span of PR 53's beside three that say: the three alone
    del mixed["spans"][-1]["attrs"]["coded"]
    assert vis_coded_pct.read(mixed) == 100.0


def test_pr_53s_spans_read_none():
    assert vis_coded_pct.read(_view(coded=None)) is None


def test_none_without_a_vis_span():
    view = _view()
    assert vis_coded_pct.read(dict(view, spans=[])) is None
    assert vis_coded_pct.read(
        dict(view, spans=[s for s in view["spans"] if s["name"] != "vis"])) is None


@pytest.mark.parametrize("coded", [(1, 1, 1, 0), None])
def test_the_readers_of_pr_53_read_the_span_wherever_it_lies(coded):
    """By name: under ``decode`` before the gather, or where PR 53 left it."""
    view = _view(coded)
    assert vis_ms.read(view) == pytest.approx(0.035)  # of 0.02, 0.03, 0.04, 2
    assert vis_keep_pct.read(view) == pytest.approx(100.0 * 3600 / 6500)
    assert vis_share_pct.read(view) == pytest.approx(100.0 * 2.09 / 60.0)


def test_the_cell_lists_it_in_the_planners_layer():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = next(m for m in bench["per_layer"] if m["name"] == "vis_coded_pct")
    assert mine == {"name": "vis_coded_pct", "unit": "%", "better": "higher",
                    "source": "program_counter", "layer": "planner", "moves": "query_p95_ms",
                    "workloads": [CELL]}
    keep = next(m for m in bench["per_layer"] if m["name"] == "vis_keep_pct")
    assert keep["layer"] == mine["layer"] and keep["workloads"] == mine["workloads"]
