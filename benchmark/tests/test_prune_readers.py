"""``layer_metrics/prune_ms.py`` and ``spans_reused_pct.py`` over hand-made
views: the ``prune`` segment of ``dispatch`` spans, and the share of them in
which every member found its candidate spans; None where a program marks
no such segment or counts no ``spans_reused`` (the parent of PR 27)."""

import json
import os

from layer_metrics import prune_ms, spans_reused_pct


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True):
    """Two ``query`` roots, one ``query_many`` of 32 whose staging nests one
    member's own dispatch, one ``density``; roots listed twice, as the
    harness lists them; a served ``dispatch`` (retroactive) has no segment."""
    def reused(n):
        return {"spans_reused": n} if counted else {}

    spans = []
    for k, (prune, n) in enumerate(((0.4, 1), (0.2, 0))):
        base = 10 * (k + 1)
        q = _span(base, base, "query", "query", 6.0)
        spans += [q, dict(q),
                  _span(base + 1, base, "query", "dispatch", 1.2, parent=base,
                        segments={"prune": prune / 1e3, "enqueue": 0.8e-3},
                        blocks=3, slots=32, **reused(n))]
    many = _span(40, 40, "query_many", "query_many", 100.0, members=32)
    spans += [many, dict(many),
              _span(41, 40, "query_many", "dispatch", 20.0, parent=40, members=32, blocks=90,
                    slots=128, groups=1,
                    segments={"prune": 6.0e-3, "enqueue": 2.0e-3}, **reused(31)),
              _span(42, 40, "query_many", "dispatch", 1.0, parent=41,
                    segments={"prune": 0.1e-3, "enqueue": 0.7e-3}, **reused(1))]
    dens = _span(50, 50, "density", "density", 5.0)
    spans += [dens, dict(dens),
              _span(51, 50, "density", "dispatch", 1.0, parent=50,
                    segments={"prune": 0.3e-3, "enqueue": 0.6e-3}, **reused(1))]
    served = _span(60, 60, "query", "query", 150.0)
    spans += [served, dict(served), _span(61, 60, "query", "dispatch", 25.0, parent=60)]
    return {"workload": "gdelt.analyst", "spans": spans, "device": None,
            "client": {"query_ms": [6.0, 6.0, 100.0, 5.0], "between_s": []}}


def test_prune_is_the_median_segment_over_every_dispatch_that_marks_it():
    # 0.4, 0.2, 6.0, 0.1, 0.3 ms; the served dispatch carries no segment
    assert abs(prune_ms.read(_view()) - 0.3) < 1e-9
    assert abs(prune_ms.read(_view(counted=False)) - 0.3) < 1e-9  # the parent marks it too


def test_reused_share_counts_a_dispatch_whose_every_member_found_its_spans():
    # query 1/1 yes, query 0/1 no, query_many 31/32 no, nested 1/1 yes, density yes
    assert abs(spans_reused_pct.read(_view()) - 60.0) < 1e-9


def test_none_where_the_program_counts_nothing():
    assert spans_reused_pct.read(_view(counted=False)) is None
    empty = {"workload": "gdelt.dashboard", "spans": [], "device": None,
             "client": {"query_ms": [], "between_s": []}}
    assert prune_ms.read(empty) is None and spans_reused_pct.read(empty) is None


def test_both_are_metrics_of_every_cell():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = [w["name"] for w in bench["workloads"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    assert by["prune_ms"]["workloads"] == cells and by["prune_ms"]["moves"] == "query_p95_ms"
    assert by["spans_reused_pct"]["workloads"] == cells
    assert by["spans_reused_pct"]["source"] == "program_counter"
    assert {by[n]["layer"] for n in ("prune_ms", "spans_reused_pct")} == {"tables and native tier"}
