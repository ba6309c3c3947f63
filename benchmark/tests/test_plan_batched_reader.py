"""``layer_metrics/plan_batched_pct.py`` over a hand-made view: the share of
``query_many`` members that the batch's one ``plan`` span took through the
batched stages; None where a program's ``plan`` spans count neither (the
parent of PR 31 opens a ``plan`` a member, without the attributes)."""

import json
import os

from layer_metrics import many_plan_ms, plan_batched_pct


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True):
    """Two ``query_many`` roots of 32 (one with a member that fell back to
    ``plan``'s own stages, whose nested probe is a child of the batch's
    span), a ``query`` root whose ``plan`` must not count, a served
    ``query``; roots listed twice, as the harness lists them."""
    def plan(i, trace, dur_ms, batched):
        attrs = {"members": 32, "batched": batched, "cpu_s": dur_ms / 1e3,
                 "segments": {"parse": 2e-3, "extract": 1e-3, "decompose": 8e-3,
                              "spans": 3e-3, "estimate": 2e-3}} if counted else {}
        return _span(i, trace, "query_many", "plan", dur_ms, parent=trace, **attrs)

    spans = []
    for base, batched in ((100, 32), (200, 31)):
        many = _span(base, base, "query_many", "query_many", 60.0, members=32)
        spans += [many, dict(many), plan(base + 1, base, 20.0, batched),
                  _span(base + 2, base, "query_many", "plan.probe", 0.1, parent=base + 1,
                        index="z3", members=32),
                  _span(base + 3, base, "query_many", "plan.decompose", 6.0, parent=base + 1,
                        index="z3", members=32, ranges=9000),
                  _span(base + 4, base, "query_many", "dispatch", 8.0, parent=base, members=32)]
    q = _span(300, 300, "query", "query", 5.0)
    spans += [q, dict(q),
              # a single query's plan: were it counted, 1 of 1 more
              _span(301, 300, "query", "plan", 0.8, parent=300, members=1, batched=1)]
    served = _span(400, 400, "query", "query", 150.0)
    spans += [served, dict(served), _span(401, 400, "query", "plan", 25.0, parent=400)]
    return {"workload": "gdelt.analyst", "spans": spans, "device": None,
            "client": {"query_ms": [60.0, 60.0, 5.0], "between_s": []}}


def test_the_share_is_batched_over_members_of_the_plans_under_query_many_roots():
    assert abs(plan_batched_pct.read(_view()) - 100.0 * 63 / 64) < 1e-9


def test_many_plan_ms_reads_the_one_span_a_batch_unchanged():
    assert abs(many_plan_ms.read(_view()) - 20.0) < 1e-9
    assert abs(many_plan_ms.read(_view(counted=False)) - 20.0) < 1e-9


def test_none_where_the_program_counts_nothing():
    assert plan_batched_pct.read(_view(counted=False)) is None
    empty = {"workload": "gdelt.dashboard", "spans": [], "device": None,
             "client": {"query_ms": [], "between_s": []}}
    assert plan_batched_pct.read(empty) is None


def test_it_is_a_planner_metric_of_the_two_analyst_cells():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    (m,) = [m for m in bench["per_layer"] if m["name"] == "plan_batched_pct"]
    assert m == bench["per_layer"][-1]
    assert m["workloads"] == ["gdelt.analyst", "gdelt-mesh4.analyst"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "program_counter", "planner", "query_p95_ms")
