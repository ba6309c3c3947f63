"""``layer_metrics/decompose_ms.py`` over hand-made views: the median
whole duration of ``plan.decompose`` spans under ``query`` roots, and
None where the window holds no such span."""

from layer_metrics import decompose_ms, plan_ms


def _span(i, trace, root, name, dur_ms, parent=None, self_ms=None, **attrs):
    self_ms = dur_ms if self_ms is None else self_ms
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": self_ms / 1e3, "attrs": attrs}


def _view():
    """Three ``query`` roots (listed twice, as the harness lists roots):
    two plan a fresh filter on both indexes, one hits the memo; and a
    ``query_many`` root whose members decompose too."""
    spans = []
    for k, (z3_ms, z2_ms) in enumerate(((0.9, 0.3), (0.5, 0.2))):
        base = 10 * (k + 1)
        q = _span(base, base, "query", "query", 6.0, self_ms=1.0)
        spans += [q, dict(q),
                  _span(base + 1, base, "query", "plan", 3.0, parent=base, self_ms=1.5),
                  _span(base + 2, base, "query", "plan.probe", 0.1, parent=base + 1),
                  # a child span of its own inside: the WHOLE duration counts
                  _span(base + 3, base, "query", "plan.decompose", z3_ms, parent=base + 1,
                        self_ms=z3_ms / 2, index="z3", ranges=1500),
                  _span(base + 4, base, "query", "plan.decompose", z2_ms, parent=base + 1,
                        index="z2", ranges=40)]
    warm = _span(30, 30, "query", "query", 2.0)
    spans += [warm, dict(warm),
              _span(31, 30, "query", "plan", 0.4, parent=30, self_ms=0.3),
              _span(32, 30, "query", "plan.probe", 0.1, parent=31)]
    many = _span(40, 40, "query_many", "query_many", 100.0)
    spans += [many, dict(many),
              _span(41, 40, "query_many", "plan", 9.0, parent=40, self_ms=1.0),
              _span(42, 40, "query_many", "plan.decompose", 8.0, parent=41, index="z3")]
    return {"workload": "gdelt.analyst", "spans": spans, "device": None,
            "client": {"query_ms": [6.0, 6.0, 2.0, 100.0], "between_s": []}}


def test_median_whole_duration_under_query_roots():
    # 0.9, 0.3, 0.5, 0.2 under `query`; the 8.0 under `query_many` is left out
    assert abs(decompose_ms.read(_view()) - 0.4) < 1e-9


def test_plan_ms_is_the_self_time_that_leaves_it_out():
    assert abs(plan_ms.read(_view()) - 1.5) < 1e-9


def test_none_where_nothing_decomposed():
    view = _view()
    view["spans"] = [s for s in view["spans"] if s["name"] != "plan.decompose"]
    assert decompose_ms.read(view) is None
    assert decompose_ms.read({"workload": "gdelt.dashboard", "spans": [], "device": None,
                              "client": {"query_ms": [], "between_s": []}}) is None
    only_many = _view()
    only_many["spans"] = [s for s in only_many["spans"] if s["root"] == "query_many"]
    assert decompose_ms.read(only_many) is None
