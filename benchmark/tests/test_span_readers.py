"""The readers PR 24 added under ``layer_metrics/``, each over a hand-made
view with known answers, and over a view of a program that lacks what
they read (the parent commit): None, never an error."""

import importlib

import pytest

from layer_metrics import _segments, idle_named_pct

NEW = ["dispatch_ms", "batch_wait_ms", "device_wait_ms", "pull_ms", "gather_ms", "refine_ms",
       "encode_ms", "lock_wait_pct", "span_coverage_pct", "scan_useful_pct", "many_plan_ms",
       "many_dispatch_ms", "many_scan_ms", "many_decode_ms", "idle_named_pct"]


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _seg(**ms):
    return {k: v / 1e3 for k, v in ms.items()}


def served_view():
    """Two requests: each an ``http`` root and a ``query`` root (listed
    twice, as the harness lists roots), fused into one batch."""
    spans = []
    for k, base in enumerate((100, 200)):
        http = _span(base, base, "http", "http", 100.0, query_trace=base + 50)
        spans += [http, dict(http),
                  _span(base + 1, base, "http", "http.parse", 1.0, parent=base, cpu_s=0.001),
                  _span(base + 2, base, "http", "http.wait", 80.0, parent=base, cpu_s=0.002),
                  _span(base + 3, base, "http", "encode", 10.0 + 10 * k, parent=base,
                        cpu_s=0.002 + 0.002 * k, write_s=0.001)]
        q = _span(base + 50, base + 50, "query", "query", 70.0, http_trace=base)
        spans += [q, dict(q),
                  _span(base + 51, base + 50, "query", "plan", 20.0, parent=base + 50,
                        cpu_s=0.002),
                  _span(base + 52, base + 50, "query", "queue", 30.0, parent=base + 50),
                  _span(base + 53, base + 50, "query", "dispatch", 4.0 + 2 * k,
                        parent=base + 50),
                  _span(base + 54, base + 50, "query", "batch.wait", 3.0 + 4 * k,
                        parent=base + 50),
                  _span(base + 55, base + 50, "query", "scan", 2.0, parent=base + 50,
                        cpu_s=0.001, member=k,
                        segments=_seg(wait=0.5, pull=0.3, bits=1.0) if k == 0
                        else _seg(bits=1.5)),
                  _span(base + 56, base + 50, "query", "decode", 8.0, parent=base + 50,
                        cpu_s=0.002, segments=_seg(gather=4.0 + 2 * k, refine=2.0, post=1.0))]
    batch = _span(300, 300, "batch", "batch", 20.0, members=2)
    spans += [batch, dict(batch),
              _span(301, 300, "batch", "dispatch", 5.0, parent=300, blocks=6, slots=128,
                    segments=_seg(prune=3.0, enqueue=2.0))]
    return {"workload": "gdelt.dashboard", "spans": spans, "device": None,
            "client": {"query_ms": [125.0, 125.0], "between_s": []}}


def embedded_view():
    """One ``query``, one ``count`` and two ``query_many`` roots."""
    spans = []
    q = _span(1, 1, "query", "query", 6.0)
    spans += [q, dict(q),
              _span(2, 1, "query", "plan", 1.0, parent=1, cpu_s=0.001),
              _span(3, 1, "query", "dispatch", 1.5, parent=1, blocks=3, slots=32),
              _span(4, 1, "query", "scan", 1.0, parent=1,
                    segments=_seg(wait=0.2, pull=0.1, bits=0.6)),
              _span(5, 1, "query", "decode", 2.0, parent=1, cpu_s=0.002,
                    segments=_seg(gather=1.2, refine=0.5, post=0.1))]
    c = _span(10, 10, "count", "count", 4.0)
    spans += [c, dict(c)]
    for k, base in enumerate((20, 60)):
        m = _span(base, base, "query_many", "query_many", 100.0 + 20 * k, members=2)
        spans += [m, dict(m),
                  _span(base + 1, base, "query_many", "plan", 10.0, parent=base, cpu_s=0.010),
                  _span(base + 2, base, "query_many", "plan", 12.0 + 4 * k, parent=base,
                        cpu_s=0.012),
                  _span(base + 3, base, "query_many", "dispatch", 8.0, parent=base,
                        blocks=10, slots=128),
                  # a member that dispatched alone nests its own: not counted twice
                  _span(base + 4, base, "query_many", "dispatch", 2.0, parent=base + 3,
                        blocks=1, slots=32),
                  _span(base + 5, base, "query_many", "scan", 3.0, parent=base, member=0,
                        segments=_seg(wait=1.0, pull=0.4, bits=1.5)),
                  _span(base + 6, base, "query_many", "scan", 1.0, parent=base, member=1,
                        segments=_seg(bits=0.9)),
                  _span(base + 7, base, "query_many", "decode", 30.0, parent=base, member=0,
                        cpu_s=0.030, segments=_seg(gather=20.0, refine=8.0, post=1.0)),
                  _span(base + 8, base, "query_many", "decode", 20.0 + 10 * k, parent=base,
                        member=1, cpu_s=0.020, segments=_seg(gather=12.0, refine=6.0))]
    return {"workload": "gdelt.analyst", "spans": spans, "device": None,
            "client": {"query_ms": [6.5, 4.5, 104.0, 125.0], "between_s": []}}


def parent_view():
    """What the parent of PR 24 gives: ``query`` roots with plain
    ``plan``, ``scan`` and ``decode`` leaves, no segment, no ``cpu_s``,
    no other root."""
    q = _span(1, 1, "query", "query", 6.0)
    return {"workload": "gdelt.analyst", "device": None,
            "spans": [q, dict(q), _span(2, 1, "query", "plan", 1.0, parent=1),
                      _span(4, 1, "query", "scan", 1.0, parent=1),
                      _span(5, 1, "query", "decode", 2.0, parent=1)],
            "client": {"query_ms": [], "between_s": []}}


def _read(name, view):
    return importlib.import_module("layer_metrics." + name).read(view)


SERVED = {"dispatch_ms": 5.0, "batch_wait_ms": 5.0, "device_wait_ms": 0.5, "pull_ms": 0.3,
          "gather_ms": 5.0, "refine_ms": 3.0, "encode_ms": 15.0,
          # plan 20+20, decode 8+8, encode 10+20 = 86 ms of wall; cpu 2+2+2+2+2+4 = 14 ms
          "lock_wait_pct": 100.0 * (1 - 14.0 / 86.0),
          "span_coverage_pct": 80.0, "scan_useful_pct": 100.0 * 6 / 128,
          "many_plan_ms": None, "many_dispatch_ms": None, "many_scan_ms": None,
          "many_decode_ms": None, "idle_named_pct": None}

EMBEDDED = {"dispatch_ms": 1.5, "batch_wait_ms": None, "device_wait_ms": 1.0, "pull_ms": 0.4,
            "gather_ms": 12.0, "refine_ms": 6.0, "encode_ms": None,
            # plan 1+10+12+10+16, decode 2+30+20+30+30 = 161 ms; their cpu halves nothing:
            # 1+10+12+10+12 + 2+30+20+30+20 = 147 ms
            "lock_wait_pct": 100.0 * (1 - 147.0 / 161.0),
            "span_coverage_pct": 100.0 * (6 + 4 + 100 + 120) / 240.0,
            "scan_useful_pct": 100.0 * (3 + 10 + 1 + 10 + 1) / (32 + 128 + 32 + 128 + 32),
            "many_plan_ms": 24.0, "many_dispatch_ms": 8.0, "many_scan_ms": 4.0,
            "many_decode_ms": 55.0, "idle_named_pct": None}


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_served_view(name):
    want = SERVED[name]
    got = _read(name, served_view())
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_on_an_embedded_view(name):
    want = EMBEDDED[name]
    got = _read(name, embedded_view())
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_the_parents_spans(name):
    """No segment, no ``cpu_s``, no new root, no ``dispatch`` span, no
    client sample in this view: None from every reader, no error. (On
    the real parent ``dispatch_ms``, ``batch_wait_ms`` and
    ``span_coverage_pct`` find the spans it always had and read them.)"""
    assert _read(name, parent_view()) is None


def test_every_new_reader_is_a_metric_of_the_benchmark():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        per_layer = {m["name"]: m for m in json.load(fh)["per_layer"]}
    assert set(NEW) <= set(per_layer)
    assert [m for m in per_layer][-len(NEW):] == NEW  # appended, in the issue's order


def test_spans_lists_a_root_once():
    v = served_view()
    assert len([s for s in v["spans"] if s["name"] == "http"]) == 4
    assert len(_segments.spans(v, "http")) == 2
    assert len(_segments.spans(v, "dispatch", roots=("batch",))) == 1


def test_idle_share_on_hand_made_events():
    """Window 0..1000, busy 100..200 and 600..700: idle 800. ``geomesa:``
    events cover 0..150 and 400..650 and (outside the window) 1200..1300:
    of the idle time 100 + 200 = 300 lie under them."""
    busy = [(100.0, 200.0), (600.0, 700.0)]
    named = [(0.0, 150.0), (400.0, 500.0), (450.0, 650.0), (1200.0, 1300.0)]
    assert idle_named_pct.share(busy, named, (0.0, 1000.0)) == pytest.approx(37.5)
    # no window annotation: the extent of the device ops, 100..700: idle 400, named 200
    assert idle_named_pct.share(busy, named, None) == pytest.approx(50.0)
    assert idle_named_pct.share(busy, [], (0.0, 1000.0)) is None
    assert idle_named_pct.share([], named, (0.0, 1000.0)) is None


def test_idle_named_reads_nothing_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(idle_named_pct, "OUT_DIR", str(tmp_path))
    view = dict(served_view(), device={"window_s": 1.0, "busy_s": 0.1})
    assert idle_named_pct.read(view) is None
