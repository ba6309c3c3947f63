"""``layer_metrics/encode_arrow_ms.py`` and ``arrow_native_pct.py`` over
hand-made views: the ``encode`` spans of the ``http`` roots whose ``fmt``
is ``arrow`` (joined by the span's ``parent``), their median wall and the
share whose ``arrow_native`` is 1; a program that counts no
``arrow_native`` (the parent of PR 43) still reads the median; and
``encode_native_pct`` goes on reading the GeoJSON answers alone."""

import json
import os

from layer_metrics import arrow_native_pct, encode_arrow_ms, encode_ms, encode_native_pct

ARROW_MS = (4.0, 6.0, 5.0, 110.0)  # three by the native build, one by pyarrow's
GEOJSON_MS = (3.0, 3.5, 2.5, 3.2, 90.0)


def _span(i, trace, name, dur_ms, parent=None, root="http", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True):
    """Nine served answers of both formats, a post and an ``encode`` under
    another root; roots listed twice, as the harness lists them."""
    spans = []

    def answer(base, fmt, dur_ms, **attrs):
        root = _span(base, base, "http", 100.0 + dur_ms, method="GET", fmt=fmt, rows=7)
        spans.extend([root, dict(root), _span(base + 1, base, "http.wait", 90.0, parent=base),
                      _span(base + 2, base, "encode", dur_ms, parent=base,
                            bytes=4000, chunks=1, write_s=1e-4, **attrs)])

    for k, ms in enumerate(ARROW_MS):
        answer(10 * (k + 1), "arrow", ms,
               **({"arrow_native": int(ms < 100), "py_writes": 0} if counted else {}))
    for k, ms in enumerate(GEOJSON_MS):
        answer(100 + 10 * k, "geojson", ms, native=int(ms < 50))
    post = _span(200, 200, "http", 95.0, method="POST")
    other = _span(300, 300, "query", 5.0, root="query")
    spans += [post, dict(post), _span(201, 200, "ingest.parse", 70.0, parent=200),
              other, dict(other),
              _span(301, 300, "encode", 1.0, parent=300, root="query", arrow_native=0)]
    return {"workload": "gdelt.dashboard", "spans": spans, "device": None,
            "client": {"query_ms": [120.0] * 9, "between_s": []}}


def test_the_median_is_over_the_arrow_answers_alone():
    assert abs(encode_arrow_ms.read(_view()) - 5.5) < 1e-9
    assert abs(encode_ms.read(_view()) - 4.0) < 1e-9  # pooled, as before: the fifth of nine


def test_the_share_is_over_the_spans_that_say_which_route():
    assert abs(arrow_native_pct.read(_view()) - 75.0) < 1e-9


def test_a_parent_like_view_reads_the_median_and_no_share():
    bare = _view(counted=False)
    assert arrow_native_pct.read(bare) is None
    assert abs(encode_arrow_ms.read(bare) - 5.5) < 1e-9


def test_none_where_no_root_says_its_format():
    view = _view()
    for s in view["spans"]:
        s["attrs"].pop("fmt", None)
    assert encode_arrow_ms.read(view) is None and arrow_native_pct.read(view) is None
    empty = {"workload": "gdelt.dashboard", "spans": [], "device": None,
             "client": {"query_ms": [], "between_s": []}}
    assert encode_arrow_ms.read(empty) is None and arrow_native_pct.read(empty) is None


def test_encode_native_pct_reads_the_geojson_answers_alone():
    """``arrow_native`` is not called ``native``: the Arrow spans are no
    sample of PR 38's share, counted or not."""
    assert abs(encode_native_pct.read(_view()) - 80.0) < 1e-9
    assert abs(encode_native_pct.read(_view(counted=False)) - 80.0) < 1e-9


def test_they_are_metrics_of_the_served_cells():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    served = ["gdelt.dashboard", "gdelt.ingest-reads"]
    assert entries["encode_arrow_ms"] == {
        "name": "encode_arrow_ms", "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "entry points", "moves": "query_p95_ms", "workloads": served}
    assert entries["arrow_native_pct"] == {
        "name": "arrow_native_pct", "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "entry points", "moves": "queries_per_s", "workloads": served}
    assert [m["name"] for m in bench["per_layer"][-2:]] == ["encode_arrow_ms", "arrow_native_pct"]
    assert entries["encode_ms"]["workloads"] == served
