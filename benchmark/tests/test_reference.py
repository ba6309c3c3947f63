"""The plain reference against the store at 2^14 rows, through the ops'
own ``embedded`` calls; and the comparison against crafted faults."""

import importlib
import json
import os

import numpy as np
import pytest

from datagen import gdelt
from harness import check, controls
from harness import reference as ref
from harness import requests as rq
from harness.data import sub_rng

N = 1 << 14
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    return json.load(open(os.path.join(HERE, "configs", "gdelt-events-1chip.json")))


@pytest.fixture(scope="module")
def world():
    from stores import datastore

    cols = gdelt.make(_config(), N, 77)
    # rows half an f32 ulp outside the probe box's west and south edges:
    # exact f64 semantics leave them out, the f32-widened mask takes them
    box = [10.0, 20.0, 12.0, 21.0]
    ulp_x = float(np.spacing(np.float32(10.0)))
    ulp_y = float(np.spacing(np.float32(20.0)))
    cols.x[100:110] = 10.0 - ulp_x / 2
    cols.y[100:110] = 20.5
    cols.x[200:210] = 11.0
    cols.y[200:210] = 20.0 - ulp_y / 2
    cols.x[300:310] = 11.5  # and ten rows well inside it
    cols.y[300:310] = 20.5
    store = datastore.build(_config(), cols, "/nonexistent")
    yield cols, store, box
    store.close()


def _requests(cols):
    role = json.load(open(os.path.join(HERE, "traffic", "analyst-notebook.json")))["roles"][0]
    gen = importlib.import_module("generators.notebook")
    return gen.generate(role["params"], sub_rng(77, 100), 80,
                        cols.context() | {"seed": 77, "client_index": 0})


def _ask(store, req):
    return rq.op_of(req).embedded(store, req)


def _compare(cols, req, answer) -> dict:
    tally = check.new_tally()
    rq.op_of(req).compare(tally, cols, req, answer)
    return tally


def test_every_operation_equals_the_reference(world):
    cols, store, _ = world
    tally = check.new_tally()
    classes = set()
    for req in _requests(cols):
        rq.op_of(req).compare(tally, cols, req, _ask(store, req))
        classes.add(req["klass"])
    assert classes == {"z3", "z2", "pip", "raster", "count", "density", "query_many"}
    assert tally["rows_compared"] > 500 and tally["witnesses"] > 20
    assert all(tally[k] == 0 for k in check.LIMITS), tally


def test_during_is_lo_inclusive_hi_exclusive(world):
    cols, store, _ = world
    lo, hi = int(cols.t[5000]), int(cols.t[6000])
    req = {"op": "query", "klass": "z3", "box": [-180.0, -90.0, 180.0, 90.0], "win": [lo, hi]}
    want = ref.ref_ids(cols, req["box"], req["win"])
    assert 5000 in want and 6000 not in want
    assert np.array_equal(np.sort(_ask(store, req)["ids"]), want)


def test_the_loose_hint_fails_the_comparison(world):
    cols, store, box = world
    req = {"op": "query", "klass": "z2", "box": box}
    assert _compare(cols, req, _ask(store, req))["wrong_answers"] == 0
    undo = controls.arm("loose")
    try:
        got = _ask(store, req)
    finally:
        undo()
    assert _compare(cols, req, got)["wrong_answers"] == 1
    extra = np.setdiff1d(got["ids"], ref.ref_ids(cols, box))
    assert len(extra) == 20 and set(extra) <= set(range(100, 110)) | set(range(200, 210))


def test_a_dropped_or_doubled_row_fails(world):
    cols, _, box = world
    want = ref.ref_ids(cols, box)
    req = {"op": "query", "klass": "z2", "box": box}
    for got, key in ((want[:-1], "wrong_answers"), (np.append(want, want[0]), "doubled_rows")):
        tally = _compare(cols, req, rq.rows_answer(got, cols.row(int(got[-1]))))
        assert tally[key] == 1 and tally["wrong_attributes"] == 0
        assert not check.verdict(tally, lambda *a, **k: None)


@pytest.mark.parametrize("fmt", ["embedded", "geojson", "arrow"])
def test_a_witness_row_is_held_to_every_attribute(world, fmt):
    """The three wires bring the same row; one attribute off fails."""
    from geomesa_tpu.io.arrow import to_arrow_table
    from geomesa_tpu.io.exporters import _geojson

    cols, store, box = world
    req = {"op": "query", "klass": "z2", "box": box, "fmt": fmt}
    fc = store.ds.query(store.type_name, rq.ecql(req))
    if fmt == "embedded":
        got = rq.collection_answer(fc)
    elif fmt == "geojson":
        got = rq.geojson_answer(_geojson(fc).encode())
    else:
        import io

        import pyarrow.ipc as ipc

        table, sink = to_arrow_table(fc), io.BytesIO()
        with ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        got = rq.arrow_answer(sink.getvalue())
    assert len(got["ids"]) == 10 and got["witness"] is not None
    assert _compare(cols, req, got)["wrong_attributes"] == 0
    got["witness"]["row"]["numMentions"] += 1
    tally = _compare(cols, req, got)
    assert tally["wrong_attributes"] == 1 and tally["wrong_answers"] == 0
    assert not check.verdict(tally, lambda *a, **k: None)
