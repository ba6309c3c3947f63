import collections
import importlib
import json
import os

import numpy as np
import pytest

from datagen import gdelt
from harness import requests as rq
from harness.data import balanced, sub_rng

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG = 2_500_000_011  # past 2**31: the driver's seeds are large


def _traffic(name):
    return json.load(open(os.path.join(HERE, "traffic", name + ".json")))


def _config():
    return json.load(open(os.path.join(HERE, "configs", "gdelt-events-1chip.json")))


def _gctx(cols, k=0):
    return cols.context() | {"seed": BIG, "client_index": k}


@pytest.fixture(scope="module")
def cols():
    return gdelt.make(_config(), 4096, BIG)


def test_columns_from_a_seed(cols):
    again = gdelt.make(_config(), 4096, BIG)
    other = gdelt.make(_config(), 4096, BIG + 1)
    for a, b in ((cols.x, again.x), (cols.y, again.y), (cols.t, again.t)):
        assert np.array_equal(a, b)
    assert all(np.array_equal(cols.attrs[k], again.attrs[k]) for k in cols.attrs)
    assert not np.array_equal(cols.x, other.x)
    assert not np.array_equal(cols.attrs["actor1Name"], other.attrs["actor1Name"])
    assert np.all(np.diff(cols.t) >= 0), "ids are in arrival order"
    assert cols.t[0] >= 1704067200000 and cols.t[-1] < 1704067200000 + cols.span_ms
    assert np.all(np.abs(cols.x) <= 180) and np.all(np.abs(cols.y) <= 90)


def test_columns_carry_every_attribute_of_the_schema(cols):
    schema, dtg, geom = gdelt.parse_schema(_config()["schema"])
    assert len(schema) == 27 and (dtg, geom) == ("dtg", "geom")
    assert set(cols.attrs) == {a for a, _ in schema} - {"dtg", "geom"}
    kinds = dict(schema)
    for name, col in cols.attrs.items():
        want = {"String": "U", "Integer": "i", "Double": "f"}[kinds[name]]
        assert col.dtype.kind == want and len(col) == len(cols), name
    assert cols.attrs["globalEventId"][7] == "1000000007"
    assert max(len(v) for v in cols.attrs["actor1Name"]) <= 24
    row = cols.row(7)
    assert set(row) == {a for a, _ in schema} and row["geom"] == [cols.x[7], cols.y[7]]
    with pytest.raises(KeyError):
        gdelt.make(dict(_config(), schema="colour:String,dtg:Date,*geom:Point:srid=4326"), 8, 1)


def test_balanced_deals_every_choice_equally():
    got = collections.Counter(balanced(sub_rng(1, 2), [6, 24, 72, 168], 1000).tolist())
    assert set(got.values()) == {250}


@pytest.mark.parametrize("mix", ["map-viewports", "analyst-notebook"])
def test_each_generator_is_a_function_of_the_seed(mix, cols):
    for role in _traffic(mix)["roles"]:
        gen = importlib.import_module("generators." + role["generator"])
        a = gen.generate(role["params"], sub_rng(BIG, 100), 200, _gctx(cols))
        b = gen.generate(role["params"], sub_rng(BIG, 100), 200, _gctx(cols))
        c = gen.generate(role["params"], sub_rng(BIG + 1, 100), 200, _gctx(cols))
        assert json.dumps(a) == json.dumps(b)
        assert json.dumps(a) != json.dumps(c)
        assert len(a) == 200


def test_viewports_ask_for_the_same_sizes_under_every_seed(cols):
    role = _traffic("map-viewports")["roles"][0]
    gen = importlib.import_module("generators.viewports")
    shapes = []
    for seed in (1, 2):
        reqs = gen.generate(role["params"], sub_rng(seed, 100), 400, _gctx(cols))
        shapes.append(sorted((round(r["box"][2] - r["box"][0], 6), r["win"][1] - r["win"][0],
                              r["fmt"]) for r in reqs))
        assert sum(r["fmt"] == "arrow" for r in reqs) == 80
        assert all(r["win"][0] % 1000 == 0 for r in reqs)
    assert [s[0] for s in shapes[0]] == [s[0] for s in shapes[1]]


def test_notebook_rounds_hold_the_mix(cols):
    role = _traffic("analyst-notebook")["roles"][0]
    gen = importlib.import_module("generators.notebook")
    reqs = gen.generate(role["params"], sub_rng(5, 100), 120, _gctx(cols))
    per_round = role["params"]["round"]
    assert sum(per_round.values()) == 40
    for r in range(3):
        got = collections.Counter(q["klass"] for q in reqs[r * 40:(r + 1) * 40])
        assert dict(got) == per_round
    many = next(q for q in reqs if q["op"] == "query_many")
    assert len(many["members"]) == 32
    pip = next(q for q in reqs if q["klass"] == "pip")
    assert len(pip["ring"]) == 6 and "POLYGON" in rq.ecql(pip)


def test_every_warm_pass_of_the_notebook_names_a_generator_and_slabs_cross_boxes_and_days(cols):
    mix = _traffic("analyst-notebook")
    for w in mix["warm"]:
        importlib.import_module("generators." + w.get("generator", mix["roles"][0]["generator"]))
    slabs = next(w for w in mix["warm"] if w.get("generator") == "slabs")
    p = slabs["params"]
    ctx = _gctx(cols)
    reqs = rq.generate(slabs, (BIG, 52), 0, ctx)
    assert json.dumps(reqs) == json.dumps(rq.generate(slabs, (BIG + 1, 52), 0, ctx)), "nothing drawn"
    assert len(reqs) == len(p["classes"]) * len(p["boxes"]) * len(p["days"])
    assert {r["klass"] for r in reqs} == set(p["classes"])
    lo, hi = ctx["t0"], ctx["t0"] + ctx["span_ms"]
    assert all(lo <= r["win"][0] < r["win"][1] <= hi for r in reqs)
    # the rungs that reach the two upper buckets of 128 blocks: 4-7 and 10-16 days of 16
    days = sorted((r["win"][1] - r["win"][0]) / 86_400_000 for r in reqs if r["klass"] == "density")
    assert days[0] >= 4 and days[-1] == cols.span_ms / 86_400_000
