"""Vessel proximity over AIS reports (benchmark configuration
``ais-reports-1chip``, cell ``ais.vessel-proximity``; PR 46) at a small size
on the CPU:

(a) ``geomesa_tpu.process``'s ``knn_search``, ``knn_many`` and
    ``tube_select`` over a store of the ``ais`` type with z3 and z2, loaded as
    ``benchmark/stores/datastore.py`` loads it, and the benchmark's plain
    reference (``harness/reference_process.py``, which imports nothing of the
    program) agree on every class of the mix under five seeds of traffic, and
    on kNN windows wide enough that every answer is a full ``k``;
(b) an answer broken on purpose does not read ``correct``, through the ops'
    own ``compare``: a row dropped, a row doubled, the k-th and the (k+1)-th
    swapped, a neighbour's coordinates on a row, a hit just past ``buffer_m``
    let in;
(c) tube boundaries against the reference on hand-made rows: a row at
    exactly ``t_first``, at ``t_last``, one millisecond before and past; a
    track whose waypoints outnumber ``max_bins``; a track that dwells
    (repeated positions, repeated times); a buffer's edge;
(d) ``datagen/ais.py``: the classes' shares of the rows, no two reports at
    the same coordinates, a report a minute a lane vessel, every voyage
    between two dwells of its vessel, a seed gives the same columns twice;
(e) ``generators/vessel_proximity.py``: every seed's round is the same
    multiset with the issue's parameters letter for letter; the warm ladder's
    rungs;
(f) the readers over hand-made spans (a tube's query as the union of groups
    PR 47 plans, and as one scan), and None on a program without the roots;
(g) the cell itself through ``benchmark/rehearse.py`` reads ``correct`` with
    every new metric; under ``--control swap-attr`` it does not.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED = 1 << 16, 2_600_000_011
CELL = "ais.vessel-proximity"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
SEEDS = (1, 2, 3, 2_600_000_011, 3_100_000_007)
ROUND = {"knn-port": 6, "knn-sea": 2, "knn-many-16": 2, "tube-2k": 4, "tube-10k": 2}
CLASSES = tuple(ROUND)
NEW_METRICS = ("knn_plan_ms", "tube_plan_ms", "knn_rounds", "knn_scan_ms", "knn_rank_ms",
               "knn_overfetch", "tube_scan_ms", "tube_refine_ms", "tube_keep_pct",
               "process_coverage_pct", "tube_groups", "tube_bins_ms", "tube_arrays_pct")


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, store, generators, ops and reference,
    imported as the benchmark imports them (tests/test_join_cell.py's fixture)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        import importlib

        from datagen import ais
        from generators import process_ladder, vessel_proximity
        from harness import check, reference_process
        from harness import requests as rq
        from ops import knn, knn_many, tube
        from stores import datastore

        yield types.SimpleNamespace(
            ais=ais, ladder=process_ladder, mix=vessel_proximity, check=check,
            ref=reference_process, rq=rq, ops={"knn": knn, "knn_many": knn_many, "tube": tube},
            stores=datastore,
            readers={m: importlib.import_module("layer_metrics." + m) for m in NEW_METRICS})
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == "ais-reports-1chip")
    assert entry["reduced"] == ["rows", "span_days"]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "vessel-proximity.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cols(bench, config):
    return bench.ais.make(config, N, SEED)


@pytest.fixture(scope="module")
def store(bench, config, cols, tmp_path_factory):
    out = bench.stores.build(config, cols, str(tmp_path_factory.mktemp("run")))
    assert [i.name for i in out.ds._indexes[out.type_name]] == ["z3", "z2"]
    yield out
    out.close()


def _requests(bench, mix, cols, seed, n):
    role = mix["roles"][0]
    return bench.rq.generate(role, (seed, 100), n, cols.context() | {"seed": seed})


def _compared(bench, cols, store, req, answer=None):
    op = bench.ops[req["op"]]
    tally = bench.check.new_tally()
    if answer is None:
        answer = op.embedded(store, req)
    op.compare(tally, cols, req, answer)
    return tally, answer


def _sound(bench, tally):
    return all(tally[n] == 0 for n in bench.check.LIMITS)


# ---------------------------------------------------- (a) the plain reference


def test_the_configuration_is_at_the_sources_shapes(config):
    assert config["schema"] == (
        "mmsi:Integer,dtg:Date,sog:Double,cog:Double,heading:Integer,vessel_name:String,"
        "imo:String,call_sign:String,vessel_type:Integer,status:String,length:Double,"
        "width:Double,draft:Double,cargo:Integer,*geom:Point:srid=4326")
    assert len(config["schema"].split(",")) == 15
    assert config["indices"] == ["z3", "z2"] and config["z3_interval"] == "week"
    assert config["store"] == "datastore" and config["chips"] == 1
    assert (config["rows"], config["span_days"]) in ((1 << 24, 16), (1 << 23, 8))
    assert config["reduced"] == ["rows", "span_days"] == sorted(config["reduced_why"])
    assert config["data"]["vessels"] == 12_288
    assert "haversine" in config["guarantees"]["answers"]
    assert len(config["about"]["assumed"]) >= 5


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "harness", "reference_process.py")) as fh:
        imports = [ln for ln in fh.read().splitlines() if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import numpy as np"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("klass", CLASSES)
def test_a_process_answers_as_the_plain_reference(klass, seed, bench, mix, cols, store):
    reqs = [r for r in _requests(bench, mix, cols, seed, 48) if r["klass"] == klass][:3]
    assert len(reqs) == 3
    rows = 0
    for req in reqs:
        tally, answer = _compared(bench, cols, store, req)
        assert _sound(bench, tally), (klass, seed, tally)
        assert tally["witnesses"] >= (1 if bench.ops[req["op"]].size(answer) else 0)
        rows += tally["rows_compared"]
    assert rows > 0


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_a_full_k_comes_back_nearest_first(seed, bench, mix, cols, store):
    """At 2^16 rows two minutes hold a few dozen reports in all: most answers
    are short. Two hours hold enough that every member answers a full k."""
    reqs = [r for r in _requests(bench, mix, cols, seed, 32) if r["op"] in ("knn", "knn_many")]
    full = 0
    for req in reqs[:6]:
        mid = sum(req["win"]) // 2
        req = dict(req, win=[mid - 3_600_000, mid + 3_600_000])
        tally, answer = _compared(bench, cols, store, req)
        assert _sound(bench, tally)
        for one in (answer if req["op"] == "knn_many" else [answer]):
            full += len(one["ids"]) == req["k"]
    assert full >= 6


def test_an_id_is_the_generators_row(bench, mix, cols, store):
    req = next(r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == "tube-2k")
    answer = bench.ops["tube"].embedded(store, req)
    w = answer["witness"]
    assert bench.check._canonical(cols, w["row"]) == cols.row(w["id"])
    assert set(w["row"]) == {a for a, _ in cols.schema}


# ------------------------------------------------------ (b) broken on purpose


def _knn_with_spare(bench, mix, cols, store):
    """A kNN request whose window holds more than k rows within the limit,
    and its k + 1 nearest by the reference."""
    for req in _requests(bench, mix, cols, SEED, 64):
        if req["op"] != "knn":
            continue
        mid = sum(req["win"]) // 2
        req = dict(req, k=8, win=[mid - 3_600_000, mid + 3_600_000])
        ids, _ = bench.ref.knn(cols, *req["point"], 9, req["win"], req["max_distance_m"])
        if len(ids) == 9:
            return req, ids
    raise AssertionError("no kNN request with a (k+1)-th row")


KNN_FAULTS = {"dropped": "wrong_answers", "doubled": "doubled_rows", "swapped": "wrong_answers",
              "kth-for-next": "wrong_answers", "moved": "wrong_answers"}


@pytest.mark.parametrize("fault", sorted(KNN_FAULTS))
def test_a_broken_knn_answer_is_not_correct(fault, bench, mix, cols, store):
    req, nine = _knn_with_spare(bench, mix, cols, store)
    sound, answer = _compared(bench, cols, store, req)
    assert _sound(bench, sound) and list(answer["ids"]) == list(nine[:8])
    ids, x, y = (np.array(answer[k]) for k in ("ids", "x", "y"))
    if fault == "dropped":
        ids, x, y = ids[:-1], x[:-1], y[:-1]
    elif fault == "doubled":
        ids, x, y = (np.concatenate([v[:1], v]) for v in (ids, x, y))
    elif fault == "swapped":  # the right rows, the two last in the wrong order
        order = [0, 1, 2, 3, 4, 5, 7, 6]
        ids, x, y = ids[order], x[order], y[order]
    elif fault == "kth-for-next":  # the (k+1)-th in the k-th's place
        ids[-1], x[-1], y[-1] = nine[8], cols.x[nine[8]], cols.y[nine[8]]
    else:  # the right ids, one with its neighbour's coordinates
        x[3], y[3] = x[4], y[4]
    tally, _ = _compared(bench, cols, store, req, dict(answer, ids=ids, x=x, y=y))
    assert tally[KNN_FAULTS[fault]] > 0


TUBE_FAULTS = {"dropped": "wrong_answers", "doubled": "doubled_rows",
               "just-past-the-buffer": "wrong_answers", "attribute": "wrong_attributes"}


@pytest.mark.parametrize("fault", sorted(TUBE_FAULTS))
def test_a_broken_tube_answer_is_not_correct(fault, bench, mix, cols, store):
    req = next(r for r in _requests(bench, mix, cols, SEED, 64) if r["klass"] == "tube-10k")
    sound, answer = _compared(bench, cols, store, req)
    assert _sound(bench, sound) and len(answer["ids"]) > 100
    ids = np.array(answer["ids"])
    broken = dict(answer)
    if fault == "dropped":
        broken["ids"] = ids[:-1]
    elif fault == "doubled":
        broken["ids"] = np.concatenate([ids[:1], ids])
    elif fault == "attribute":
        row = dict(answer["witness"]["row"], mmsi=answer["witness"]["row"]["mmsi"] + 1)
        broken["witness"] = dict(answer["witness"], row=row)
    else:  # the nearest row outside the corridor, inside the track's time, let in
        ts, xy = req["track_t"], req["track_xy"]
        rows = np.flatnonzero((cols.t >= ts[0]) & (cols.t <= ts[-1]))
        rows = np.setdiff1d(rows, ids)
        at = cols.t[rows].astype(np.float64)
        d = bench.ref.haversine_m(cols.x[rows], cols.y[rows],
                                  np.interp(at, ts.astype(np.float64), xy[:, 0]),
                                  np.interp(at, ts.astype(np.float64), xy[:, 1]))
        assert d.min() > req["buffer_m"]
        broken["ids"] = np.concatenate([ids, rows[[int(np.argmin(d))]]])
    tally, _ = _compared(bench, cols, store, req, broken)
    assert tally[TUBE_FAULTS[fault]] > 0


# --------------------------------------------------------- (c) tube boundaries


T0 = 1_496_275_200_000


def _edge_store(x, y, t):
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    sft = FeatureType.from_spec("edge", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    ds = DataStore()
    ds.create_schema(sft)
    n = len(x)
    ds.write("edge", FeatureCollection.from_columns(
        sft, np.arange(n, dtype=np.int64),
        {"dtg": np.asarray(t, np.int64), "geom": (np.array(x, float), np.array(y, float))}),
        check_ids=False)
    return ds


def _both(bench, x, y, t, track_xy, track_t, buffer_m, **kw):
    """(the program's ids, the reference's) over hand-made rows."""
    from geomesa_tpu.process import tube_select

    rows = types.SimpleNamespace(x=np.array(x, float), y=np.array(y, float),
                                 t=np.asarray(t, np.int64))
    got = tube_select(_edge_store(x, y, t), "edge", track_xy, track_t, buffer_m, **kw)
    return (np.sort(np.asarray(got.ids).astype(np.int64)),
            bench.ref.tube(rows, track_xy, track_t, buffer_m))


def _line(n, span_ms):
    f = np.linspace(0.0, 1.0, n)
    return (np.stack([-123.0 + 0.5 * f, 37.0 + 0.3 * f], 1),
            T0 + (f * span_ms).astype(np.int64))


@pytest.mark.parametrize("span_ms", [256 * 60_000, 256 * 60_000 + 7, 359 * 60_000, 1_000])
def test_the_tracks_first_and_last_instants_are_inside(span_ms, bench):
    xy, ts = _line(64, span_ms)
    # on the track's ends at t_first, t_last, and a millisecond outside both
    x = [xy[0, 0], xy[0, 0], xy[-1, 0], xy[-1, 0], xy[32, 0]]
    y = [xy[0, 1], xy[0, 1], xy[-1, 1], xy[-1, 1], xy[32, 1]]
    t = [ts[0], ts[0] - 1, ts[-1], ts[-1] + 1, ts[32]]
    got, want = _both(bench, x, y, t, xy, ts, 100.0)
    assert list(want) == [0, 2, 4] and list(got) == list(want)


def test_more_waypoints_than_bins_lose_no_waypoint(bench):
    """1,000 waypoints in 16 bins on a track that zigzags: a row beside any
    waypoint is found, whichever bin's box has to hold it."""
    n = 1000
    f = np.linspace(0.0, 1.0, n)
    xy = np.stack([-123.0 + 0.5 * f, 37.0 + 0.05 * np.sin(40 * f)], 1)
    ts = T0 + (f * 6 * 3_600_000).astype(np.int64)
    x, y, t = xy[:, 0] + 1e-4, xy[:, 1] - 1e-4, ts
    got, want = _both(bench, x, y, t, xy, ts, 50.0, max_bins=16)
    assert len(want) == n and np.array_equal(got, want)
    got, want = _both(bench, x, y, t, xy, ts, 50.0)  # the default: 256 bins
    assert len(want) == n and np.array_equal(got, want)


def test_a_track_that_dwells(bench):
    """Repeated positions (a vessel at its berth) and a repeated time (two
    fixes in one second): the corridor stands still, and the position AT a
    repeated time is the last waypoint of that time."""
    xy = np.array([[-122.4, 37.8]] * 5 + [[-122.3, 37.85], [-122.2, 37.9]], float)
    ts = T0 + np.array([0, 60, 120, 120, 180, 240, 300], np.int64) * 1000
    rng = np.random.default_rng(5)
    n = 400
    x = rng.normal(-122.4, 0.004, n)
    y = rng.normal(37.8, 0.004, n)
    t = T0 + rng.integers(-30, 330, n) * 1000
    got, want = _both(bench, x, y, t, xy, ts, 300.0)
    assert 20 < len(want) < n and np.array_equal(got, want)


def test_a_buffers_edge(bench):
    """Rows a metre inside and a metre outside ``buffer_m`` of a point of
    the track, by the reference's own haversine."""
    xy, ts = _line(10, 9 * 60_000)
    per_m = 1.0 / 111_194.92664455873  # degrees of latitude a metre on R = 6,371,000 m
    x = [xy[4, 0]] * 2
    y = [xy[4, 1] + 499.0 * per_m, xy[4, 1] + 501.0 * per_m]
    got, want = _both(bench, x, y, [ts[4]] * 2, xy, ts, 500.0)
    assert list(want) == [0] and list(got) == [0]


def _due_east(x, y, metres):
    """The point ``metres`` due east (bearing 90) of (x, y) on the sphere."""
    d, p1 = metres / 6_371_000.0, np.radians(y)
    p2 = np.arcsin(np.sin(p1) * np.cos(d))
    l2 = np.radians(x) + np.arctan2(np.sin(d) * np.cos(p1), np.cos(d) - np.sin(p1) * np.sin(p2))
    return float(np.degrees(l2)), float(np.degrees(p2))


@pytest.mark.parametrize("buffer_m", [2_000.0, 10_000.0])
def test_a_row_at_the_corridors_east_rim_is_inside(buffer_m, bench):
    """PR 46's repair: a slice's box was sized at 111,320 m a degree, and a
    degree of this sphere is 111,195: rows in the last 0.1% of the buffer
    due east or west of the track fell outside every box."""
    xy = np.array([[-123.7, 39.2 + 0.001 * i] for i in range(30)])
    ts = T0 + np.arange(30, dtype=np.int64) * 60_000
    inside = _due_east(xy[10, 0], xy[10, 1], buffer_m * 0.9998)
    outside = _due_east(xy[10, 0], xy[10, 1], buffer_m * 1.0002)
    west = (2 * xy[20, 0] - _due_east(xy[20, 0], xy[20, 1], buffer_m * 0.9998)[0],
            _due_east(xy[20, 0], xy[20, 1], buffer_m * 0.9998)[1])
    got, want = _both(bench, [inside[0], outside[0], west[0]], [inside[1], outside[1], west[1]],
                      [ts[10], ts[10], ts[20]], xy, ts, buffer_m)
    assert list(want) == [0, 2] and list(got) == [0, 2]


def test_a_row_at_the_searchs_east_rim_is_a_neighbour(bench):
    """The same box sizes a kNN window: at ``max_distance_m`` what lies
    inside the circle is the answer, its east rim too."""
    from geomesa_tpu.process import knn_search

    x, y, limit = -124.5, 41.0, 100_000.0
    px, py = zip(_due_east(x, y, 50_000.0), _due_east(x, y, limit * 0.9998),
                 _due_east(x, y, limit * 1.0002))
    t = [T0, T0, T0]
    got = knn_search(_edge_store(px, py, t), "edge", x, y, 5, estimated_distance_m=1_000.0,
                     max_distance_m=limit)
    rows = types.SimpleNamespace(x=np.array(px), y=np.array(py), t=np.array(t, np.int64))
    want, _ = bench.ref.knn(rows, x, y, 5, [T0 - 1, T0 + 1], limit)
    assert list(want) == [0, 1] and list(np.asarray(got.ids).astype(np.int64)) == [0, 1]


# ------------------------------------------------------------- (d) the data set


@pytest.fixture(scope="module")
def million(bench, config):
    return bench.ais.make(config, 1 << 20, 7)


def test_the_rows_lie_in_their_classes_shares(bench, million):
    n = len(million)
    share = np.bincount(million.kind, minlength=4) / n
    names = bench.ais.CLASSES
    assert names == ("moored", "way", "coastal", "junk")
    assert round(share[2] * n) == round(0.095 * n) and round(share[3] * n) == round(0.005 * n)
    assert abs(share[0] - 0.55) < 0.03 and abs(share[1] - 0.35) < 0.03
    junk = million.kind == names.index("junk")
    x0, y0, x1, y1 = bench.ais.REGION
    inside = (million.x > x0) & (million.x < x1) & (million.y > y0) & (million.y < y1)
    assert inside[~junk].all() and not inside[junk].all()
    assert set(np.unique(million.attrs["status"][million.kind == 0])) == {"moored", "at anchor"}


def test_no_two_reports_share_their_coordinates(million):
    xy = np.stack([million.x, million.y], 1)
    assert len(np.unique(xy, axis=0)) == len(xy)


def test_a_lane_vessel_reports_every_minute(bench, config, million):
    lane = np.flatnonzero(million.kind <= 1)
    v = million.vessel[lane]
    same = v[1:] == v[:-1]
    gaps = np.diff(million.t[lane])[same]
    assert gaps.min() >= 49_000 and gaps.max() <= 71_000 and (million.t % 1000 == 0).all()
    assert abs(np.median(gaps) - 60_000) <= 1_000
    assert million.t.min() >= million.t0 and million.t.max() < million.t0 + million.span_ms
    assert million.span_ms == million.minutes * 60_000 == 2 * 86_400_000  # the density's floor
    assert million.vessel.max() + 1 == 12_288 * len(million) // config["rows"]


def test_a_voyage_lies_between_two_dwells(bench, million):
    v = million.voyages
    assert len(v["row"]) > 50
    moored, way = 0, 1
    for row, vessel, left in zip(v["row"], v["vessel"], v["rows_left"]):
        assert million.kind[row] == way and million.kind[row - 1] == moored
        assert million.vessel[row - 1] == vessel == million.vessel[row + left - 1]
        run = million.kind[row:row + left]
        end = int(np.argmax(run != way)) if (run != way).any() else len(run)
        if end < len(run):  # it arrived inside the span: a dwell follows
            assert run[end] == moored
        # it leaves from where it lay: the first fix under way a few minutes' sail off
        assert bench.ref.haversine_m(million.x[row - 1], million.y[row - 1],
                                     million.x[row], million.y[row]) < 1_500


def test_a_seed_gives_the_same_columns_twice(bench, config, cols):
    again = bench.ais.make(config, N, SEED)
    assert np.array_equal(again.x, cols.x) and np.array_equal(again.t, cols.t)
    assert all(np.array_equal(again.attrs[a], cols.attrs[a]) for a in cols.attrs)
    other = bench.ais.make(config, N, SEED + 1)
    assert not np.array_equal(other.x, cols.x)
    row = cols.row(0)
    assert list(row) == ["dtg", "geom"] + [a for a in cols.attrs]
    assert isinstance(row["mmsi"], int) and isinstance(row["vessel_name"], str)


# ------------------------------------------------------------- (e) the requests


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_is_the_same_multiset(seed, bench, mix, cols):
    role = mix["roles"][0]
    p = role["params"]
    assert p["round"] == ROUND and sum(ROUND.values()) == 16
    assert mix["client"] == "embedded" and role["clients"] == 1
    assert role["requests_per_client"] >= 2_000
    assert (p["k"], p["window_s"], p["max_distance_m"]) == (32, 60, 100_000.0)
    assert p["classes"]["knn-port"] == {"kind": "knn-port", "offset_sigmas": 0.5}
    assert p["classes"]["knn-sea"] == {"kind": "knn-sea", "min_port_km": 40.0}
    assert p["classes"]["knn-many-16"] == {"kind": "knn-many", "points": 16, "k": 8}
    assert p["classes"]["tube-2k"] == {"kind": "tube", "hours": 6, "every": 1,
                                       "buffer_m": 2_000.0}
    assert p["classes"]["tube-10k"] == {"kind": "tube", "hours": 24, "every": 5,
                                        "buffer_m": 10_000.0}
    assert mix["check"]["max_per_class"] == {k: 2 for k in CLASSES}
    reqs = _requests(bench, mix, cols, seed, 64)
    orders = []
    for r in range(4):
        one = reqs[r * 16:(r + 1) * 16]
        assert sorted(q["klass"] for q in one) == sorted(k for k, c in ROUND.items()
                                                         for _ in range(c))
        orders.append(tuple(q["klass"] for q in one))
    assert len(set(orders)) > 1  # dealt anew every round
    ctx = cols.context()
    px, py = np.array(ctx["ports"]["x"]), np.array(ctx["ports"]["y"])
    for q in reqs:
        if q["op"] == "tube":
            n = len(q["track_t"])
            assert q["track_xy"].shape == (n, 2) and (np.diff(q["track_t"]) >= 0).all()
            assert 2 <= n <= (360 if q["klass"] == "tube-2k" else 288)
            assert q["buffer_m"] == (2_000.0 if q["klass"] == "tube-2k" else 10_000.0)
            step = np.median(np.diff(q["track_t"]))
            assert abs(step - (60_000 if q["klass"] == "tube-2k" else 300_000)) <= 2_000
            row = int(np.flatnonzero((cols.x == q["track_xy"][0, 0])
                                     & (cols.t == q["track_t"][0]))[0])
            assert cols.kind[row] == 1 and cols.kind[row - 1] == 0  # a departure from a berth
            continue
        assert q["win"][1] - q["win"][0] == 120_000 and q["win"][0] % 1000 == 0
        assert ctx["t0"] <= q["win"][0] and q["win"][1] <= ctx["t0"] + ctx["span_ms"]
        assert q["estimated_distance_m"] is None and q["max_distance_m"] == 100_000.0
        if q["klass"] == "knn-many-16":
            assert q["op"] == "knn_many" and len(q["points"]) == 16 and q["k"] == 8
            continue
        assert q["op"] == "knn" and q["k"] == 32
        to_port = bench.ais.flat_km(q["point"][0], q["point"][1], px, py).min()
        assert to_port >= 40.0 if q["klass"] == "knn-sea" else to_port < 5.0
    again = _requests(bench, mix, cols, seed, 64)
    assert [q["klass"] for q in again] == [q["klass"] for q in reqs]
    assert all(np.array_equal(a.get("track_t", 0), b.get("track_t", 0))
               and a.get("point") == b.get("point") for a, b in zip(again, reqs))


def test_the_warm_ladder_asks_every_rung(bench, mix, cols):
    assert mix["warm"][0] == {"requests": 160}
    warm = mix["warm"][1]
    assert warm["generator"] == "process_ladder"
    reqs = bench.ladder.generate(warm["params"], np.random.default_rng(1), 0, cols.context())
    knn = [q for q in reqs if q["op"] == "knn"]
    assert sorted({q["estimated_distance_m"] for q in knn}) == [250, 1000, 4000, 16000, 25000,
                                                                100000]
    assert len(knn) == 6 * 2 * 3
    assert sorted(len(q["points"]) for q in reqs if q["op"] == "knn_many") == [2, 4, 8, 16, 32]
    tubes = [q for q in reqs if q["op"] == "tube"]
    assert sorted({q["max_bins"] for q in tubes}) == [2, 8, 32, 128, 256]
    assert sorted({q["buffer_m"] for q in tubes}) == [500, 2000, 10000, 20000]
    assert len(tubes) == 20


# -------------------------------------------------------------- (f) the readers


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def test_the_readers_read_the_processes_spans(bench):
    knn = _span(1, 1, "knn", "knn", 20.0, members=2, k=8, rounds=2, windows=3, candidates=90,
                returned=16, short=0)
    # a tube as PR 47 plans it: a union of two groups under ONE query root, whose plan,
    # one dispatch and a scan + decode a branch all lie directly under the root
    tube = _span(20, 2, "tube", "tube", 100.0, waypoints=360, bins=32, buffer_m=2000.0,
                 groups=2, boxes=32, windows=2, ranges=250, candidates=4000, rows=1000,
                 kept=400, query_trace=3, arrays=1)
    query = _span(30, 3, "query", "query", 80.0, tube_trace=2)
    other = _span(40, 4, "query", "query", 5.0)  # a query no tube asked
    # a tube of sixteen slices or fewer: one scan, ``groups`` 0
    short = _span(50, 5, "tube", "tube", 20.0, waypoints=12, bins=8, buffer_m=500.0,
                  groups=0, boxes=8, windows=1, ranges=40, candidates=100, rows=50, kept=40,
                  query_trace=6, arrays=0)
    asked = _span(60, 6, "query", "query", 15.0, tube_trace=5)
    spans = [
        knn, dict(knn),  # roots twice, as the harness lists them
        _span(2, 1, "knn", "knn.estimate", 0.5, parent=1, probes=4),
        _span(3, 1, "knn", "knn.round", 9.0, parent=1, pending=2),
        _span(4, 1, "knn", "plan", 1.0, parent=3), _span(5, 1, "knn", "plan", 1.5, parent=3),
        _span(6, 1, "knn", "dispatch", 2.0, parent=3),
        _span(7, 1, "knn", "dispatch", 1.9, parent=6),  # a lone member's own, nested
        _span(8, 1, "knn", "scan", 1.0, parent=3), _span(9, 1, "knn", "decode", 0.75, parent=3),
        _span(10, 1, "knn", "knn.rank", 0.25, parent=3),
        _span(11, 1, "knn", "knn.round", 5.0, parent=1, pending=1),
        _span(12, 1, "knn", "plan", 0.5, parent=11),
        _span(13, 1, "knn", "dispatch", 1.0, parent=11),
        _span(14, 1, "knn", "scan", 1.0, parent=11),
        _span(15, 1, "knn", "decode", 0.5, parent=11),
        _span(16, 1, "knn", "knn.rank", 0.5, parent=11),
        tube, dict(tube),
        _span(21, 2, "tube", "tube.bins", 4.0, parent=20),
        _span(22, 2, "tube", "tube.refine", 6.0, parent=20),
        query, dict(query),
        _span(31, 3, "query", "plan", 30.0, parent=30, members=1, batched=1, sliced=2),
        _span(32, 3, "query", "plan.decompose", 20.0, parent=31),
        _span(33, 3, "query", "dispatch", 2.0, parent=30, members=2),
        _span(37, 3, "query", "dispatch", 1.5, parent=33),  # a branch alone on its index, nested
        _span(34, 3, "query", "scan", 2.0, parent=30, member=0),
        _span(36, 3, "query", "scan", 1.0, parent=30, member=1),
        _span(35, 3, "query", "decode", 25.0, parent=30, candidates=2500, member=0),
        _span(38, 3, "query", "decode", 15.0, parent=30, candidates=1500, member=1),
        other, dict(other), _span(41, 4, "query", "plan", 99.0, parent=40),
    ]
    view = {"spans": spans, "client": {"query_ms": [21.0, 104.0, 25.0]}}
    r = bench.readers
    assert r["knn_plan_ms"].read(view) == pytest.approx(3.0)
    assert r["knn_scan_ms"].read(view) == pytest.approx(3.0 + 2.0)  # the nested one left out
    assert r["knn_rank_ms"].read(view) == pytest.approx(2.0)
    assert r["knn_rounds"].read(view) == pytest.approx(1.5)
    assert r["knn_overfetch"].read(view) == pytest.approx(90 / 16)
    assert r["tube_plan_ms"].read(view) == pytest.approx(30.0)  # whole, its child inside it
    assert r["tube_scan_ms"].read(view) == pytest.approx(5.0)  # the one dispatch + both scans
    assert r["tube_refine_ms"].read(view) == pytest.approx(46.0)  # both decodes + tube.refine
    assert r["tube_keep_pct"].read(view) == pytest.approx(10.0)
    assert r["tube_groups"].read(view) == pytest.approx(2.0)
    # PR 48: the root's ``arrays`` (its plan counted ``slice_rows``) and its ``tube.bins``
    assert r["tube_arrays_pct"].read(view) == pytest.approx(100.0)
    assert r["tube_bins_ms"].read(view) == pytest.approx(4.0)
    assert r["process_coverage_pct"].read(view) == pytest.approx(100 * 120.0 / 150.0)
    # beside a tube that stayed one scan: medians over the two roots, groups pooled
    both = {"spans": spans + [
        short, dict(short), _span(51, 5, "tube", "tube.refine", 1.0, parent=50),
        _span(52, 5, "tube", "tube.bins", 1.0, parent=50),
        asked, dict(asked), _span(61, 6, "query", "plan", 4.0, parent=60, sliced=0),
        _span(62, 6, "query", "dispatch", 1.0, parent=60),
        _span(63, 6, "query", "scan", 2.0, parent=60),
        _span(64, 6, "query", "decode", 3.0, parent=60, candidates=100)],
        "client": view["client"]}
    assert r["tube_groups"].read(both) == pytest.approx(1.0)
    assert r["tube_arrays_pct"].read(both) == pytest.approx(50.0)  # one slice: And(BBox, During)
    assert r["tube_bins_ms"].read(both) == pytest.approx((4.0 + 1.0) / 2)
    assert r["tube_plan_ms"].read(both) == pytest.approx((30.0 + 4.0) / 2)
    assert r["tube_scan_ms"].read(both) == pytest.approx((5.0 + 3.0) / 2)
    assert r["tube_refine_ms"].read(both) == pytest.approx((46.0 + 4.0) / 2)
    assert r["tube_keep_pct"].read(both) == pytest.approx(100 * 440 / 4100)
    # a program without the two roots (PR 46's parent): the tube's query is a plain query root
    parent = {"spans": [dict(s, attrs={}) for s in spans if s["root"] == "query"],
              "client": view["client"]}
    for name, reader in r.items():
        assert reader.read(parent) is None, name
    # a tube root whose query was sampled out carries no candidates: no share, no failure;
    # nor ``groups`` (the explainer held no plan): nothing to read, as on PR 47's parent
    bare = {"spans": [dict(tube, attrs={"rows": 5, "kept": 1})], "client": view["client"]}
    assert r["tube_keep_pct"].read(bare) is None
    assert r["tube_groups"].read(bare) is None
    assert r["tube_arrays_pct"].read(bare) is None  # as on PR 48's parent: no ``arrays``
    assert r["tube_plan_ms"].read(bare) == 0.0
    assert r["tube_bins_ms"].read(bare) == 0.0


# ---------------------------------------------------------------- (g) the cell


def _rehearse(*more):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL,
         "--rows", str(N), "--seconds", "3", *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return out, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu():
    out, line = _rehearse("--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    read = line["rehearsal_metrics"]
    assert set(NEW_METRICS) | {"query_p50_ms", "client_gap_ms", "load_rows_per_s"} <= set(read)
    assert 1.0 <= read["knn_rounds"]["value"] < 3.0
    assert read["knn_overfetch"]["value"] >= 1.0
    assert 0 < read["tube_keep_pct"]["value"] <= 100
    assert 2 <= read["tube_groups"]["value"] <= 16  # 256 slices a track, fewer for a short one
    assert read["tube_arrays_pct"]["value"] == 100.0 and read["tube_bins_ms"]["value"] > 0
    assert 90 < read["process_coverage_pct"]["value"] <= 100
    window = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "window"' in s)
    assert window["compile_requests_in_window"] == 0
    latency = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "latency"' in s)
    assert set(latency["by_class"]) == set(CLASSES)


def test_a_row_with_anothers_attribute_is_not_correct():
    out, line = _rehearse("--trace", "0", "--control", "swap-attr")
    assert out.returncode == 1 and line["correct"] is False
    compared = {json.loads(s)["number"]: json.loads(s)["value"]
                for s in out.stdout.splitlines() if '"phase": "compared"' in s}
    assert compared["wrong_attributes"] > 0
