"""The OSM building-footprints deployment (benchmark configuration
``osm-buildings-1chip``, cell ``osm-buildings.intersects``) at a small size
on the CPU:

(a) a store of the ``osm-buildings`` type with an XZ2 index alone, loaded
    as ``benchmark/stores/datastore_extents.py`` loads it, and the
    benchmark's plain reference (``harness/reference_extents.py``) agree on
    every class of the mix under five seeds, witness vertices included;
(b) that reference and the program's ``geo.intersects`` agree pair by pair
    on 500 seeded footprint / ring pairs (boxes, rings that cross, rings
    inside a footprint, footprints inside a ring);
(c) the refinement's tiers on the ``decode`` span (PR 39; docs/
    observability.md): ``refine_rect + refine_accept + refine_exact`` =
    ``candidates``, ``refine_hits`` = the rows answered, no footprint
    passes ``box_info`` (the rectangle shortcut decides none of them), the
    XZ2 plan's ``plan.decompose`` carries ``index`` and ``ranges``, an
    untraced query is the traced one, and the three readers read them;
(d) ``datagen/osm_buildings.py``: a seed gives the same columns twice,
    every ring is closed, simple and counter-clockwise, the vertex counts
    come in the stated shares, every town's centre holds the stated density;
(e) ``generators/footprint_queries.py``: every seed's round is the same
    multiset, no request has a window; ``extent_ladder`` grows by under the
    square root of two;
(f) the cell itself through ``benchmark/rehearse.py`` reads ``correct``;
    ``drop-row`` and ``swap-attr`` do not; ``loose`` does, because an
    extent mask never decides the filter (``loose_ok`` needs one that does).
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import geometry as geo
from geomesa_tpu import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED = 1 << 16, 2_600_000_011
CELL = "osm-buildings.intersects"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
SEEDS = (1, 2, 3, 2_600_000_011, 3_100_000_007)
CLASSES = ("view-0.005", "view-0.01", "view-0.02", "view-0.04", "poly-24", "poly-96")
TIERS = ("refine_rect", "refine_accept", "refine_exact")


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, store, generators, op and reference,
    imported as the benchmark imports them (tests/test_heatmap_cell.py's
    fixture)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import osm_buildings
        from generators import extent_ladder, footprint_queries
        from harness import check, controls, reference_extents
        from harness import requests as rq
        from layer_metrics import extent_hit_pct, refine_exact_pct, refine_exact_us
        from ops import query_extents
        from stores import datastore_extents

        yield types.SimpleNamespace(
            osm_buildings=osm_buildings, extent_ladder=extent_ladder,
            footprint_queries=footprint_queries, check=check, controls=controls,
            ref=reference_extents, rq=rq, op=query_extents, stores=datastore_extents,
            readers={"refine_exact_pct": refine_exact_pct, "refine_exact_us": refine_exact_us,
                     "extent_hit_pct": extent_hit_pct})
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == "osm-buildings-1chip")
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "footprint-intersects.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cols(bench, config):
    return bench.osm_buildings.make(config, N, SEED)


@pytest.fixture(scope="module")
def store(bench, config, cols, tmp_path_factory):
    out = bench.stores.build(config, cols, str(tmp_path_factory.mktemp("run")))
    assert [i.name for i in out.ds._indexes[out.type_name]] == ["xz2"]
    yield out
    out.close()


def _requests(bench, mix, cols, seed, n):
    role = mix["roles"][0]
    return bench.rq.generate(role, (seed, 100), n, cols.context() | {"seed": seed})


def _compared(bench, cols, store, req):
    tally = bench.check.new_tally()
    answer = bench.op.embedded(store, req)
    bench.op.compare(tally, cols, req, answer)
    return tally, answer


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    from geomesa_tpu import conf

    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _spans(tracer, name):
    tr = tracer.traces()[-1]
    return [s for s in [tr.root] + list(tr.spans) if s.name == name]


# ---------------------------------------------------- (a) the plain reference


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("klass", CLASSES)
def test_the_xz2_store_answers_as_the_plain_reference(klass, seed, bench, mix, cols, store):
    reqs = [r for r in _requests(bench, mix, cols, seed, 64) if r["klass"] == klass][:3]
    assert len(reqs) == 3 and all(r["op"] == "query_extents" for r in reqs)
    rows = 0
    for req in reqs:
        tally, answer = _compared(bench, cols, store, req)
        assert all(tally[k] == 0 for k in bench.check.LIMITS), (tally, req)
        assert tally["witnesses"] == (1 if len(answer["ids"]) else 0)
        rows += tally["rows_compared"]
    assert rows > 0


def test_the_witness_row_carries_the_rows_own_tags_and_vertices(bench, cols, store):
    x, y = float(cols.cx[0]), float(cols.cy[0])
    req = bench.footprint_queries.view_request("view", x, y, 0.02, 0.01)
    answer = bench.op.embedded(store, req)
    w = answer["witness"]
    assert len(answer["ids"]) > 100 and w["row"] == cols.row(w["id"])
    ring = w["row"]["geom"]
    assert ring[0] == ring[-1] and len(ring) == int(cols.vertices[w["id"]])
    assert set(w["row"]) == {"osm_id", "building", "name", "levels", "height", "dtg", "geom"}
    # a vertex moved by one f64 step is not the row's own
    moved = dict(w["row"], geom=[[float(np.nextafter(ring[0][0], 99.0)), ring[0][1]]] + ring[1:])
    tally = bench.check.new_tally()
    bench.op.compare(tally, cols, req, dict(answer, witness={"id": w["id"], "row": moved}))
    assert tally["wrong_attributes"] == 1 and tally["wrong_answers"] == 0


# ------------------------------------- (b) the reference against geo.intersects


def _pair_rings(rng, kind, foot):
    """A query ring near one footprint: ``kind`` says how near."""
    x0, y0, x1, y1 = foot[:, 0].min(), foot[:, 1].min(), foot[:, 0].max(), foot[:, 1].max()
    cx, cy, w, h = (x0 + x1) / 2, (y0 + y1) / 2, x1 - x0, y1 - y0
    if kind == "box":  # a viewport's edge through or beside the footprint
        dx, dy = rng.uniform(-1.2, 1.2, 2)
        bx, by = cx + dx * w, cy + dy * h
        return np.array([[bx - w / 2, by - h / 2], [bx + w / 2, by - h / 2],
                         [bx + w / 2, by + h / 2], [bx - w / 2, by + h / 2]])
    scale = {"cross": 1.0, "inside": 0.02, "around": 4.0, "far": 1.0}[kind]
    k = int(rng.choice([6, 24, 96]))
    angles = np.sort(rng.uniform(0, 2 * np.pi, k))
    radius = rng.uniform(0.75, 1.0, k) * scale
    off = {"cross": rng.uniform(-1.0, 1.0, 2), "inside": rng.uniform(-0.02, 0.02, 2),
           "around": rng.uniform(-0.5, 0.5, 2), "far": rng.uniform(1.2, 2.5, 2)}[kind]
    if kind == "inside":  # round the first vertex pulled inwards: a point of every shape
        cx, cy = 0.9 * foot[0] + 0.1 * np.array([cx, cy])
    return np.stack([cx + off[0] * w + radius * w * np.cos(angles),
                     cy + off[1] * h + radius * h * np.sin(angles)], axis=1)


@pytest.mark.parametrize("kind", ["box", "cross", "inside", "around", "far"])
def test_the_reference_agrees_with_geo_intersects_pair_by_pair(kind, bench, cols):
    rng = np.random.default_rng([SEED, 7, len(kind)])
    agree = hits = 0
    for i in rng.choice(N, 100, replace=False):
        foot = cols.ring(int(i))
        ring = _pair_rings(rng, kind, foot)
        want = bool(bench.ref.rings_intersect(foot[None], ring)[0])
        got = geo.intersects(geo.Polygon(foot), geo.Polygon(np.vstack([ring, ring[:1]])))
        agree += int(want == got)
        hits += int(want)
    assert agree == 100
    if kind in ("cross", "box"):
        assert 10 < hits < 100  # both outcomes are met
    elif kind == "far":
        assert hits == 0
    elif kind == "around":
        assert hits > 90  # a ring of six drawn angles may leave the footprint out


def test_a_touch_and_a_collinear_overlap_count(bench):
    foot = np.array([[[0.0, 0.0], [2.0, 0.0], [2.0, 1.0], [0.0, 1.0], [0.0, 0.0]]])
    touch = np.array([[2.0, 1.0], [3.0, 1.0], [3.0, 2.0], [2.0, 2.0]])  # one shared corner
    along = np.array([[2.0, 0.25], [3.0, 0.25], [3.0, 0.75], [2.0, 0.75]])  # a shared edge piece
    apart = np.array([[2.0 + 1e-12, 0.0], [3.0, 0.0], [3.0, 1.0], [2.0 + 1e-12, 1.0]])
    for ring, want in ((touch, True), (along, True), (apart, False)):
        assert bool(bench.ref.rings_intersect(foot, ring)[0]) is want
        assert geo.intersects(geo.Polygon(foot[0]), geo.Polygon(np.vstack([ring, ring[:1]]))) is want


# ------------------------------------------------------ (c) the tiers' counters


@pytest.mark.parametrize("klass", CLASSES)
def test_the_tiers_sum_to_the_candidates(klass, bench, mix, cols, store, traced):
    reqs = [r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == klass][:2]
    exact = 0
    for req in reqs:
        answer = bench.op.embedded(store, req)
        (decode,) = _spans(traced, "decode")
        a = decode.attrs
        assert sum(a.get(k, 0) for k in TIERS) == a["candidates"], a
        assert a["refine_hits"] == len(answer["ids"])
        # rectangle algebra decides no footprint: what it counts are candidates whose
        # stored f32 bbox misses the f64 query (the device mask is an f32 step wider)
        assert a["refine_rect"] <= max(1, a["candidates"] // 100)
        assert ("refine_exact_s" in a) == (a["refine_exact"] > 0)
        exact += a["refine_exact"]
    # a footprint across a ring's edge has no vertex inside it; of a box's candidates the
    # accept tier leaves a few in a thousand
    assert exact > 0 or klass.startswith("view-")


def test_no_footprint_takes_the_rectangle_shortcut(bench, cols):
    mask, _ = bench.stores.packed_column(cols).box_info()
    assert int(mask.sum()) == 0
    assert (np.diff(cols.offsets) == cols.vertices).all()


def test_the_xz2_plan_decomposes_under_a_span_with_index_and_ranges(bench, cols, store, traced):
    x, y = float(cols.cx[3]), float(cols.cy[3])
    bench.op.embedded(store, bench.footprint_queries.view_request("view", x, y, 0.0123, 0.0061))
    (dec,) = _spans(traced, "plan.decompose")
    assert dec.attrs["index"] == "xz2" and dec.attrs["ranges"] > 0
    (dispatch,) = _spans(traced, "dispatch")
    assert 0 < dispatch.attrs["blocks"] <= dispatch.attrs["slots"]


@pytest.mark.parametrize("klass", ["view-0.04", "poly-96"])
def test_an_untraced_query_is_the_traced_one(klass, bench, mix, cols, store, traced):
    from geomesa_tpu import conf

    req = next(r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == klass)
    with_spans = bench.op.embedded(store, req)
    assert _spans(traced, "decode")[0].attrs["refine_exact"] >= 0
    n_traces = len(traced.traces())
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())
    without = bench.op.embedded(store, req)
    assert obs.tracer().current() is None and len(traced.traces()) == n_traces
    assert np.array_equal(with_spans["ids"], without["ids"])
    assert with_spans["witness"] == without["witness"]


def test_the_readers_read_the_decode_spans(bench):
    def span(i, name, **attrs):
        return {"trace": i, "root": "query", "id": i, "parent": None if name == "query" else 0,
                "name": name, "t0": 0.0, "dur_s": 0.01, "self_s": 0.01, "attrs": attrs}

    spans = [span(1, "decode", candidates=100, refine_rect=0, refine_accept=60, refine_exact=40,
                  refine_exact_s=0.01, refine_hits=80),
             span(2, "decode", candidates=20, refine_rect=0, refine_exact=20,
                  refine_exact_s=0.002, refine_hits=10),
             span(3, "decode", candidates=1000)]  # a store of points: no tier counted
    view = {"spans": spans}
    assert bench.readers["refine_exact_pct"].read(view) == pytest.approx(50.0)
    assert bench.readers["refine_exact_us"].read(view) == pytest.approx(200.0)
    assert bench.readers["extent_hit_pct"].read(view) == pytest.approx(75.0)
    for reader in bench.readers.values():  # the parent's spans: nothing to read
        assert reader.read({"spans": spans[2:]}) is None


# --------------------------------------------------------------- (d) the data


def test_a_seed_gives_the_same_columns_twice(bench, config, cols):
    again = bench.osm_buildings.make(config, N, SEED)
    assert np.array_equal(again.coords, cols.coords) and np.array_equal(again.t, cols.t)
    assert np.array_equal(again.offsets, cols.offsets)
    assert all(np.array_equal(again.attrs[a], cols.attrs[a]) for a in cols.attrs)
    other = bench.osm_buildings.make(config, N, SEED + 1)
    assert not np.array_equal(other.cx, cols.cx)
    assert list(cols.attrs) == ["osm_id", "building", "name", "levels", "height"]
    assert len(np.unique(cols.attrs["osm_id"])) == N
    assert 0.93 < (cols.attrs["name"] == "").mean() < 0.97
    assert 0.57 < (cols.attrs["building"] == "yes").mean() < 0.63


def _rings_by_count(cols):
    for v in np.unique(cols.vertices):
        rows = np.flatnonzero(cols.vertices == v)
        yield int(v), rows, cols.coords[cols.offsets[rows, None] + np.arange(int(v))]


def test_every_ring_is_closed_counter_clockwise_and_inside_its_bounds(bench, cols):
    seen = 0
    for v, rows, rings in _rings_by_count(cols):
        assert (rings[:, 0] == rings[:, -1]).all()
        x, y = rings[..., 0], rings[..., 1]
        area2 = (x[:, :-1] * y[:, 1:] - x[:, 1:] * y[:, :-1]).sum(axis=1)
        assert (area2 > 0).all()  # the shoelace sum of a counter-clockwise ring
        assert np.array_equal(cols.bounds[rows], np.stack(
            [x.min(axis=1), y.min(axis=1), x.max(axis=1), y.max(axis=1)], axis=1))
        # sides of 8 to 30 m, turned: a bbox of at most 30 sqrt(2) m
        assert ((cols.bounds[rows, 3] - cols.bounds[rows, 1]) * 110_540 < 43).all()
        seen += len(rows)
    assert seen == N


def test_every_ring_is_simple(bench, cols):
    """No two edges of a ring meet but neighbours at their shared vertex."""
    for v, rows, rings in _rings_by_count(cols):
        rings = rings[:400]
        e = v - 1
        for i in range(e):
            for j in range(i + 2, e):
                if i == 0 and j == e - 1:
                    continue  # the closing edge is the first one's neighbour
                meet = bench.ref._edges_meet(rings[:, i], rings[:, i + 1],
                                             rings[:, j], rings[:, j + 1])
                assert not meet.any(), (v, i, j)


def test_the_vertex_counts_come_in_the_stated_shares(bench, cols):
    for v, share in bench.osm_buildings.KINDS:
        assert abs((cols.vertices == v).mean() - share) < 0.01
    assert abs(sum(share for _, share in bench.osm_buildings.KINDS) - 1.0) < 1e-12
    assert abs(sum(share for _, share in bench.osm_buildings.BUILDING) - 1.0) < 1e-12


@pytest.mark.parametrize("town", [0, 5, 40])
def test_a_towns_centre_holds_the_stated_density(town, bench, cols):
    """Every town's sigma is set from its own rows: the rows a square
    degree at its centre are the same for a heavy town and a light one."""
    sx, sy = float(cols.sx[town]), float(cols.sy[town])
    x, y = float(cols.cx[town]), float(cols.cy[town])
    mid = (cols.bounds[:, :2] + cols.bounds[:, 2:]) / 2
    near = (np.abs(mid[:, 0] - x) < sx / 2) & (np.abs(mid[:, 1] - y) < sy / 2)
    density = near.sum() / (sx * sy) / 0.96  # a Gaussian's mean over +-sigma/2 is 0.96 of its peak
    assert 0.6 * bench.osm_buildings.CENTRE_DENSITY < density < 1.5 * bench.osm_buildings.CENTRE_DENSITY
    assert sy == pytest.approx(bench.osm_buildings.SIGMA_RATIO * sx)


# ----------------------------------------------------------- (e) the requests


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_is_the_same_multiset_and_no_request_has_a_window(seed, bench, mix, cols):
    per_round = mix["roles"][0]["params"]["round"]
    size = sum(per_round.values())
    assert size == 16 and set(per_round) == set(CLASSES)
    reqs = _requests(bench, mix, cols, seed, 4 * size)
    orders = []
    for r in range(4):
        one = reqs[r * size:(r + 1) * size]
        assert sorted(q["klass"] for q in one) == sorted(
            k for k, c in per_round.items() for _ in range(c))
        orders.append([q["klass"] for q in one])
    assert len({tuple(o) for o in orders}) > 1  # dealt anew every round
    polys = mix["roles"][0]["params"]["polygons"]
    for q in reqs:
        assert "win" not in q and q["op"] == "query_extents"
        x0, y0, x1, y1 = q["box"]
        if q["klass"].startswith("view-"):
            w = float(q["klass"][5:])
            assert "ring" not in q and x1 - x0 == pytest.approx(w) and y1 - y0 == pytest.approx(w / 2)
            assert bench.rq.ecql(q).startswith("bbox(geom, ")
        else:
            ring = np.array(q["ring"])
            assert len(ring) == polys[q["klass"]]["vertices"]
            a, b = polys[q["klass"]]["semi_axes_deg"]
            assert 1.4 * a < x1 - x0 <= 2 * a and 1.4 * b < y1 - y0 <= 2 * b
            assert [x0, y0, x1, y1] == [ring[:, 0].min(), ring[:, 1].min(),
                                        ring[:, 0].max(), ring[:, 1].max()]
            assert bench.rq.ecql(q).startswith("INTERSECTS(geom, POLYGON((")
    assert _requests(bench, mix, cols, seed, 4 * size) == reqs


def test_the_warm_ladders_grow_by_under_the_square_root_of_two(bench, mix, cols):
    """Viewports to 2 deg (the 64-slot bucket), rings as far as the mix's
    own: a ring's candidates outside it cost the host's exact tier."""
    views, rings = mix["warm"][1], mix["warm"][2]
    assert mix["warm"][0] == {"requests": 160}
    for warm, top in ((views, 2.0), (rings, 0.16)):
        assert warm["generator"] == "extent_ladder" and warm["params"]["ratio"] < 2 ** 0.5
        reqs = bench.extent_ladder.generate(warm["params"], None, 0, cols.context())
        per_class = len(reqs) // len(warm["params"]["classes"])
        widths = [r["box"][2] - r["box"][0] for r in reqs[:per_class]]
        assert widths[0] == pytest.approx(0.005) and top / 1.4 < widths[-1] <= top * 1.0001
        assert all(b / a < 2 ** 0.5 for a, b in zip(widths, widths[1:]))
        assert all(r["op"] == "query_extents" and "win" not in r for r in reqs)
        assert all(("ring" in r) == (warm is rings) for r in reqs)
    assert {len(r["ring"]) for r in bench.extent_ladder.generate(
        rings["params"], None, 0, cols.context())} == {24, 96}


# ---------------------------------------------------------------- (f) the cell


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL,
         "--rows", str(N), "--seconds", "3", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    return out, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_on_the_cpu():
    out, line = _rehearse("--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    read = line["rehearsal_metrics"]
    assert {"refine_exact_pct", "refine_exact_us", "extent_hit_pct", "gather_ms", "refine_ms",
            "plan_ms", "decompose_ms", "scan_useful_pct", "gather_native_pct"} <= set(read)
    assert 0 < read["refine_exact_pct"]["value"] < 100 and 0 < read["extent_hit_pct"]["value"] <= 100


@pytest.mark.parametrize("control,number", [("drop-row", "wrong_answers"),
                                            ("swap-attr", "wrong_attributes")])
def test_a_broken_guarantee_is_not_correct_on_the_cell(control, number):
    out, line = _rehearse("--trace", "0", "--control", control)
    assert out.returncode == 1 and line["correct"] is False
    compared = [json.loads(s) for s in out.stdout.splitlines() if '"compared"' in s]
    assert {c["number"]: c["value"] for c in compared}[number] > 0


def test_the_loose_control_cannot_break_an_extent_store(bench, mix, cols, store):
    """``controls.arm("loose")`` sets the planner's hint; ``loose_ok`` also
    needs a device mask that decides the filter, and a mask over bboxes
    never decides an intersects over polygons: the answers stay exact."""
    from geomesa_tpu.filter import ecql
    from geomesa_tpu.planning.planner import mask_decides_filter

    reqs = _requests(bench, mix, cols, SEED, 16)
    plan = store.ds.planner.plan(store.type_name, ecql.parse(bench.rq.ecql(reqs[0])))
    assert plan.index == "xz2" and plan.config.geom_precise is False
    assert not mask_decides_filter(plan.filter, plan.config, store.ds.get_schema(store.type_name))
    undo = bench.controls.arm("loose")
    try:
        for req in reqs:
            tally, _ = _compared(bench, cols, store, req)
            assert all(tally[k] == 0 for k in bench.check.LIMITS), tally
    finally:
        undo()
