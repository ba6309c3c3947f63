"""The NYC-taxi broadcast join (benchmark configuration ``nyc-taxi-1chip``,
cell ``nyc-taxi.zone-join``) at a small size on the CPU:

(a) ``geomesa_tpu.sql.spatial_join_indexed`` over a store of the
    ``nyctaxi`` type with a z2 index alone, loaded as
    ``benchmark/stores/datastore_join.py`` loads it, and the benchmark's
    plain reference (``harness/reference_join.py``) agree on every class of
    the mix under five seeds;
(b) that reference and the program's ``geo.contains`` agree pair by pair on
    500 seeded polygon / point pairs but where the point lies ON the ring
    (a shared edge, a vertex): there the reference holds it in no interior
    and the program's even-odd parity gives it to one side (docs/joins.md);
(c) ``datagen/nyc_taxi.py``: the three layers are planar partitions of the
    city's box with the source's counts of polygons and vertices under
    every seed (every point of the box in exactly one polygon, shared edges
    vertex for vertex, rings closed, simple, counter-clockwise), a seed
    gives the same columns twice, the pickups lie where the shares say;
(d) the join's spans (PR 41; docs/observability.md): root ``join`` with
    ``join.plan`` (its tiers sum to ``members``), ``join.host``,
    ``dispatch`` / ``scan``, ``join.refine`` (``certain`` + ``uncertain`` =
    ``rows``), ``join.assemble``; a traced answer is the untraced one; PR 42:
    the Manhattan-like borough's whole-table pass answers alike, pair for
    pair and count for count, whether it walks the table 1,024 points at a
    time or ``filter.raster.CLASSIFY_CHUNK``, and ``join.host`` counts its
    ``chunks`` and the points that went through them (``chunked``); PR 52:
    ``join.assemble`` counts the answer's ``pairs``, the members it ordered
    (``sorted``) and the pairs it copied out of a member's array (``moved``:
    none where one member answered, whose rows are the answer);
(e) ``generators/zone_joins.py``: every seed's round is the same multiset;
    ``join_ladder`` asks alone every borough and every neighborhood a mix
    of 8,000 requests reaches, under every seed;
(f) the cell itself through ``benchmark/rehearse.py`` reads ``correct``;
    an answer with a pair dropped, doubled or moved to the neighbouring
    polygon does not, through ``ops/join.compare``.
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
from geomesa_tpu.sql.join import _TIERS as TIERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED = 1 << 16, 2_600_000_011
CELL = "nyc-taxi.zone-join"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
SEEDS = (1, 2, 3, 2_600_000_011, 3_100_000_007)
CLASSES = ("blocks-16", "nbhd-4", "boro-1")
LAYERS = ("blocks", "neighborhoods", "boroughs")


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, store, generators, op and reference,
    imported as the benchmark imports them (tests/test_buildings_cell.py's
    fixture)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import nyc_taxi
        from generators import join_ladder, zone_joins
        from harness import check, reference_join
        from harness import requests as rq
        from layer_metrics import (join_assemble_moved_pct, join_assemble_ns_pair,
                                   join_device_pct, join_host_chunked_pct, join_polygon_us,
                                   join_residue_pct)
        from ops import join
        from stores import datastore_join

        yield types.SimpleNamespace(
            nyc_taxi=nyc_taxi, join_ladder=join_ladder, zone_joins=zone_joins, check=check,
            ref=reference_join, rq=rq, op=join, stores=datastore_join,
            readers={"join_device_pct": join_device_pct, "join_polygon_us": join_polygon_us,
                     "join_residue_pct": join_residue_pct,
                     "join_host_chunked_pct": join_host_chunked_pct,
                     "join_assemble_ns_pair": join_assemble_ns_pair,
                     "join_assemble_moved_pct": join_assemble_moved_pct})
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == "nyc-taxi-1chip")
    assert entry["reduced"] == ["rows"]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "zone-joins.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cols(bench, config):
    return bench.nyc_taxi.make(config, N, SEED)


@pytest.fixture(scope="module")
def store(bench, config, cols, tmp_path_factory):
    out = bench.stores.build(config, cols, str(tmp_path_factory.mktemp("run")))
    assert [i.name for i in out.ds._indexes[out.type_name]] == ["z2"]
    assert {k: len(v) for k, v in out.layers.items()} == bench.nyc_taxi.POLYGONS
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
def test_the_join_answers_as_the_plain_reference(klass, seed, bench, mix, cols, store):
    reqs = [r for r in _requests(bench, mix, cols, seed, 64) if r["klass"] == klass][:3]
    assert len(reqs) == 3 and all(r["op"] == "join" for r in reqs)
    pairs = 0
    for req in reqs:
        tally, answer = _compared(bench, cols, store, req)
        assert all(tally[k] == 0 for k in bench.check.LIMITS), (tally, req)
        assert tally["rows_compared"] == bench.op.size(answer)
        pairs += tally["rows_compared"]
    assert pairs > 0


def test_a_point_id_is_the_generators_row(bench, cols, store):
    """The right side of a pair is an ordinal of ``ds.features``: the store
    keeps the rows in the order written, so it is the generator's id."""
    fc = store.ds.features(store.type_name)
    assert np.array_equal(np.asarray(fc.ids), np.arange(N))
    assert np.array_equal(fc.geom_column.x, cols.x) and np.array_equal(fc.geom_column.y, cols.y)
    assert len(fc.sft.attributes) == 14 and fc.sft.geom_field == "geom"


# ------------------------------------- (b) the reference against geo.contains


def _held(bench, ring, x, y) -> bool:
    return bool(len(bench.ref.ring_interior(bench.ref.PointsByY([x], [y]), ring)))


def _pairs(kind, rng, cols, city):
    """100 (ring, x, y, on_ring) of one kind."""
    out = []
    if kind == "edge":  # a dyadic box cut along its diagonal: the arithmetic is exact
        for _ in range(50):
            x0, y0 = rng.integers(-64, 64, 2) / 64.0
            w, h = rng.integers(1, 64, 2) / 64.0
            t = rng.integers(1, 8) / 8.0
            low = np.array([[x0, y0], [x0 + w, y0], [x0 + w, y0 + h], [x0, y0]])
            high = np.array([[x0, y0], [x0 + w, y0 + h], [x0, y0 + h], [x0, y0]])
            out += [(low, x0 + t * w, y0 + t * h, True), (high, x0 + t * w, y0 + t * h, True)]
        return out
    layer = cols.layers["neighborhoods" if kind == "vertex" else "blocks"]
    x0, y0, x1, y1 = city
    for k in rng.choice(len(layer), 100, replace=False):
        ring = layer.ring(int(k))
        bx0, by0, bx1, by1 = layer.bounds[k]
        v = ring[rng.integers(0, len(ring) - 1)]
        if kind == "bbox":
            p = (rng.uniform(bx0, bx1), rng.uniform(by0, by1))
        elif kind == "near":  # a ten-thousandth of a block off a vertex
            p = (v[0] + rng.normal(0, 3e-7), v[1] + rng.normal(0, 3e-7))
        elif kind == "far":
            p = (rng.uniform(x0, x1), rng.uniform(y0, y1))
        else:
            p = (float(v[0]), float(v[1]))
        out.append((ring, p[0], p[1], kind == "vertex"))
    return out


@pytest.mark.parametrize("kind", ["bbox", "near", "far", "vertex", "edge"])
def test_the_reference_agrees_with_geo_contains_pair_by_pair(kind, bench, cols):
    rng = np.random.default_rng([SEED, 7, len(kind)])
    agree = held = given = 0
    for ring, x, y, on_ring in _pairs(kind, rng, cols, bench.nyc_taxi.CITY):
        want = _held(bench, ring, x, y)
        got = geo.contains(geo.Polygon(ring), geo.Point(x, y))
        if on_ring:  # the reference: in no interior; the program's parity: on one side
            assert want is False
            assert bool(geo.points_on_boundary([x], [y], geo.Polygon(ring))[0])
            given += int(got)
        else:
            agree += int(want == got)
            held += int(want)
    if kind in ("vertex", "edge"):
        assert 0 < given < 100  # the documented departure shows, and is not "always"
        if kind == "edge":
            assert given == 50  # of the two triangles along a diagonal, exactly one
    else:
        assert agree == 100
        assert (10 < held < 90) if kind != "far" else held <= 1


# ------------------------------------------------------------- (c) the layers


@pytest.fixture(scope="module", params=[1, SEED])
def layers(request, bench):
    return bench.nyc_taxi.make_layers(request.param)[0]


def _edges(layer):
    """[E, 4]: every ring's directed edges (x0, y0, x1, y1)."""
    last = np.zeros(len(layer.coords), bool)
    last[layer.offsets[1:] - 1] = True
    a = np.flatnonzero(~last)
    return np.concatenate([layer.coords[a], layer.coords[a + 1]], axis=1)


@pytest.mark.parametrize("name", LAYERS)
def test_a_layer_has_the_sources_counts_under_every_seed(name, bench, layers):
    layer = layers[name]
    means = {"blocks": 12.5, "neighborhoods": 30.6, "boroughs": 662.0}
    assert len(layer) == bench.nyc_taxi.POLYGONS[name]
    assert len(layer.coords) == bench.nyc_taxi.VERTICES[name] == int(layer.offsets[-1])
    assert len(layer.coords) / len(layer) == pytest.approx(means[name], abs=1e-9)
    sizes = np.diff(layer.offsets)
    if name == "blocks":
        assert sizes.min() >= 9 and sizes.max() <= 21  # four sides of 1 to 4, and five corners
    if name == "boroughs":
        assert (sizes - 1 > 256).all()  # every borough lies past the device's edge ladder


@pytest.mark.parametrize("name", LAYERS)
def test_a_layer_is_a_planar_partition_of_the_box(name, bench, layers):
    layer = layers[name]
    x0, y0, x1, y1 = bench.nyc_taxi.CITY
    first, last = layer.coords[layer.offsets[:-1]], layer.coords[layer.offsets[1:] - 1]
    assert np.array_equal(first, last)  # closed to the last bit
    e = _edges(layer)
    owner = np.repeat(np.arange(len(layer)), np.diff(layer.offsets) - 1)
    area2 = np.bincount(owner, e[:, 0] * e[:, 3] - e[:, 2] * e[:, 1], len(layer))
    assert (area2 > 0).all()  # counter-clockwise
    assert area2.sum() / 2 == pytest.approx((x1 - x0) * (y1 - y0), rel=1e-9)  # no gap, no overlap
    # neighbours share their edges vertex for vertex: an edge's reverse is another
    # polygon's edge, bit for bit, unless the edge lies on the box
    void = np.dtype((np.void, 32))
    fwd = np.ascontiguousarray(e).view(void).ravel()
    rev = np.ascontiguousarray(e[:, [2, 3, 0, 1]]).view(void).ravel()
    assert len(np.unique(fwd)) == len(fwd)
    alone = ~np.isin(fwd, rev)
    on_box = (((e[:, 0] == e[:, 2]) & np.isin(e[:, 0], (x0, x1)))
              | ((e[:, 1] == e[:, 3]) & np.isin(e[:, 1], (y0, y1))))
    assert np.array_equal(alone, on_box)


@pytest.mark.parametrize("name", LAYERS)
def test_every_point_of_the_box_lies_in_exactly_one_polygon(name, bench, layers):
    layer = layers[name]
    x0, y0, x1, y1 = bench.nyc_taxi.CITY
    rng = np.random.default_rng([5, len(name)])
    points = bench.ref.PointsByY(rng.uniform(x0, x1, 20_000), rng.uniform(y0, y1, 20_000))
    seen = np.zeros(20_000, np.int64)
    for k in range(len(layer)):
        seen[bench.ref.ring_interior(points, layer.ring(k))] += 1
    assert (seen == 1).all()


@pytest.mark.parametrize("name", LAYERS)
def test_every_ring_is_simple(name, bench, layers):
    """No two edges of a ring meet but neighbours at their shared vertex."""
    from harness import reference_extents

    layer = layers[name]
    rng = np.random.default_rng(3)
    for k in rng.choice(len(layer), min(len(layer), 200), replace=False):
        ring = layer.ring(int(k))
        a, b = ring[:-1], ring[1:]
        meet = reference_extents._edges_meet(a[:, None], b[:, None], a[None, :], b[None, :])
        i, j = np.nonzero(meet)
        gap = np.abs(i - j)
        assert ((gap <= 1) | (gap == len(a) - 1)).all(), (name, int(k))


def test_a_seed_gives_the_same_columns_twice(bench, config, cols):
    again = bench.nyc_taxi.make(config, N, SEED)
    assert np.array_equal(again.x, cols.x) and np.array_equal(again.t, cols.t)
    for a, c in cols.attrs.items():
        if isinstance(c, tuple):
            assert np.array_equal(again.attrs[a][0], c[0]) and np.array_equal(again.attrs[a][1], c[1])
        else:
            assert np.array_equal(again.attrs[a], c)
    for name in LAYERS:
        assert np.array_equal(again.layers[name].coords, cols.layers[name].coords)
    other = bench.nyc_taxi.make(config, N, SEED + 1)
    assert not np.array_equal(other.cx, cols.cx)
    assert not np.array_equal(other.layers["blocks"].coords, cols.layers["blocks"].coords)
    assert list(cols.attrs) == list(bench.nyc_taxi.ATTRIBUTES)
    assert (np.diff(cols.t) >= 0).all() and (cols.attrs["dropoff_datetime"] > cols.t).all()
    assert set(cols.row(N - 1)) == {a for a, _ in cols.schema}


def test_the_pickups_lie_where_the_shares_say(bench, cols):
    x0, y0, x1, y1 = bench.nyc_taxi.CITY
    outside = (cols.x < x0) | (cols.x > x1) | (cols.y < y0) | (cols.y > y1)
    assert abs(outside.mean() - bench.nyc_taxi.SHARES["junk"]) < 0.002
    assert 0.001 < ((cols.x == 0.0) & (cols.y == 0.0)).mean() < 0.004
    ks, ids = bench.ref.join_pairs(cols, "boroughs", range(5))
    assert len(ids) == len(np.unique(ids)) == N - int(outside.sum())  # junk pairs with nothing
    share = np.bincount(ks, minlength=5) / N
    assert 0.84 < share[bench.nyc_taxi.MANHATTAN] < 0.93 and (share[1:] > 0.002).all()
    assert abs(sum(bench.nyc_taxi.SHARES.values()) - 1.0) < 1e-12
    ctx = cols.context()
    assert len(ctx["cx"]) == 256 and ctx["layers"]["boroughs"]["lines"] is None
    assert [len(v) for v in ctx["layers"]["blocks"]["lines"]] == [159, 249]
    edges = np.diff(cols.layers["blocks"].offsets) - 1
    assert ctx["layers"]["blocks"]["over_16_edges"] and all(
        edges[k] > 16 for k in ctx["layers"]["blocks"]["over_16_edges"])


# -------------------------------------------------------------- (d) the spans


@pytest.mark.parametrize("klass", CLASSES)
def test_the_joins_spans_count_its_members_and_rows(klass, bench, mix, cols, store, traced):
    reqs = [r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == klass][:2]
    for req in reqs:
        answer = bench.op.embedded(store, req)
        tr = traced.traces()[-1]
        root = tr.root
        assert tr.name == "join" and root.attrs["members"] == len(req["subset"])
        assert root.attrs["predicate"] == "contains" and root.attrs["pairs"] == len(answer["ids"])
        (plan,) = _spans(traced, "join.plan")
        assert sum(plan.attrs[t] for t in TIERS) == root.attrs["members"], plan.attrs
        assert plan.attrs["edges"] == sum(
            len(cols.layers[req["layer"]].ring(k)) - 1 for k in req["subset"])
        assert plan.attrs["ranges"] >= 0 and "cpu_s" in plan.attrs
        (dispatch,) = [s for s in _spans(traced, "dispatch") if s.parent_id == root.span_id]
        live = root.attrs["members"] - plan.attrs["host_raster"] - plan.attrs["empty"]
        assert dispatch.attrs["members"] == live
        assert len(_spans(traced, "scan")) == live
        assert len(_spans(traced, "join.host")) == (1 if plan.attrs["host_raster"] else 0)
        refines = _spans(traced, "join.refine")
        for s in refines:
            assert s.attrs["certain"] + s.attrs["uncertain"] == s.attrs["rows"] > 0
        assert len(_spans(traced, "join.assemble")) == (1 if len(answer["ids"]) else 0)
        for s in _spans(traced, "join.assemble"):  # PR 52: what the assembly wrote, and how
            answering = len(np.unique(answer["k"]))
            assert s.attrs["members"] == answering and s.attrs["pairs"] == root.attrs["pairs"]
            assert s.attrs["sorted"] <= len(refines)  # a member the scan answered, if rows stayed
            assert 0 <= answering - s.attrs["sorted"] <= plan.attrs["host_raster"]
            assert s.attrs["moved"] == (0 if answering == 1 else s.attrs["pairs"])
        if plan.attrs["host_raster"]:
            (host,) = _spans(traced, "join.host")
            assert host.attrs["points"] == N * plan.attrs["host_raster"]
            assert host.attrs["decided"] + host.attrs["residue"] == host.attrs["points"]
            assert host.attrs["chunked"] == host.attrs["points"]  # PR 42: every one in chunks
            assert host.attrs["chunks"] == plan.attrs["host_raster"]  # 2^16 rows: one a member
        elif live:
            assert 0 < dispatch.attrs["blocks"] <= dispatch.attrs["slots"]
            assert any("wait" in (s.attrs or {}).get("segments", {})
                       for s in _spans(traced, "scan"))
        spans = [s for s in tr.spans if s.parent_id == root.span_id]
        assert sum(s.dur_s for s in spans) <= root.dur_s


def test_the_manhattan_borough_takes_the_hosts_route_and_the_blocks_the_devices(
        bench, cols, store, traced):
    boro = bench.zone_joins.join_request("boro-1", "boroughs", [0], "contains")
    bench.op.embedded(store, boro)
    assert _spans(traced, "join.plan")[0].attrs["host_raster"] == 1
    spot = bench.zone_joins.patch_round(cols.context(), "blocks", cols.cx[0], cols.cy[0], 4, 4)
    bench.op.embedded(store, bench.zone_joins.join_request("blocks-16", "blocks", spot, "contains"))
    plan = _spans(traced, "join.plan")[0].attrs
    assert plan["pip"] + plan["rast"] == 16 and plan["candidate_rows"] > 0


@pytest.mark.parametrize("predicate", ["contains", "intersects"])
def test_the_manhattan_pass_answers_alike_whatever_the_chunk(
        predicate, bench, cols, store, traced, monkeypatch):
    from geomesa_tpu.filter import raster as fr
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.sql import spatial_join_indexed

    left = store.layers["boroughs"].take(np.array([bench.nyc_taxi.MANHATTAN]))

    def ask():
        m = MetricsRegistry()
        k, ids = spatial_join_indexed(store.ds, store.type_name, left, predicate, metrics=m)
        (host,) = _spans(traced, "join.host")
        return k, ids, dict(host.attrs), [
            m.counter_value(f"geomesa.join.raster.{c}") for c in ("decided", "residue")]

    k, ids, host, counted = ask()
    assert host["chunks"] == 1 and N <= fr.CLASSIFY_CHUNK
    monkeypatch.setattr(fr, "CLASSIFY_CHUNK", 1024)
    k_s, ids_s, host_s, counted_s = ask()
    assert host_s["chunks"] == N // 1024 and host["chunks"] == 1
    for name in ("members", "points", "decided", "residue", "chunked"):
        assert host_s[name] == host[name], name
    assert counted_s == counted == [host["decided"], host["residue"]]
    assert host["chunked"] == host["points"] == N and 0 < host["residue"] < N
    assert ids_s.dtype == np.int64 and np.array_equal(k, k_s) and np.array_equal(ids, ids_s)
    assert (np.diff(ids) > 0).all() and 0.84 * N < len(ids) < 0.93 * N
    if predicate == "contains":  # the cell's own request, held to the plain reference
        _, want = bench.ref.join_pairs(cols, "boroughs", [bench.nyc_taxi.MANHATTAN])
        assert np.array_equal(ids, want)


@pytest.mark.parametrize("klass", CLASSES)
def test_an_untraced_join_is_the_traced_one(klass, bench, mix, cols, store, traced):
    from geomesa_tpu import conf

    req = next(r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == klass)
    with_spans = bench.op.embedded(store, req)
    n_traces = len(traced.traces())
    conf.OBS_TRACE_SAMPLE.clear()
    conf.OBS_SLOW_MS.set(0)
    try:
        obs.install(obs.Tracer())
        without = bench.op.embedded(store, req)
        assert obs.tracer().current() is None and not obs.tracer().traces()
    finally:
        conf.OBS_SLOW_MS.clear()
    assert len(traced.traces()) == n_traces
    assert with_spans["k"].dtype == without["k"].dtype == np.int64
    assert np.array_equal(with_spans["k"], without["k"])
    assert np.array_equal(with_spans["ids"], without["ids"])


def test_the_readers_read_the_joins_spans(bench):
    def span(i, name, parent=1, **attrs):
        return {"trace": 1, "root": "join", "id": i, "parent": parent, "name": name, "t0": 0.0,
                "dur_s": 0.01, "self_s": 0.01, "attrs": attrs}

    spans = [span(1, "join", parent=None, members=20),
             span(2, "join.plan", pip=12, rast=4, bbox_only=2, host_raster=1, empty=1),
             span(3, "join.refine", rows=100, certain=75, uncertain=25),
             span(4, "join.refine", rows=300, certain=300, uncertain=0),
             span(5, "join.host", members=1, points=65536, decided=60000, residue=5536,
                  chunks=64, chunked=65536),
             span(6, "join.assemble", members=3, pairs=400, sorted=2, moved=400),
             span(7, "join", parent=None, members=1, pairs=1600),
             span(8, "join.assemble", parent=7, members=1, pairs=1600, sorted=0, moved=0)]
    spans[0]["attrs"]["pairs"] = 400
    view = {"spans": spans}
    # two assemblies of 10 ms over 2,000 pairs, a fifth of them copied
    assert bench.readers["join_assemble_ns_pair"].read(view) == pytest.approx(10_000.0)
    assert bench.readers["join_assemble_moved_pct"].read(view) == pytest.approx(20.0)
    for s in spans[-3::2]:  # PR 52's parent: the span and the root's pairs, no count of moves
        s["attrs"] = {"members": s["attrs"]["members"]}
    assert bench.readers["join_assemble_ns_pair"].read(view) == pytest.approx(10_000.0)
    assert bench.readers["join_assemble_moved_pct"].read(view) is None
    del spans[-3:]
    assert bench.readers["join_device_pct"].read(view) == pytest.approx(80.0)
    assert bench.readers["join_polygon_us"].read(view) == pytest.approx(500.0)
    assert bench.readers["join_residue_pct"].read(view) == pytest.approx(6.25)
    assert bench.readers["join_host_chunked_pct"].read(view) == 100.0
    for gone in ("chunks", "chunked"):  # PR 42's parent: one sweep, and it counts neither
        del spans[-1]["attrs"][gone]
    assert bench.readers["join_host_chunked_pct"].read(view) is None
    parent = [dict(s, root="query", name=s["name"].replace("join", "query")) for s in spans[:1]]
    for reader in bench.readers.values():  # the parent's spans: nothing to read
        assert reader.read({"spans": parent}) is None


# ----------------------------------------------------------- (e) the requests


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_is_the_same_multiset(seed, bench, mix, cols):
    role = mix["roles"][0]
    per_round = role["params"]["round"]
    size = sum(per_round.values())
    assert size == 16 and per_round == {"blocks-16": 8, "nbhd-4": 6, "boro-1": 2}
    assert mix["client"] == "embedded" and role["clients"] == 1
    assert role["requests_per_client"] == 8000 and role["params"]["predicate"] == "contains"
    reqs = _requests(bench, mix, cols, seed, 4 * size)
    orders, others = [], []
    for r in range(4):
        one = reqs[r * size:(r + 1) * size]
        assert sorted(q["klass"] for q in one) == sorted(
            k for k, c in per_round.items() for _ in range(c))
        orders.append([q["klass"] for q in one])
        first, second = [q["subset"] for q in one if q["klass"] == "boro-1"]
        assert first == [0] and second != [0]  # the Manhattan-like one every round
        others.append(second[0])
    assert others == [1, 2, 3, 4]  # the other four in turn
    assert len({tuple(o) for o in orders}) > 1  # dealt anew every round
    sizes = {"blocks-16": 16, "nbhd-4": 4, "boro-1": 1}
    for q in reqs:
        assert q["op"] == "join" and q["predicate"] == "contains" and "win" not in q
        assert len(q["subset"]) == sizes[q["klass"]] == len(set(q["subset"]))
        assert q["subset"] == sorted(q["subset"])
        assert 0 <= q["subset"][0] and q["subset"][-1] < len(cols.layers[q["layer"]])
        if q["klass"] == "nbhd-4":  # a 2 x 2 patch of the 13-wide lattice
            a, b, c, d = q["subset"]
            assert (b - a, c - a, d - a) == (1, 13, 14)
    assert _requests(bench, mix, cols, seed, 4 * size) == reqs


def test_a_patch_lies_round_its_centre(bench, cols):
    ctx = cols.context()
    for s in range(8):
        x, y = ctx["cx"][s], ctx["cy"][s]
        for layer, side in (("blocks", 4), ("neighborhoods", 2)):
            subset = bench.zone_joins.patch_round(ctx, layer, x, y, side, side)
            b = cols.layers[layer].bounds[subset]
            assert b[:, 0].min() < x < b[:, 2].max() and b[:, 1].min() < y < b[:, 3].max()
            pts = bench.ref.PointsByY([x], [y])
            assert sum(len(bench.ref.ring_interior(pts, cols.layers[layer].ring(k)))
                       for k in subset) == 1  # the patch holds its centre


@pytest.mark.parametrize("seed", SEEDS)
def test_the_warm_ladder_asks_alone_every_zone_the_mix_reaches(seed, bench, config, mix):
    assert mix["warm"][0] == {"requests": 160}
    warm = mix["warm"][1]
    assert warm["generator"] == "join_ladder"
    cols = bench.nyc_taxi.make(config, 1024, seed)  # the layers and hot spots are the seed's
    reqs = bench.join_ladder.generate(warm["params"], None, 0, cols.context())
    alone = {layer: {q["subset"][0] for q in reqs if q["layer"] == layer and len(q["subset"]) == 1}
             for layer in LAYERS}
    asked = {k for r in _requests(bench, mix, cols, seed, 8_000)
             if r["layer"] == "neighborhoods" for k in r["subset"]}
    assert asked <= alone["neighborhoods"] and len(alone["neighborhoods"]) < 64
    assert alone["boroughs"] == set(range(5))
    over = set(cols.context()["layers"]["blocks"]["over_16_edges"])
    assert over and over <= alone["blocks"] and len(alone["blocks"]) >= 8
    sides = sorted({len(q["subset"]) for q in reqs if q["layer"] == "blocks"})
    assert sides == [1, 4, 9, 16, 36, 64]  # over 8 members fuse: both sides of that line
    assert all(q["op"] == "join" and q["predicate"] == "contains" for q in reqs)


# ---------------------------------------------------------------- (f) the cell


def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL,
         "--rows", str(N), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    read = line["rehearsal_metrics"]
    assert {"join_plan_ms", "join_polygon_us", "join_scan_ms", "join_refine_ms", "join_host_ms",
            "join_device_pct", "join_residue_pct", "join_coverage_pct", "join_host_chunked_pct",
            "query_p50_ms"} <= set(read)
    assert read["join_host_chunked_pct"]["value"] == 100.0
    assert 80 < read["join_device_pct"]["value"] < 100  # all but the Manhattan-like borough
    assert 90 < read["join_coverage_pct"]["value"] <= 100
    window = next(json.loads(s) for s in out.stdout.splitlines() if '"phase": "window"' in s)
    assert window["compile_requests_in_window"] == 0


@pytest.mark.parametrize("fault,number", [("dropped", "wrong_answers"),
                                          ("doubled", "doubled_rows"),
                                          ("moved", "wrong_answers")])
def test_a_broken_answer_is_not_correct(fault, number, bench, mix, cols, store):
    req = next(r for r in _requests(bench, mix, cols, SEED, 32) if r["klass"] == "nbhd-4")
    answer = bench.op.embedded(store, req)
    k, ids = answer["k"], answer["ids"]
    assert len(ids) > 10 and len(np.unique(k)) > 1
    if fault == "dropped":
        broken = {"k": k[:-1], "ids": ids[:-1]}
    elif fault == "doubled":
        broken = {"k": np.concatenate([k[:1], k]), "ids": np.concatenate([ids[:1], ids])}
    else:  # the first pair of the second polygon, given to the first
        at = int(np.searchsorted(k, k[0] + 1))
        moved = k.copy()
        moved[at] = k[0]
        broken = {"k": moved, "ids": ids}
    tally = bench.check.new_tally()
    bench.op.compare(tally, cols, req, broken)
    assert tally[number] > 0
    sound = bench.check.new_tally()
    bench.op.compare(sound, cols, req, answer)
    assert all(sound[n] == 0 for n in bench.check.LIMITS)
