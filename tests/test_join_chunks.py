"""The host's raster classification and the join's broad route in chunks
(PR 42; docs/joins.md): ``filter.raster.CLASSIFY_CHUNK`` points at a time,
so that a pass's temporaries are a chunk's whatever the table holds, and
nothing of the answer moves:

(a) ``RasterApprox.classify_points`` over several chunks and a ragged tail
    is the single pass's result element for element, points outside the
    window, on its edges and at (0, 0) among them;
(b) ``spatial_join_indexed`` through the broad route gives the same pairs
    in the same order, the same ``geomesa.join.raster.*`` counters and the
    same ``join.host`` counts whether a chunk is 1,024 points or the
    constant's own, for ``contains`` and ``intersects``, and the pairs of
    the probe-only plan (tests/test_raster_join.py's store, which needs
    shapely and so never runs here; the star's own vertices are among the
    points, so the boundary test has work);
(c) ``join.host`` counts ``chunks`` and ``chunked``;
(d) a broad join's traced allocations peak under the answer, 3 B a point
    and a chunk's scratch (10 B a point at 2^20 points), where the single
    pass peaked at 31 B a point: four f64 arrays of the table;
(e) the assembly (PR 52): an answer's pairs are written once. One member's
    rows ARE the answer's right side (ordered in place where they came off a
    scan) beside one ``np.full``; several members are copied into their
    slices of one ``np.empty`` a side and ordered there. The pairs are the
    exact code's and the earlier assembly's (a sort, a fill and two lists a
    member, two concatenations), pair for pair, for one broad member, one
    scan member, both mixed, members with no rows among them, both
    predicates and a mesh-sharded store; what comes back is the caller's
    (int64, C-contiguous, writable, shared with no later call);
    ``join.assemble`` counts ``pairs``, ``sorted`` and ``moved``; a lone
    member's assembly allocates one answer-sized array, several members'
    the answer's two.
"""

import tracemalloc

import numpy as np
import pytest

from geomesa_tpu import DataStore, FeatureCollection, FeatureType, conf, obs
from geomesa_tpu import geometry as geo
from geomesa_tpu.filter import raster as fr
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.sql import join as sj

CHUNK = fr.CLASSIFY_CHUNK


@pytest.fixture(autouse=True)
def _fresh():
    fr.clear_cache()
    yield
    conf.JOIN_BROAD_FRACTION.clear()
    fr.clear_cache()


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield obs.tracer()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def jagged_star(cx, cy, r, n_arms, seed=0):
    rng = np.random.default_rng(seed)
    a = np.linspace(0, 2 * np.pi, 2 * n_arms + 1)[:-1]
    rad = np.where(np.arange(2 * n_arms) % 2 == 0, r, r * rng.uniform(0.3, 0.7, 2 * n_arms))
    return geo.Polygon([(cx + rr * np.cos(t), cy + rr * np.sin(t)) for t, rr in zip(a, rad)])


def _single_pass(ap, x, y):
    """``classify_points`` as it stood before it walked in chunks."""
    i = np.floor((x - ap.x0) / ap.cell_w).astype(np.int64)
    j = np.floor((y - ap.y0) / ap.cell_h).astype(np.int64)
    ok = (i >= 0) & (i < ap.nx) & (j >= 0) & (j < ap.ny)
    out = np.zeros(len(x), dtype=np.int8)
    out[ok] = ap.classes[j[ok], i[ok]]
    return out


# ------------------------------------------------------ (a) classify_points


def test_the_chunk_is_one_constant_of_the_raster_module():
    assert CHUNK == 1 << 18  # PERF.md section 6 (PR 42) has the sweep that chose it
    assert not [name for name in conf.REGISTRY if "join" in name and "chunk" in name]


@pytest.mark.parametrize("n", [0, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 17],
                         ids=["empty", "chunk-1", "chunk", "chunk+1", "3chunks+17"])
def test_classify_points_in_chunks_is_the_single_pass(n):
    ap = fr.build_raster(jagged_star(3.0, -2.0, 20.0, 12, seed=5))
    assert {geo.RASTER_FULL, geo.RASTER_PARTIAL, geo.RASTER_OUT} <= set(np.unique(ap.classes))
    rng = np.random.default_rng([7, n])
    x1, y1 = ap.x0 + ap.nx * ap.cell_w, ap.y0 + ap.ny * ap.cell_h
    x = rng.uniform(ap.x0 - 5, x1 + 5, n)  # a sixth of them outside the window
    y = rng.uniform(ap.y0 - 5, y1 + 5, n)
    if n:
        # the window's own edges and corners, cell edges, and the TLC data's (0, 0),
        # at the start, across the first chunk's end and in the ragged tail
        edge_x = np.array([ap.x0, x1, ap.x0, x1, ap.x0 + 3 * ap.cell_w, 0.0, np.nextafter(x1, 0)])
        edge_y = np.array([ap.y0, y1, y1, ap.y0, ap.y0 + 2 * ap.cell_h, 0.0, np.nextafter(y1, 0)])
        for at in {0, max(min(CHUNK - 3, n - 7), 0), n - 7}:
            x[at:at + 7], y[at:at + 7] = edge_x, edge_y
    got = ap.classify_points(x, y)
    want = _single_pass(ap, x, y)
    assert got.dtype == np.int8 and got.shape == (n,)
    assert np.array_equal(got, want)
    if n:
        assert len(np.unique(got)) == 3
        # anything np.asarray takes is taken as before
        assert np.array_equal(ap.classify_points(list(x[:50]), list(y[:50])), want[:50])


# -------------------------------------------------------- (b) the broad route


def _store(n=50_000, seed=31):
    """tests/test_raster_join.py ``TestAdaptiveJoin._stores`` with the
    near-world-sized star of its broad-route test, whose vertices are also
    points of the store."""
    rng = np.random.default_rng(seed)
    big = jagged_star(0.0, 0.0, 80.0, 20, seed=99)
    ring = np.asarray(big.shell)[:-1]
    x = np.concatenate([rng.uniform(-50, 50, n - len(ring)), ring[:, 0]])
    y = np.concatenate([rng.uniform(-40, 40, n - len(ring)), ring[:, 1]])
    order = rng.permutation(n)
    x, y = x[order], y[order]
    sft = FeatureType.from_spec("pts", "*geom:Point:srid=4326")
    polys = [jagged_star(float(rng.uniform(-40, 40)), float(rng.uniform(-30, 30)),
                         float(rng.uniform(1.0, 8.0)), int(rng.integers(4, 50)), seed=k)
             for k in range(3)] + [big]
    left = FeatureCollection.from_columns(
        FeatureType.from_spec("polys", "*geom:Polygon:srid=4326"), np.arange(len(polys)),
        {"geom": geo.PackedGeometryColumn.from_geometries(polys)})
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("pts", FeatureCollection.from_columns(sft, np.arange(n), {"geom": (x, y)}),
             check_ids=False)
    return ds, left, big, x, y


def _broad_join(ds, left, predicate, tracer):
    m = MetricsRegistry()
    lo, ro = sj.spatial_join_indexed(ds, "pts", left, predicate, metrics=m)
    tr = tracer.traces()[-1]
    (host,) = [s for s in tr.spans if s.name == "join.host"]
    counters = {k: m.counter_value(f"geomesa.join.{k}")
                for k in ("raster.decided", "raster.residue", "strategy.host_raster",
                          "strategy.probe")}
    return lo, ro, dict(host.attrs), counters


@pytest.mark.parametrize("predicate", ["contains", "intersects"])
def test_the_broad_route_answers_alike_whatever_the_chunk(predicate, traced, monkeypatch):
    ds, left, big, x, y = _store()
    conf.JOIN_BROAD_FRACTION.set(0.2)
    lo, ro, host, counters = _broad_join(ds, left, predicate, traced)
    assert counters["strategy.host_raster"] == 1 and counters["strategy.probe"] == 3
    assert host["points"] == len(x) and host["chunks"] == 1  # 50,000 points: one chunk
    monkeypatch.setattr(fr, "CLASSIFY_CHUNK", 1024)
    lo_s, ro_s, host_s, counters_s = _broad_join(ds, left, predicate, traced)
    assert lo_s.dtype == ro_s.dtype == np.int64
    assert np.array_equal(lo, lo_s) and np.array_equal(ro, ro_s)  # the pairs, in their order
    assert counters_s == counters
    assert host_s["chunks"] == -(-len(x) // 1024) == 49
    for k in ("members", "points", "decided", "residue", "chunked"):
        assert host_s[k] == host[k], k
    assert 0 < host["residue"] < host["points"] == host["decided"] + host["residue"]
    # the star's members ascend, and are what the exact code says of every point
    mine = ro[lo == 3]
    assert (np.diff(mine) > 0).all()
    want = geo.points_in_polygon(x, y, big)
    on_ring = geo.points_on_boundary(x, y, big)
    assert on_ring.sum() >= 40  # its own vertices
    if predicate == "intersects":
        want |= on_ring
    assert np.array_equal(mine, np.flatnonzero(want))
    # and the plan that sends every member to the device pairs alike
    conf.JOIN_BROAD_FRACTION.set(2.0)
    plain = sj.spatial_join_indexed(ds, "pts", left, predicate)
    assert np.array_equal(plain[0], lo) and np.array_equal(plain[1], ro)


def test_a_polygon_inside_call_keeps_its_signature_and_the_gates_units(monkeypatch):
    """``_polygon_inside(xs, ys, ga, predicate, approx, metrics, cls=None)``
    books seconds a point and seconds a point-edge, a chunk or a table."""
    import inspect

    assert list(inspect.signature(sj._polygon_inside).parameters) == [
        "xs", "ys", "ga", "predicate", "approx", "metrics", "cls"]
    booked = []
    monkeypatch.setattr(sj._GATE, "update", lambda kind, s, units: booked.append((kind, units)))
    monkeypatch.setattr(fr, "CLASSIFY_CHUNK", 4096)
    big = jagged_star(0.0, 0.0, 30.0, 20, seed=99)
    ap = fr.raster_for(big)
    rng = np.random.default_rng(3)
    x, y = rng.uniform(-40, 40, 10_000), rng.uniform(-40, 40, 10_000)
    inside, residue, chunks = sj._broad_inside(x, y, big, "contains", ap, MetricsRegistry())
    assert chunks == 3 and inside.dtype == bool and inside.shape == x.shape
    assert sum(u for k, u in booked if k == "cls_s") == len(x)
    assert sum(u for k, u in booked if k == "pip_s") == residue * sj._edge_count(big)
    once, left_over = sj._polygon_inside(x, y, big, "contains", ap, MetricsRegistry())
    assert np.array_equal(inside, once) and left_over == residue


# ---------------------------------------------------------- (c) the counters


def test_join_host_counts_its_chunks(traced, monkeypatch):
    ds, left, *_ = _store(n=20_000)
    conf.JOIN_BROAD_FRACTION.set(0.2)
    monkeypatch.setattr(fr, "CLASSIFY_CHUNK", 4096)
    *_, host, _ = _broad_join(ds, left, "contains", traced)
    assert host["chunks"] == 5 and host["chunked"] == host["points"] == 20_000


# ------------------------------------------------------------ (d) the memory


def test_a_broad_joins_temporaries_are_a_chunks(monkeypatch):
    n, chunk = 1 << 20, 1 << 14
    rng = np.random.default_rng(17)
    x, y = rng.uniform(-50, 50, n), rng.uniform(-40, 40, n)
    sft = FeatureType.from_spec("pts", "*geom:Point:srid=4326")
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("pts", FeatureCollection.from_columns(sft, np.arange(n), {"geom": (x, y)}),
             check_ids=False)
    star = jagged_star(0.0, 0.0, 38.0, 20, seed=99)  # a fifth of the box
    left = FeatureCollection.from_columns(
        FeatureType.from_spec("polys", "*geom:Polygon:srid=4326"), np.arange(1),
        {"geom": geo.PackedGeometryColumn.from_geometries([star])})
    conf.JOIN_BROAD_FRACTION.set(0.1)
    monkeypatch.setattr(fr, "CLASSIFY_CHUNK", chunk)
    m = MetricsRegistry()
    sj.spatial_join_indexed(ds, "pts", left, "contains", metrics=m)  # rasters, memos, imports
    tracemalloc.start()
    try:
        lo, ro = sj.spatial_join_indexed(ds, "pts", left, "contains", metrics=m)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert m.counter_value("geomesa.join.strategy.host_raster") == 2
    assert 0.1 * n < len(ro) < 0.4 * n
    allowed = lo.nbytes + ro.nbytes + 3 * n + 32 * 8 * chunk
    # the single pass peaked at 31 B a point on this store (PR 42's parent, measured)
    assert peak < allowed < 31 * n, (peak, allowed)


# ---------------------------------------------------------- (e) the assembly


def _as_written(per_left, ascending):
    """``join.assemble`` as it stood before PR 52."""
    lo_parts, ro_parts = [], []
    for k in sorted(per_left):
        ords = per_left[k]
        if k not in ascending:
            ords = np.sort(ords)
        lo_parts.append(np.full(len(ords), k, dtype=np.int64))
        ro_parts.append(ords)
    return np.concatenate(lo_parts), np.concatenate(ro_parts)


def _members(shape, seed=5, table_n=1 << 16):
    """(per_left, ascending): members' rows as ``_join_indexed`` holds them
    before the assembly, unique ordinals below ``table_n``; a broad
    member's ascend (and are a ``flatnonzero``: a view that owns nothing),
    a scan's come in another order."""
    rng = np.random.default_rng([seed, len(shape)])
    per_left, ascending = {}, set()
    for k, (kind, n) in shape.items():
        rows = rng.choice(table_n, n, replace=False).astype(np.int64)
        if kind == "broad":
            mask = np.zeros(table_n, bool)
            mask[rows] = True
            rows = np.flatnonzero(mask).astype(np.int64, copy=False)
            ascending.add(k)
        per_left[k] = rows
    return per_left, ascending


SHAPES = {
    "one-broad": {3: ("broad", 40_000)},
    "one-scan": {0: ("scan", 9_000)},
    "one-scan-of-one-row": {7: ("scan", 1)},
    "scans": {0: ("scan", 900), 1: ("scan", 1), 5: ("scan", 7_000), 9: ("scan", 30)},
    "broads": {2: ("broad", 20_000), 4: ("broad", 50_000)},
    "mixed": {0: ("scan", 500), 2: ("broad", 30_000), 3: ("scan", 8_000), 11: ("broad", 100)},
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_assembly_is_the_earlier_ones_pair_for_pair(shape):
    per_left, ascending = _members(SHAPES[shape])
    want_lo, want_ro = _as_written({k: v.copy() for k, v in per_left.items()}, ascending)
    lone = per_left[min(per_left)] if len(per_left) == 1 else None
    lo, ro, moved = sj._assemble(per_left, ascending)
    for got, want in ((lo, want_lo), (ro, want_ro)):
        assert got.dtype == np.int64 and got.flags.c_contiguous and got.flags.writeable
        assert np.array_equal(got, want)
    assert moved == (0 if lone is not None else len(ro))
    assert (ro is lone) == (lone is not None)  # one member's rows ARE the answer


def _polys_store(case, mesh=None):
    """(store, left, x, y, members that answer rows): (b)'s points under
    the left side a case asks, the stars first and the near-world one
    last as there."""
    ds, left, big, x, y = _store(n=30_000, seed=37)
    geoms = left.geometries()
    far = [jagged_star(120.0 + 9 * k, 70.0, 2.0, 6, seed=k) for k in range(3)]  # no point there
    polys = {"one": [big], "small": [geoms[1]], "four": list(geoms),
             "with-empties": [far[0], geoms[0], far[1], big, geoms[2], far[2]]}[case]
    if mesh is not None:
        from geomesa_tpu.parallel import make_mesh

        sharded = DataStore(mesh=make_mesh(mesh))
        sharded.create_schema(ds.get_schema("pts"))
        sharded.write("pts", ds.features("pts"), check_ids=False)
        ds = sharded
    left = FeatureCollection.from_columns(
        FeatureType.from_spec("polys", "*geom:Polygon:srid=4326"), np.arange(len(polys)),
        {"geom": geo.PackedGeometryColumn.from_geometries(polys)})
    return ds, left, polys, x, y


def _exact(polys, x, y, predicate):
    """The pairs by the exact code over every point, sorted by (left, right)."""
    lo, ro = [], []
    for k, g in enumerate(polys):
        inside = geo.points_in_polygon(x, y, g)
        if predicate == "intersects":
            inside |= geo.points_on_boundary(x, y, g)
        ro.append(np.flatnonzero(inside))
        lo.append(np.full(len(ro[-1]), k, dtype=np.int64))
    return np.concatenate(lo), np.concatenate(ro)


#: case: (left side, geomesa.join.broad.fraction, mesh devices, members ordered at assembly,
#: members that answer)
JOINS = {
    "one-broad": ("one", 0.2, None, 0, 1),
    "one-scan": ("one", 2.0, None, 1, 1),
    "one-small-scan": ("small", 0.2, None, 1, 1),
    "mixed": ("four", 0.2, None, 3, 4),
    "scans": ("four", 2.0, None, 4, 4),
    "with-empties": ("with-empties", 0.2, None, 2, 3),
    "mesh-mixed": ("four", 0.2, 4, 3, 4),
    "mesh-one-scan": ("one", 2.0, 4, 1, 1),
}


@pytest.mark.parametrize("predicate", ["contains", "intersects"])
@pytest.mark.parametrize("case", sorted(JOINS))
def test_a_joins_pairs_are_the_exact_ones_and_the_callers_own(case, predicate, traced):
    which, fraction, mesh, n_sorted, answering = JOINS[case]
    ds, left, polys, x, y = _polys_store(which, mesh)
    conf.JOIN_BROAD_FRACTION.set(fraction)
    want_lo, want_ro = _exact(polys, x, y, predicate)
    assert len(np.unique(want_lo)) == answering
    lo, ro = sj.spatial_join_indexed(ds, "pts", left, predicate)
    tr = traced.traces()[-1]
    (span,) = [s for s in tr.spans if s.name == "join.assemble"]
    assert span.attrs["members"] == answering and span.attrs["sorted"] == n_sorted
    assert span.attrs["pairs"] == tr.root.attrs["pairs"] == len(want_ro) > 0
    assert span.attrs["moved"] == (0 if answering == 1 else len(want_ro))
    hosts = [s for s in tr.spans if s.name == "join.host"]
    assert len(hosts) == (1 if answering > n_sorted else 0)
    for got, want in ((lo, want_lo), (ro, want_ro)):
        assert got.dtype == np.int64 and got.flags.c_contiguous and got.flags.writeable
        assert np.array_equal(got, want)
    # the arrays are the caller's: overwritten, they change no later answer,
    lo[:] = -1
    ro[:] = -7
    again = sj.spatial_join_indexed(ds, "pts", left, predicate)
    assert np.array_equal(again[0], want_lo) and np.array_equal(again[1], want_ro)
    assert not np.shares_memory(again[1], ro) and not np.shares_memory(again[0], lo)
    # nor a row of the store, and an untraced answer is the traced one
    col = ds.features("pts").geom_column
    assert np.array_equal(col.x, x) and np.array_equal(col.y, y)
    conf.OBS_TRACE_SAMPLE.clear()
    n_traces = len(traced.traces())
    plain = sj.spatial_join_indexed(ds, "pts", left, predicate)
    assert len(traced.traces()) == n_traces
    assert np.array_equal(plain[0], want_lo) and np.array_equal(plain[1], want_ro)


@pytest.mark.parametrize("shape,arrays", [("one-broad", 1), ("one-scan", 1), ("mixed", 2),
                                          ("scans", 2)])
def test_an_assembly_allocates_the_answer_once(shape, arrays):
    """A lone member: one answer-sized array (the left side; its rows are
    the right). Several: the answer's two, and no member's copy, fill or
    list of parts beside them (the earlier assembly peaked at four a lone
    member, three and a member's sort several)."""
    per_left, ascending = _members({k: (kind, 16 * n) for k, (kind, n) in SHAPES[shape].items()},
                                   table_n=1 << 20)
    answer = 8 * sum(len(v) for v in per_left.values())
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        lo, ro, _ = sj._assemble(per_left, ascending)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert lo.nbytes == ro.nbytes == answer > 1_000_000
    assert peak - before < arrays * answer + (16 << 10), (peak - before, answer)
