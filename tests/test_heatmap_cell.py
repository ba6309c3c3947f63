"""The OSM GPS-traces deployment (benchmark configuration ``osm-gpx-1chip``,
cell ``osm-gpx.heatmap``) at a small size on the CPU:

(a) a store of the ``osm-gpx`` type with a Z2 index alone and the
    benchmark's plain NumPy reference agree on every class of the mix;
(b) the whole-table shape past the bucket ladder, at a ladder shortened
    for the test: ``_full_or`` taken, the grid equal to the laddered one,
    ``full`` = 1 on the span;
(c) the ``density`` root's spans (docs/observability.md; ``full`` is new)
    on a one-chip and on a four-device store; the ``agg`` span's counts of
    the kernel's slots by path (PR 34); an untraced density is the traced
    one and pulls the grid alone;
(d) ``datagen/osm_gpx.py``: a seed gives the same columns twice, times
    ascend, no zoom-0 square of the tile pyramid holds 6.25% of the rows;
    and PERF.md section 7 (u), repaired in PR 33: a dense city ON a tile's
    edge, free f64 rows, is counted as the reference counts it (the key
    ranges cover the box the f32 mask keeps);
(e) ``generators/heatmap_tiles.py``: tiles lie on the EPSG:4326 gridset,
    every seed's round is the same multiset, no request has a window;
(f) the cell itself through ``benchmark/rehearse.py``, and the ``loose``
    control is not correct on this type.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.scan import block_kernels as bk
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage.table import IndexTable

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED, CHIPS, TILE = 1 << 17, 2_600_000_011, 4, 4096
CELL = "osm-gpx.heatmap"
BENCH_PACKAGES = ("harness", "ops", "datagen", "generators", "clients", "stores",
                  "layer_metrics", "kernels")
SEEDS = (1, 2, 3, 2_600_000_011, 3_100_000_007)
ZOOMS = (0, 1, 2, 4, 6, 8)
CLASSES = tuple(f"tile-z{z}" for z in ZOOMS) + ("z2",)


@pytest.fixture(scope="module")
def bench():
    """The new cell's data set, generators, ops and reference, imported as
    the benchmark imports them (tests/test_ingest_cell.py's fixture)."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import osm_gpx
        from generators import heatmap_tiles, tile_levels
        from harness import check, controls, reference
        from harness import requests as rq
        from harness.data import sub_rng
        from ops import density, query

        yield types.SimpleNamespace(
            osm_gpx=osm_gpx, heatmap_tiles=heatmap_tiles, tile_levels=tile_levels, check=check,
            controls=controls, reference=reference, rq=rq, sub_rng=sub_rng, density=density,
            query=query)
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == "osm-gpx-1chip")
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "heatmap-tiles.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cols(bench, config):
    return bench.osm_gpx.make(config, N, SEED)


def _store(config, cols, mesh=None):
    """What ``benchmark/stores/datastore.py`` builds, at a small block."""
    sft = FeatureType.from_spec(config["type_name"], config["schema"])
    sft.user_data["geomesa.indices.enabled"] = ",".join(config["indices"])
    sft.user_data["geomesa.z3.interval"] = config["z3_interval"]
    ds = DataStore(mesh=mesh, tile=TILE)
    ds.create_schema(sft)
    columns = {cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())}
    ds.write(sft.name, FeatureCollection.from_columns(
        sft, np.arange(len(cols), dtype=np.int64), columns), check_ids=False)
    return types.SimpleNamespace(ds=ds, type_name=sft.name)


@pytest.fixture(scope="module")
def one(config, cols):
    store = _store(config, cols)
    assert [i.name for i in store.ds._indexes[store.type_name]] == ["z2"]
    return store


@pytest.fixture(scope="module")
def mesh4(config, cols):
    return _store(config, cols, make_mesh(CHIPS))


def _requests(bench, mix, cols, seed, n):
    role = mix["roles"][0]
    ctx = cols.context() | {"seed": seed, "client_index": 0}
    return bench.heatmap_tiles.generate(role["params"], bench.sub_rng(seed, 100), n, ctx)


@pytest.fixture(scope="module")
def by_class(bench, mix, cols):
    by = {}
    for r in _requests(bench, mix, cols, SEED, 64):
        by.setdefault(r["klass"], []).append(r)
    assert set(by) == set(CLASSES)
    return by


# ---------------------------------------------------- (a) the plain reference


@pytest.mark.parametrize("klass", CLASSES)
def test_z2_only_store_answers_as_the_plain_reference(klass, bench, cols, one, by_class):
    rows = 0
    for req in by_class[klass][:4]:
        tally = bench.check.new_tally()
        op = bench.rq.op_of(req)
        assert op is (bench.query if klass == "z2" else bench.density)
        answer = op.embedded(one, req)
        op.compare(tally, cols, req, answer)
        assert all(tally[k] == 0 for k in bench.check.LIMITS), tally
        rows += tally["rows_compared"]
    # street-level boxes may be empty at this size; every tile class holds rows
    assert rows > 0 or klass == "z2"


def test_a_row_query_returns_the_rows_own_attributes(bench, cols, one):
    """A box round the heaviest city's centre: rows at this size too, and
    the witness row (date and point) is the generator's."""
    x, y = float(cols.cx[0]), float(cols.cy[0])
    req = {"op": "query", "klass": "z2", "box": [x - 0.2, y - 0.2, x + 0.2, y + 0.2]}
    tally = bench.check.new_tally()
    bench.query.compare(tally, cols, req, bench.query.embedded(one, req))
    assert tally["rows_compared"] > 100 and tally["witnesses"] == 1
    assert all(tally[k] == 0 for k in bench.check.LIMITS), tally


# --------------------------------------------------- (b) the whole-table shape


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    conf.OBS_SLOW_MS.set(0.0)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    conf.OBS_SLOW_MS.clear()
    obs.install(obs.Tracer())


def _spans(trace, name):
    return [s for s in [trace.root] + list(trace.spans) if s.name == name]


def _tile(bench, store, z, i, j, grid=64):
    req = bench.heatmap_tiles.tile_request(z, i, j, grid)
    return req, bench.density.embedded(store, req)


def test_past_the_ladder_the_whole_table_is_scanned_and_the_grid_is_the_same(
        bench, cols, one, traced, monkeypatch):
    table = one.ds.table(one.type_name, "z2")
    assert table.n_blocks == N // TILE == 32
    req, laddered = _tile(bench, one, 0, 1, 0)
    (tr,) = traced()
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    asked = d.attrs["blocks"]
    assert 8 < asked < table.n_blocks and d.attrs["full"] == 0
    assert d.attrs["slots"] == bk.bucket_of(asked)

    taken = []
    real = IndexTable._full_or

    def spy(self, blocks):
        out = real(self, blocks)
        taken.append((len(blocks), len(out)))
        return out

    monkeypatch.setattr(IndexTable, "_full_or", spy)
    monkeypatch.setattr(bk, "M_BUCKETS", (4, 8))
    obs.install(obs.Tracer())
    _, whole = _tile(bench, one, 0, 1, 0)
    assert taken == [(asked, table.n_blocks)]
    assert np.array_equal(whole, laddered) and whole.sum() > 0
    (tr,) = traced()
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    assert d.attrs["full"] == 1 and d.attrs["blocks"] == table.n_blocks
    assert d.attrs["slots"] == bk.bucket_of(table.n_blocks) == 32
    check = bench.reference.check_density(
        whole, *bench.reference.loose_rows(cols, req["box"]), req["box"], 64, 64)
    assert check["rows"] > 0 and check["sum_gap"] == 0 and check["bad_pixels"] == 0, check


# ------------------------------------------------------ (c) the density spans


@pytest.mark.parametrize("devices", [1, CHIPS])
def test_density_spans_count_blocks_slots_full_and_time_the_grid(
        devices, bench, one, mesh4, traced):
    store = one if devices == 1 else mesh4
    _tile(bench, store, 1, 2, 1)  # warm: a compile is no segment
    obs.install(obs.Tracer())
    _, grid = _tile(bench, store, 1, 2, 1)
    (tr,) = traced()
    assert tr.name == "density" and grid.sum() > 0
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    a = d.attrs
    assert a["full"] == 0 and 0 < a["blocks"] <= a["slots"]
    assert {"prune", "enqueue"} <= set(a["segments"])
    assert ("devices" in a) == (devices > 1)
    (agg,) = _spans(tr, "agg")
    assert set(agg.attrs["segments"]) == {"wait", "pull"}


@pytest.fixture()
def pallas():
    """The Pallas density kernel in interpret mode: the XLA twin a CPU takes
    by default has one path and counts none."""
    conf.PALLAS_MODE.set("1")
    yield
    conf.PALLAS_MODE.clear()


def _paths(span):
    return [span.attrs[k] for k in ("skipped", "windowed", "whole")]


def test_the_agg_span_counts_the_kernels_slots_by_path(bench, one, traced, pallas):
    """PR 34: the kernel contracts a block over the window of the grid its
    rows touch, and says how many slots took which path. The ``agg`` span
    carries the three counts; they sum to the ``dispatch`` span's slots,
    and ``skipped`` holds at least the bucket's padding."""
    _, grid = _tile(bench, one, 1, 2, 1)
    (tr,) = traced()
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    (agg,) = _spans(tr, "agg")
    skipped, windowed, whole = _paths(agg)
    assert skipped + windowed + whole == d.attrs["slots"]
    assert skipped >= d.attrs["slots"] - d.attrs["blocks"]
    assert windowed + whole > 0 and grid.sum() > 0
    assert set(agg.attrs["segments"]) == {"wait", "pull"}
    conf.PALLAS_MODE.set("0")  # the XLA twin: the same grid, nothing to count
    obs.install(obs.Tracer())
    _, twin = _tile(bench, one, 1, 2, 1)
    (tr,) = traced()
    (agg,) = _spans(tr, "agg")
    assert np.array_equal(twin, grid) and "windowed" not in agg.attrs


def test_past_the_ladder_skipped_counts_the_blocks_outside_the_tile(
        bench, one, traced, pallas, monkeypatch):
    """The whole-table shape hands the kernel every block; those with no
    row in the tile cost it a mask and no contraction."""
    table = one.ds.table(one.type_name, "z2")
    _, laddered = _tile(bench, one, 0, 1, 0)
    (tr,) = traced()
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    asked = d.attrs["blocks"]
    inside = sum(_paths(_spans(tr, "agg")[0])[1:])  # candidates that hold a row of it
    assert 0 < inside <= asked < table.n_blocks
    monkeypatch.setattr(bk, "M_BUCKETS", (4, 8))
    obs.install(obs.Tracer())
    _, whole_table = _tile(bench, one, 0, 1, 0)
    (tr,) = traced()
    (d,) = [s for s in _spans(tr, "dispatch") if "slots" in (s.attrs or {})]
    assert d.attrs["full"] == 1 and d.attrs["slots"] == table.n_blocks
    skipped, windowed, whole = _paths(_spans(tr, "agg")[0])
    assert skipped == table.n_blocks - inside and windowed + whole == inside
    assert np.array_equal(whole_table, laddered)


def test_density_windowed_pct_reads_the_agg_spans_of_density_roots(bench):
    """``benchmark/layer_metrics/density_windowed_pct.py``: windowed over
    windowed + whole, summed over the window; spans that count neither (the
    program before PR 34, the XLA twin, a mesh store) are nothing to read."""
    from layer_metrics import density_windowed_pct as reader

    def span(i, root, **attrs):
        return {"id": i, "name": "agg", "root": root, "parent": 0, "attrs": attrs, "dur_s": 0.001}

    counted = [span(1, "density", skipped=600, windowed=3400, whole=96, segments={}),
               span(2, "density", skipped=20, windowed=0, whole=12),
               span(3, "density", skipped=32, windowed=0, whole=0)]
    other = [span(4, "density", segments={"wait": 0.001}), span(5, "count", windowed=9, whole=1)]
    assert reader.read({"spans": counted + other + counted}) == 100.0 * 3400 / 3508
    assert reader.read({"spans": other}) is None and reader.read({"spans": counted[2:]}) is None


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_an_untraced_density_is_the_traced_one(kernel, bench, one, traced, monkeypatch):
    """... and pays no pull beyond the grid's: the kernel's slot counts are
    fetched with the grid, in one ``device_get``, only under a span."""
    import jax

    if kernel == "pallas":
        conf.PALLAS_MODE.set("1")
    pulled = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: pulled.append(x) or real(x))
    try:
        _, with_spans = _tile(bench, one, 2, 4, 2)
        assert len(pulled) == 1 and isinstance(pulled[0], tuple) == (kernel == "pallas")
        conf.OBS_TRACE_SAMPLE.clear()
        obs.install(obs.Tracer())
        _, without = _tile(bench, one, 2, 4, 2)
    finally:
        conf.PALLAS_MODE.clear()
    assert not obs.tracer().traces() and np.array_equal(with_spans, without)
    assert len(pulled) == 2 and not isinstance(pulled[1], tuple)


# ---------------------------------------------------------------- (d) the data


class _Cols(types.SimpleNamespace):
    """Hand-made columns: what ``_store`` and the reference read of them."""

    def __len__(self):
        return len(self.x)


def _heaviest_square(bench, c):
    nx = int(round(360 / bench.osm_gpx.SQUARE_DEG))
    h, _, _ = np.histogram2d(c.y, c.x, bins=[nx // 2, nx], range=[[-90, 90], [-180, 180]])
    return h.max() / len(c)


def test_a_seed_gives_the_same_columns_twice(bench, config, cols):
    again = bench.osm_gpx.make(config, N, SEED)
    for name in ("x", "y", "t", "cx", "cy"):
        assert np.array_equal(getattr(cols, name), getattr(again, name)), name
    other = bench.osm_gpx.make(config, N, SEED + 1)
    assert not np.array_equal(cols.x, other.x)
    assert cols.attrs == {} and [a for a, _ in cols.schema] == ["dtg", "geom"]
    assert cols.row(5) == {"dtg": int(cols.t[5]), "geom": [float(cols.x[5]), float(cols.y[5])]}


@pytest.mark.parametrize("seed", SEEDS)
def test_no_zoom_0_square_holds_a_sixteenth_of_the_rows(seed, bench, config):
    osm = bench.osm_gpx
    c = osm.make(config, 1 << 20, seed)
    assert len(c) == 1 << 20 and (np.diff(c.t) >= 0).all()
    assert c.t[0] >= c.t0 and c.t[-1] < c.t0 + c.span_ms
    assert (np.abs(c.x) <= 180).all() and (np.abs(c.y) <= 90).all()
    assert len(c.cx) == osm.N_CITIES and (np.diff(c.weights) < 0).all()
    # every seed's zoom-0 tiles do the same work: the east holds its share of the
    # cities' weight to within the lightest city's, and so of the rows
    assert abs(c.weights[c.cx > 0].sum() - osm.EAST_SHARE) < c.weights[-1]
    east = osm.UNIFORM_SHARE / 2 + (1 - osm.UNIFORM_SHARE) * osm.EAST_SHARE
    assert abs((c.x > 0).mean() - east) < 0.03
    # a row's f32 rounding is the row: the device's columns and the reference's agree
    for v in (c.x, c.y):
        assert v.dtype == np.float64 and np.array_equal(v, v.astype(np.float32))
    # the bound the generator holds by arithmetic on the weights, whatever the
    # number of rows, and what these rows read under it
    bound = osm.square_bound(c.cx, c.cy, c.weights)
    assert _heaviest_square(bench, c) < bound <= osm.SQUARE_SHARE
    assert osm.SQUARE_SHARE * config["rows"] <= 2 ** 23
    # a tenth of the rows are the uniform background: outside every city's reach
    far = np.ones(len(c), bool)
    for k in range(64):
        far &= (np.abs(c.x - c.cx[k]) > 3) | (np.abs(c.y - c.cy[k]) > 3)
    assert 0.05 < far.mean() < 0.95


def test_coords_f64_leaves_the_fixes_free_and_f32_is_their_rounding(bench, config, cols):
    """``data.coords``: the configuration asks for the f32 lattice (the
    program before PR 33 has to read ``correct`` on the cell); ``"f64"``,
    the generator's default and upstream's precision, is the same draw
    unrounded."""
    assert config["data"]["coords"] == "f32"
    free = bench.osm_gpx.make(config | {"data": config["data"] | {"coords": "f64"}}, N, SEED)
    default = {k: v for k, v in config["data"].items() if k != "coords"}
    assert np.array_equal(bench.osm_gpx.make(config | {"data": default}, N, SEED).x, free.x)
    for a, b in ((free.x, cols.x), (free.y, cols.y)):
        assert not np.array_equal(a, b) and np.array_equal(a.astype(np.float32), b)
    assert np.array_equal(free.t, cols.t)
    with pytest.raises(KeyError):
        bench.osm_gpx.make(config | {"data": config["data"] | {"coords": "f16"}}, 8, SEED)


def _city_on_a_corner(lattice):
    """2^16 rows within a few 1e-4 deg of the corner that four zoom-8 tiles
    share (lon 101.25, lat 36.5625), and those four tiles."""
    rng = np.random.default_rng(11)
    s = 180.0 / 256
    n = 1 << 16
    x, y = -180 + 400 * s + rng.normal(0, 1e-4, n), -90 + 180 * s + rng.normal(0, 1e-4, n)
    if lattice:
        x, y = x.astype(np.float32).astype(np.float64), y.astype(np.float32).astype(np.float64)
    cols = _Cols(x=x, y=y, t=np.arange(n, dtype=np.int64), dtg="dtg", geom="geom")
    return cols, [(i, j) for i, j in ((399, 179), (400, 179), (399, 180), (400, 180))]


@pytest.mark.parametrize("lattice", [True, False], ids=["f32-lattice", "f64"])
def test_a_dense_city_on_a_tile_edge_is_counted_as_the_reference_counts_it(
        lattice, bench, config):
    """PERF.md section 7 (u), found on the chip at 2^27 rows and repaired in
    PR 33. A tile's edges are cell boundaries of the Z2 curve. The ranges of
    ``BBOX(geom, tile)`` were the f64 box's and covered no cell beyond the
    tile; the mask compares f32 columns against the box one f32 step wider,
    so a row half a step beyond an edge, which the mask and the reference's
    f32 semantics keep, lay in a block that was not scanned, and the grid's
    total fell between the exact f64 count and the reference's (2-22 rows of
    10^7 a tile on the chip; 200 of 2^16 here). Now the ranges cover the
    box the mask keeps, and free f64 rows read as rows on the lattice do."""
    cols, tiles = _city_on_a_corner(lattice)
    store = _store(config, cols)
    beyond = 0
    for i, j in tiles:
        req = bench.heatmap_tiles.tile_request(8, i, j, 64)
        grid = bench.density.embedded(store, req)
        d = bench.reference.check_density(
            grid, *bench.reference.loose_rows(cols, req["box"]), req["box"], 64, 64)
        exact = len(bench.reference.ref_ids(cols, req["box"]))
        assert 0 < exact <= int(grid.sum()) == d["rows"]
        assert d["sum_gap"] == 0 and d["bad_pixels"] == 0, d
        beyond += d["rows"] - exact
        # the row queries refine in f64: exact, whatever the ranges cover
        got = bench.query.embedded(store, {"op": "query", "klass": "z2", "box": req["box"]})
        assert np.array_equal(np.sort(got["ids"]), bench.reference.ref_ids(cols, req["box"]))
    # the witness bites: on free f64 rows the f32 reading holds rows the f64 one does not
    assert (beyond == 0) == lattice


def test_a_tiles_ranges_reach_the_cells_an_f32_step_beyond_its_edges(one):
    """The repair itself, on the plan: the key ranges of a tile's BBOX hold
    the z2 cell of a point half an f32 step west and south of its corner,
    which the f64 box's own ranges do not; no contained range does (a
    contained row skips the f64 refinement)."""
    idx = one.ds._indexes[one.type_name][0]
    s = 180.0 / 256
    x0, y0 = -180 + 400 * s, -90 + 180 * s
    cfg = idx.scan_config(ecql.parse(f"bbox({idx.geom}, {x0!r}, {y0!r}, {x0 + s!r}, {y0 + s!r})"))
    x = float(np.nextafter(np.float32(x0), np.float32(-np.inf))) / 2 + x0 / 2
    y = float(np.nextafter(np.float32(y0), np.float32(-np.inf))) / 2 + y0 / 2
    assert x < x0 and np.float32(x) == np.float32(x0)  # the mask keeps it
    z = int(idx.sfc.index(np.array([x]), np.array([y]))[0])
    lo, hi = cfg.range_lo.astype(object), cfg.range_hi.astype(object)
    inside = (lo <= z) & (z <= hi)
    assert inside.sum() == 1 and not cfg.range_contained[inside].any()
    f64_lo, f64_hi, _ = idx.sfc.ranges_arrays([(x0, y0, x0 + s, y0 + s)], inner=True)
    assert not ((f64_lo.astype(object) <= z) & (z <= f64_hi.astype(object))).any()


def test_centres_that_crowd_a_square_are_drawn_again(bench):
    osm = bench.osm_gpx
    w = osm.city_weights()
    assert abs(w.sum() - 1) < 1e-12 and 0.033 < w[0] < 0.034 and w[:6].sum() < 0.11
    rng = np.random.default_rng(7)
    cx = np.where(osm._east(w), 1.0, -1.0) * rng.uniform(0, 160, osm.N_CITIES)
    cy = rng.uniform(-55, 65, osm.N_CITIES)
    spread = osm.square_bound(cx, cy, w)
    cx[:3], cy[:3] = cx[0], cy[0]  # the three heaviest cities on one spot (made so, not dealt)
    assert spread < osm.SQUARE_SHARE < osm.square_bound(cx, cy, w)


# ------------------------------------------------------------- (e) the traffic


@pytest.mark.parametrize("seed", SEEDS)
def test_rounds_are_one_multiset_of_tiles_on_the_gridset(seed, bench, mix, cols):
    role = mix["roles"][0]
    per_round = role["params"]["round"]
    size = sum(per_round.values())
    assert size == 16 and per_round == {
        "tile-z0": 1, "tile-z1": 1, "tile-z2": 2, "tile-z4": 3, "tile-z6": 3, "tile-z8": 2,
        "z2": 4}
    reqs = _requests(bench, mix, cols, seed, 20 * size)
    assert len(reqs) == 20 * size
    first = {0: [], 1: []}
    for r in range(20):
        one_round = reqs[r * size:(r + 1) * size]
        assert sorted(q["klass"] for q in one_round) == sorted(
            k for k, n in per_round.items() for _ in range(n))
        for q in one_round:
            assert "win" not in q and "ring" not in q
            x0, y0, x1, y1 = q["box"]
            if q["klass"] == "z2":
                assert q["op"] == "query" and abs((x1 - x0) - 0.01) < 1e-9
                assert abs((y1 - y0) - 0.01) < 1e-9
                continue
            z = int(q["klass"][len("tile-z"):])
            s = 180.0 / 2 ** z
            assert q["op"] == "density" and q["grid"] == 256
            assert (x1 - x0, y1 - y0) == (s, s)
            i, j = (x0 + 180) / s, (y0 + 90) / s
            assert i == int(i) and j == int(j) and 0 <= i < 2 ** (z + 1) and 0 <= j < 2 ** z
            # a tile's corners are exact in f32: the device and the reference cut alike
            assert all(float(np.float32(v)) == v for v in q["box"])
            if z < 2:
                first[z].append((int(i), int(j)))
    assert first[0][:4] == [(0, 0), (1, 0), (0, 0), (1, 0)]
    assert first[1][:8] == [(i, j) for j in range(2) for i in range(4)]
    again = _requests(bench, mix, cols, seed, 20 * size)
    assert again == reqs


def test_the_warm_pass_walks_the_pyramid(bench, mix, cols):
    warm = [w for w in mix["warm"] if w.get("generator") == "tile_levels"]
    assert len(warm) == 1 and mix["warm"][0] == {"requests": 160}
    p = warm[0]["params"]
    reqs = bench.tile_levels.generate(p, None, 0, cols.context())
    whole = sum(2 ** (2 * z + 1) for z in p["whole_levels"])
    assert len(reqs) > whole and all(r["op"] == "density" and "win" not in r for r in reqs)
    assert [r["klass"] for r in reqs[:2]] == ["tile-z0", "tile-z0"]
    tiles = {(r["klass"], tuple(r["box"])) for r in reqs}
    assert len(tiles) == len(reqs)  # no tile twice
    for z in p["centre_levels"]:
        i, j = bench.heatmap_tiles.tile_at(z, cols.cx[0], cols.cy[0])
        assert (f"tile-z{z}", tuple(bench.heatmap_tiles.tile_request(z, i, j, 256)["box"])) in tiles


# ---------------------------------------------------------------- (f) the cell


def test_the_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", CELL,
         "--rows", "131072", "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == CELL and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 32
    read = set(line["rehearsal_metrics"])
    assert {"agg_wait_ms", "agg_pull_ms", "density_useful_pct", "density_full_pct"} <= read


@pytest.mark.parametrize("control", [None, "loose"])
def test_the_loose_control_is_not_correct_on_this_type(control, bench, cols, one):
    """``benchmark/control.py --control loose`` arms the f32-widened mask
    with no exact refinement. A box whose east edge lies one f64 step west
    of a row keeps the row out exactly and lets it in loosely: the row
    comparison counts the answer wrong, as it has to."""
    k = int(np.argmin(np.abs(cols.x - cols.cx[0]) + np.abs(cols.y - cols.cy[0])))
    x, y = float(cols.x[k]), float(cols.y[k])
    req = {"op": "query", "klass": "z2",
           "box": [x - 0.05, y - 0.05, float(np.nextafter(x, -np.inf)), y + 0.05]}
    undo = bench.controls.arm(control) if control else (lambda: None)
    try:
        answer = bench.query.embedded(one, req)
    finally:
        undo()
    tally = bench.check.new_tally()
    bench.query.compare(tally, cols, req, answer)
    assert tally["rows_compared"] > 0
    assert (k in answer["ids"]) == (control == "loose")
    assert tally["wrong_answers"] == (1 if control == "loose" else 0)
