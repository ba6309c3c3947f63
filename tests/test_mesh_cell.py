"""The four-shard GDELT deployment (benchmark configuration
``gdelt-events-mesh4``, cell ``gdelt-mesh4.analyst``) at a small size on
four of the forced CPU devices:

(a) a 4-device mesh store and the benchmark's plain NumPy reference give
    the same answers for every request class of the cell's mix;
(b) the shares add up: the devices' decoded rows are disjoint and their
    union is the answer, the per-device density grids sum to the grid;
(c) candidate skew splits a fused chunk and changes no answer;
(d) the mesh table's device seam carries the span model's segments and
    counters (docs/observability.md); a one-chip store's are as before;
(e) the cell itself runs through ``benchmark/rehearse.py``.

The benchmark's directories are not packages of the program: its modules
that import NumPy alone are loaded by path, as tests/test_plan_arrays.py
loads ``harness/data.py``.
"""

import importlib.util
import json
import os
import subprocess
import sys
import types
from unittest import mock

import numpy as np
import pytest

from geomesa_tpu import conf, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.parallel.dtable import DistributedIndexTable
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage.table import NO_SPANS, ScanSpans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
N, SEED, CHIPS, TILE = 1 << 15, 2_600_000_011, 4, 4096
TYPE = "gdelt"
SPEC = "actor1Name:String,numMentions:Integer,dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
SPAN_DAYS = 16
CLASSES = ("z3", "z2", "pip", "raster", "count", "density", "query_many")


def _by_path(name, *rel, shim=None):
    spec = importlib.util.spec_from_file_location(name, os.path.join(BENCH, *rel))
    mod = importlib.util.module_from_spec(spec)
    with mock.patch.dict(sys.modules, shim or {}):
        spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """harness/data.py, harness/reference.py, harness/requests.py (NumPy
    alone) and generators/notebook.py, whose ``from harness.data import``
    is given the module just loaded."""
    data = _by_path("_mesh_cell_data", "harness", "data.py")
    pkg = types.ModuleType("harness")
    pkg.data = data
    return types.SimpleNamespace(
        data=data,
        reference=_by_path("_mesh_cell_reference", "harness", "reference.py"),
        requests=_by_path("_mesh_cell_requests", "harness", "requests.py"),
        notebook=_by_path("_mesh_cell_notebook", "generators", "notebook.py",
                          shim={"harness": pkg, "harness.data": data}),
    )


@pytest.fixture(scope="module")
def cols(bench):
    """GDELT-shaped rows: ``gdelt_points``, ascending times over the span,
    id = arrival order (what the reference's binary search relies on)."""
    rng = bench.data.sub_rng(SEED, 1)
    cx, cy = bench.data.cluster_centres(rng)
    x, y = bench.data.gdelt_points(N, rng, cx, cy)
    t = np.sort(T0 + rng.integers(0, SPAN_DAYS * bench.data.DAY_MS, N))
    return types.SimpleNamespace(x=x, y=y, t=t, cx=cx, cy=cy)


def _store(cols, mesh):
    sft = FeatureType.from_spec(TYPE, SPEC)
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    sft.user_data["geomesa.z3.interval"] = "week"
    ds = DataStore(mesh=mesh, tile=TILE)
    ds.create_schema(sft)
    names = np.array(["", "POLICE", "ARMY", "COURT"])
    fc = FeatureCollection.from_columns(sft, np.arange(N, dtype=np.int64), {
        "actor1Name": names[np.arange(N) % 4], "numMentions": (np.arange(N) % 7).astype(np.int32),
        "dtg": cols.t, "geom": (cols.x.copy(), cols.y.copy())})
    ds.write(TYPE, fc, check_ids=False)
    return ds


@pytest.fixture(scope="module")
def mesh_ds(cols):
    ds = _store(cols, make_mesh(CHIPS))
    for index in ("z3", "z2"):
        table = ds.table(TYPE, index)
        assert isinstance(table, DistributedIndexTable) and table.n_devices == CHIPS
        assert table.blocks_local * CHIPS == table.n_blocks >= 2 * CHIPS
    return ds


@pytest.fixture(scope="module")
def one_ds(cols):
    return _store(cols, None)


@pytest.fixture(scope="module")
def requests(bench, cols):
    """Two rounds of the cell's own mix (``traffic/analyst-notebook.json``
    through ``generators/notebook.py``), by class."""
    with open(os.path.join(BENCH, "traffic", "analyst-notebook.json")) as fh:
        role = json.load(fh)["roles"][0]
    ctx = {"t0": T0, "span_ms": SPAN_DAYS * bench.data.DAY_MS, "cx": cols.cx, "cy": cols.cy,
           "n_rows": N, "seed": SEED, "client_index": 0}
    reqs = bench.notebook.generate(role["params"], bench.data.sub_rng(SEED, 100), 80, ctx)
    by = {}
    for r in reqs:
        by.setdefault(r["klass"], []).append(r)
    assert set(by) == set(CLASSES)
    return by


def _ids(fc):
    return np.sort(np.asarray(fc.ids).astype(np.int64))


# ---------------------------------------------------- (a) the plain reference


@pytest.mark.parametrize("klass", CLASSES)
def test_mesh_store_answers_as_the_plain_reference(klass, bench, cols, mesh_ds, requests):
    ref, rq = bench.reference, bench.requests
    hits = 0
    for req in requests[klass][:6]:
        if klass == "query_many":
            assert len(req["members"]) == 32
            outs = mesh_ds.query_many(TYPE, [rq.ecql(m) for m in req["members"]])
            for m, fc in zip(req["members"], outs):
                want = ref.ref_ids(cols, m["box"], m["win"])
                assert np.array_equal(_ids(fc), want)
                hits += len(want)
            continue
        want = ref.ref_ids(cols, req["box"], req.get("win"), req.get("ring"))
        hits += len(want)
        if klass == "count":
            assert mesh_ds.count(TYPE, rq.ecql(req)) == len(want)
        elif klass == "density":
            g = req["grid"]
            grid = np.asarray(mesh_ds.density(TYPE, rq.ecql(req), envelope=tuple(req["box"]),
                                              width=g, height=g))
            d = ref.check_density(grid, *ref.loose_rows(cols, req["box"], req["win"]),
                                  req["box"], g, g)
            assert d["sum_gap"] == 0 and d["bad_pixels"] == 0, d
        else:
            assert np.array_equal(_ids(mesh_ds.query(TYPE, rq.ecql(req))), want)
    assert hits > 0  # the mix is not answered by empty sets


# ------------------------------------------------------ (b) the shares add up


def _wide_request(requests):
    """The z3 request of the widest box and longest window."""
    return max(requests["z3"], key=lambda r: (r["box"][2] - r["box"][0], r["win"][1] - r["win"][0]))


def test_device_rows_are_disjoint_and_their_union_is_the_answer(
        bench, cols, mesh_ds, requests, monkeypatch):
    req = _wide_request(requests)
    plan = mesh_ds.planner.plan(TYPE, bench.requests.ecql(req))
    table = mesh_ds.table(TYPE, plan.index)
    dealt = []
    real = DistributedIndexTable._merge_device_rows

    def spy(self, parts):
        dealt.append([np.asarray(r) for r, _ in parts])
        return real(self, parts)

    monkeypatch.setattr(DistributedIndexTable, "_merge_device_rows", spy)
    rows, certain = table._device_scan(table._agg_blocks(plan.config), plan.config)
    (parts,) = dealt
    assert len(parts) == CHIPS and all(len(p) for p in parts)  # every shard holds some
    owners = [set(((p // table.block) % CHIPS).tolist()) for p in parts]
    assert all(len(o) == 1 for o in owners) and len(set().union(*owners)) == CHIPS
    union = np.concatenate(parts)
    assert len(np.unique(union)) == len(union)  # disjoint
    assert np.array_equal(np.sort(union), rows)
    # the wide hits of every candidate block, refined in f64, are the answer;
    # the rows the device called certain are in it
    ids = table.perm[rows].astype(np.int64)
    x0, y0, x1, y1 = req["box"]
    lo, hi = req["win"]
    keep = ((cols.x[ids] >= x0) & (cols.x[ids] <= x1) & (cols.y[ids] >= y0)
            & (cols.y[ids] <= y1) & (cols.t[ids] >= lo) & (cols.t[ids] < hi))
    want = bench.reference.ref_ids(cols, req["box"], req["win"])
    assert len(want) > 0 and np.array_equal(np.sort(ids[keep]), want)
    assert keep[certain].all()


def test_device_density_grids_sum_to_the_reference_grid(bench, cols, mesh_ds, requests):
    from geomesa_tpu.scan import aggregations

    req = max(requests["density"], key=lambda r: r["win"][1] - r["win"][0])
    g = req["grid"]
    plan = mesh_ds.planner.plan(TYPE, bench.requests.ecql(req))
    table = mesh_ds.table(TYPE, plan.index)
    config = plan.config
    bids2, n_real = table._split_blocks(table._agg_blocks(config), pad=-1)
    assert (n_real > 0).all()
    boxes, wins = table._params(config)
    names = table._agg_cols(config)
    gb = np.asarray(req["box"], np.float32)
    shards = {k: np.asarray(table.cols3[k]) for k in names}  # [D, blocks a device, SUB, 128]
    grids = [np.asarray(aggregations.block_density(
        tuple(shards[k][d] for k in names), bids2[d], boxes, wins, gb, width=g, height=g,
        **table._kernel_kwargs(config, names))) for d in range(CHIPS)]
    assert sum(int(x.sum() > 0) for x in grids) == CHIPS  # no shard is idle
    total = np.sum(grids, axis=0)
    merged = np.asarray(table.density(config, req["box"], g, g))  # the psum
    assert np.array_equal(total, merged)
    d = bench.reference.check_density(
        total, *bench.reference.loose_rows(cols, req["box"], req["win"]), req["box"], g, g)
    assert d["rows"] > 0 and d["sum_gap"] == 0 and d["bad_pixels"] == 0, d


# ------------------------------------------------------------------- tracing


@pytest.fixture()
def traced():
    """Every root retained by a fresh tracer; knobs restored after."""
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    conf.OBS_SLOW_MS.set(0.0)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    conf.OBS_SLOW_MS.clear()
    obs.install(obs.Tracer())


def _spans(trace, name):
    return [s for s in [trace.root] + list(trace.spans) if s.name == name]


# ------------------------------------------------------------------ (c) skew


def test_skew_splits_a_fused_chunk_and_changes_no_answer(mesh_ds, traced):
    """Twenty members whose candidate blocks all lie on device 0 (global
    blocks 0 and 4 of 8) overflow that device's ``fused_slots``: the chunk
    is cut in two, ``splits`` counts it, and every member decodes what the
    per-query route decodes."""
    plan = mesh_ds.planner.plan(TYPE, "bbox(geom, -170.0, -80.0, 170.0, 80.0)")
    assert plan.index == "z2"
    table, config = mesh_ds.table(TYPE, "z2"), plan.config
    on_one = np.arange(0, table.n_blocks, CHIPS, dtype=np.int64)  # residue class 0
    n_members = table.fused_slots // len(on_one) + 4
    assert n_members * len(on_one) > table.fused_slots >= (n_members + 1) // 2 * len(on_one)
    spans = ScanSpans(table.candidate_spans_split(config)[0], NO_SPANS)
    members = [(j, config, on_one, spans) for j in range(n_members)]
    names = table._scan_cols(config)
    finishes = [None] * n_members
    with obs.tracer().trace("query_many"):
        with obs.span("dispatch") as sp:
            table._submit_fused_chunk(members, names, True, False, finishes, None)
        fused = [f() for f in finishes]
    a = sp.attrs
    assert a["splits"] == 1 and a["groups"] == 2 and a["devices"] == CHIPS
    assert a["blocks"] == n_members * len(on_one) == a["blocks_max"]  # all on one device
    assert a["slots"] == 2 * CHIPS * table.fused_slots
    single = table._make_finish(
        table._device_scan_submit(on_one, config), config, spans, None)()
    assert len(single[0]) > 0
    for rows, certain in fused:
        assert np.array_equal(rows, single[0]) and np.array_equal(certain, single[1])


# ----------------------------------------------------------------- (d) spans

DISPATCH = {"prune", "deal", "enqueue"}
COUNTERS = {"blocks", "slots", "devices", "blocks_max"}


def _run(ds, op, bench, requests):
    rq = bench.requests
    if op == "query":
        return ds.query(TYPE, rq.ecql(_wide_request(requests)))
    if op == "count":
        return ds.count(TYPE, rq.ecql(_wide_request(requests)))
    if op == "density":
        req = requests["density"][0]
        return ds.density(TYPE, rq.ecql(req), envelope=tuple(req["box"]), width=64, height=64)
    return ds.query_many(TYPE, [rq.ecql(m) for m in requests["query_many"][0]["members"]])


@pytest.mark.parametrize("op", ["query", "query_many", "count", "density"])
def test_mesh_spans_carry_the_deal_and_the_merge(op, bench, mesh_ds, requests, traced):
    _run(mesh_ds, op, bench, requests)  # warm: a compile is no segment
    obs.install(obs.Tracer())
    _run(mesh_ds, op, bench, requests)
    (tr,) = traced()
    assert tr.name == op
    dispatches = [s for s in _spans(tr, "dispatch") if (s.attrs or {}).get("blocks")]
    assert dispatches
    for s in dispatches:
        a = s.attrs
        assert DISPATCH <= set(a["segments"]), a
        assert COUNTERS <= set(a) and a["devices"] == CHIPS
        assert a["blocks_max"] * a["devices"] >= a["blocks"] >= a["blocks_max"] >= 1
        assert a["slots"] >= a["blocks"] and a["slots"] % CHIPS == 0
    if op == "density":
        (agg,) = _spans(tr, "agg")
        assert set(agg.attrs["segments"]) == {"wait", "pull"}
        return
    scans = _spans(tr, "scan")
    pulled = [s for s in scans if "pull" in s.attrs.get("segments", {})]
    assert pulled
    for s in pulled:
        assert {"wait", "pull", "bits", "merge"} <= set(s.attrs["segments"])
    if op == "query_many":
        top = next(s for s in dispatches if s.parent_id == tr.root.span_id)
        assert top.attrs["groups"] >= 1 and "splits" not in top.attrs
        assert len(scans) == 32 and sum(s.attrs.get("group", 0) for s in pulled) >= 2
        for s in scans:  # a member that did not pull still decodes and merges
            segs = set(s.attrs.get("segments", {}))
            assert not segs or {"bits", "merge"} <= segs


@pytest.mark.parametrize("op", ["query", "query_many", "density"])
def test_one_chip_spans_are_as_before(op, bench, one_ds, requests, traced):
    _run(one_ds, op, bench, requests)
    obs.install(obs.Tracer())
    _run(one_ds, op, bench, requests)
    (tr,) = traced()
    for s in [tr.root] + list(tr.spans):
        a = s.attrs or {}
        assert not {"devices", "blocks_max", "splits"} & set(a)
        assert not {"deal", "merge"} & set(a.get("segments", {}))
    for s in _spans(tr, "dispatch"):
        if s.attrs.get("blocks"):
            assert set(s.attrs["segments"]) == {"prune", "enqueue"}
    for s in _spans(tr, "scan"):
        segs = set(s.attrs.get("segments", {}))
        assert segs in ({"wait", "pull", "bits"}, {"bits"}, set())
    if op == "density":
        assert set(_spans(tr, "agg")[0].attrs["segments"]) == {"wait", "pull"}


@pytest.mark.parametrize("op", ["query", "query_many"])
def test_a_plan_costed_on_a_swapped_mesh_table_recomputes_its_spans(
        op, bench, cols, requests, traced):
    """The spans ``cost()`` leaves in a config's slot name the mesh table
    they index: the dispatch that follows finds them (``spans_reused`` =
    members), and after a write has swapped the table the same plans
    recompute theirs and answer with the new rows too."""
    ds = _store(cols, make_mesh(CHIPS))
    members = requests["query_many"][0]["members"][:8] if op == "query_many" else [
        _wide_request(requests)]
    filters = [bench.requests.ecql(m) for m in members]

    def run(plans):
        with obs.tracer().trace(op):
            outs = ds.planner.execute_many(plans) if op == "query_many" else [
                ds.planner.execute(plans[0])]
        return outs, [s for s in _spans(traced()[-1], "dispatch") if "spans_reused" in s.attrs]

    plans = [ds.planner.plan(TYPE, f) for f in filters]
    old = {p.index: ds.table(TYPE, p.index) for p in plans}
    outs, dispatches = run(plans)
    assert sum(s.attrs["spans_reused"] for s in dispatches) == len(plans)
    for m, fc in zip(members, outs):
        assert np.array_equal(_ids(fc), bench.reference.ref_ids(cols, m["box"], m["win"]))
    # the same rows again under new ids: every answer doubles
    sft = ds.get_schema(TYPE)
    names = np.array(["", "POLICE", "ARMY", "COURT"])
    conf.COMPACT_MIN_ROWS.set(1000)
    try:
        ds.write(TYPE, FeatureCollection.from_columns(sft, np.arange(N, 2 * N, dtype=np.int64), {
            "actor1Name": names[np.arange(N) % 4],
            "numMentions": (np.arange(N) % 7).astype(np.int32),
            "dtg": cols.t, "geom": (cols.x.copy(), cols.y.copy())}), check_ids=False)
    finally:
        conf.COMPACT_MIN_ROWS.clear()
    for index, table in old.items():
        new = ds.table(TYPE, index)
        assert new is not table and isinstance(new, DistributedIndexTable)
    outs, dispatches = run(plans)
    assert dispatches and all(s.attrs["spans_reused"] == 0 for s in dispatches)
    hits = 0
    for m, fc in zip(members, outs):
        want = bench.reference.ref_ids(cols, m["box"], m["win"])
        assert np.array_equal(_ids(fc), np.sort(np.concatenate([want, want + N])))
        hits += len(want)
    assert hits > 0


# -------------------------------------------------------------- (e) the cell


def test_the_cell_rehearses_on_four_cpu_devices():
    """``benchmark/rehearse.py`` is the chip run's ``run_cell`` with the look
    for the chip skipped: the configuration, its store module, the mix, the
    check against the plain reference and the new readers, end to end."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "rehearse.py"), "--workload", "gdelt-mesh4.analyst",
         "--rows", "32768", "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["workload"] == "gdelt-mesh4.analyst" and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["count"] == 4
    got = line["rehearsal_metrics"]
    for name in ("mesh_deal_ms", "mesh_merge_ms", "shard_skew"):
        assert name in got, sorted(got)
    assert got["shard_skew"]["value"] >= 1.0
    assert 0 < got["scan_useful_pct"]["value"] <= 100
    assert "mesh_scan_roofline" not in got  # needs a device trace
    assert line["metrics"] == {}  # a CPU run yields no device number
