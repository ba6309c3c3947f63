"""Multi-device sharded scan: result equality with the single-device path.

Runs on the forced 8-device CPU mesh (conftest.py), mirroring the reference
TestGeoMesaDataStore strategy: the full planner + distributed scan stack
with zero infra.
"""

import numpy as np
import pytest

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.parallel import make_mesh
from geomesa_tpu.sft import FeatureType

SPEC = "name:String,age:Int,dtg:Date,*geom:Point:srid=4326"


def _points(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    t = t0 + rng.integers(0, 45 * 86400_000, n)
    return x, y, t


def _store(mesh=None, n=4000, tile=64):
    sft = FeatureType.from_spec("pts", SPEC)
    ds = DataStore(tile=tile, mesh=mesh)
    ds.create_schema(sft)
    x, y, t = _points(n)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(n)],
        {
            "name": np.array([f"n{i % 17}" for i in range(n)]),
            "age": np.arange(n) % 90,
            "dtg": t,
            "geom": (x, y),
        },
    )
    ds.write("pts", fc)
    return ds


QUERIES = [
    "bbox(geom, -20, -10, 40, 35) AND dtg DURING 2024-01-03T00:00:00Z/2024-01-20T12:00:00Z",
    "bbox(geom, -180, -90, 180, 90) AND dtg DURING 2024-01-01T00:00:00Z/2024-02-15T00:00:00Z",
    "bbox(geom, 10, 10, 11, 11)",
    "bbox(geom, -150, -80, 150, 80) AND age < 30",
    "bbox(geom, -20, -10, 40, 35) AND dtg DURING 2024-01-03T00:00:00Z/2024-01-20T12:00:00Z AND name = 'n3'",
]


@pytest.fixture(scope="module")
def stores():
    return _store(), _store(make_mesh(8))


@pytest.mark.parametrize("q", QUERIES)
def test_distributed_matches_single(stores, q):
    single, dist = stores
    a = sorted(single.query("pts", q).ids.tolist())
    b = sorted(dist.query("pts", q).ids.tolist())
    assert a == b
    assert len(a) > 0  # queries chosen to hit


def test_distributed_matches_brute_force(stores):
    single, dist = stores
    q = QUERIES[0]
    from geomesa_tpu.filter import ecql

    f = ecql.parse(q)
    fc = dist.features("pts")
    mask = np.asarray(f.evaluate(fc.batch))
    expect = sorted(fc.ids[mask].tolist())
    got = sorted(dist.query("pts", q).ids.tolist())
    assert got == expect


def test_distributed_count(stores):
    single, dist = stores
    # loose count >= exact hits; equal here because the bbox test is precise
    # for points up to f32 widening
    q = "bbox(geom, -20, -10, 40, 35)"
    assert dist.count("pts", q) == single.count("pts", q)


def test_distributed_empty_result(stores):
    _, dist = stores
    out = dist.query("pts", "bbox(geom, 10.00001, 10.00001, 10.00002, 10.00002) AND dtg DURING 2030-01-01T00:00:00Z/2030-01-02T00:00:00Z")
    assert len(out) == 0


def test_mesh_sizes():
    # distributed path works for mesh sizes that do not divide tile counts
    for d in (2, 3, 5):
        ds = _store(make_mesh(d), n=1000, tile=32)
        single = _store(n=1000, tile=32)
        for q in QUERIES[:2]:
            assert sorted(ds.query("pts", q).ids.tolist()) == sorted(
                single.query("pts", q).ids.tolist()
            )


def test_mesh_fused_shape_is_a_function_of_local_block_count():
    """A four-device table's fused dispatch is four lists of
    bucket_of(blocks_local) slots: the per-device bucket comes from the
    local block count alone, the packer fills four times that."""
    from geomesa_tpu.scan import block_kernels as bk
    from geomesa_tpu.storage.table import FUSED_CHUNK_SLOTS

    ds = _store(make_mesh(4), n=600_000, tile=64)
    table = ds.table("pts", "z3")
    # past the ladder's floor, so the bucket is the block count's own
    assert table.n_devices == 4 and table.blocks_local > bk.M_BUCKETS[0]
    assert table.fused_slots == min(
        FUSED_CHUNK_SLOTS, bk.bucket_of(table.blocks_local)
    )
    assert table.fused_pack_capacity == 4 * table.fused_slots


def test_distributed_certainty_vector(stores):
    """The mesh table returns the same exactness tier as the single-chip
    table: identical ordinals AND identical certain flags (VERDICT r3 #1)."""
    from geomesa_tpu.filter import ecql

    single, dist = stores
    for q in QUERIES[:3]:
        f = ecql.parse(q)
        idx = single.indexes("pts")[0]
        cfg = idx.scan_config(f)
        if cfg is None:
            continue
        o1, c1 = single.table("pts", "z3").scan(cfg)
        o2, c2 = dist.table("pts", "z3").scan(cfg)
        assert o1.tolist() == o2.tolist()
        assert c1.tolist() == c2.tolist()
    # the tier is live: at least one query has certain rows
    f = ecql.parse(QUERIES[0])
    cfg = single.indexes("pts")[0].scan_config(f)
    _, c = dist.table("pts", "z3").scan(cfg)
    assert c.any()


def test_distributed_zero_recompiles(stores):
    """After one warmup pass, a mixed query batch triggers NO new XLA
    compiles on the mesh path (the round-2 cap-retry recompile loop is
    gone)."""
    import logging

    _, dist = stores
    import jax

    mix = QUERIES * 4  # 20 queries
    for q in mix:  # warmup: compile every (bucket, flags) variant once
        dist.query("pts", q)
    jax.config.update("jax_log_compiles", True)
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    loggers = [logging.getLogger(n) for n in ("jax._src.dispatch", "jax._src.interpreters.pxla", "jax._src.compiler")]
    for lg in loggers:
        lg.addHandler(handler)
        lg.setLevel(logging.DEBUG)
    try:
        for q in mix:
            dist.query("pts", q)
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg in loggers:
            lg.removeHandler(handler)
    compiles = [m for m in records if "Compiling" in m]
    assert compiles == [], f"unexpected recompiles: {compiles}"


def test_distributed_density_and_bounds(stores):
    single, dist = stores
    q = QUERIES[0]
    g1 = single.density("pts", q, envelope=(-20, -10, 40, 35), width=32, height=16)
    g2 = dist.density("pts", q, envelope=(-20, -10, 40, 35), width=32, height=16)
    assert np.array_equal(g1, g2)
    assert g1.sum() > 0
    b1 = single.bounds("pts", q, estimate=True)
    b2 = dist.bounds("pts", q, estimate=True)
    assert b1 == b2 and b1 is not None


def test_mesh_delta_tier():
    """Mesh stores absorb small writes in the host delta tier (no forced
    per-write compaction) and still answer exactly."""
    from geomesa_tpu.storage.delta import TieredTable

    mesh = make_mesh(4)
    single, dist = _store(n=2000), _store(mesh, n=2000)
    sft = single.get_schema("pts")
    x, y, t = _points(300, seed=9)
    fc = FeatureCollection.from_columns(
        sft,
        [f"extra{i}" for i in range(300)],
        {
            "name": np.array([f"n{i % 17}" for i in range(300)]),
            "age": np.arange(300) % 90,
            "dtg": t,
            "geom": (x, y),
        },
    )
    single.write("pts", fc)
    dist.write("pts", fc)
    # the second write stayed in the delta (below the compaction threshold)
    assert isinstance(dist.table("pts", "z3"), TieredTable)
    for q in QUERIES[:3]:
        assert sorted(single.query("pts", q).ids.tolist()) == sorted(
            dist.query("pts", q).ids.tolist()
        )
    assert dist.count("pts", "bbox(geom, -20, -10, 40, 35)") == single.count(
        "pts", "bbox(geom, -20, -10, 40, 35)"
    )


def test_extent_geometries_distributed():
    # polygons via XZ2/XZ3 on the mesh
    sft = FeatureType.from_spec("polys", "name:String,dtg:Date,*geom:Polygon:srid=4326")
    rng = np.random.default_rng(3)
    rows = []
    for i in range(300):
        cx, cy = rng.uniform(-170, 170), rng.uniform(-80, 80)
        w, h = rng.uniform(0.1, 4, 2)
        rows.append(
            {
                "__id__": str(i),
                "name": f"p{i}",
                "dtg": int(np.datetime64("2024-01-05", "ms").astype(np.int64) + i * 3600_000),
                "geom": f"POLYGON(({cx} {cy}, {cx + w} {cy}, {cx + w} {cy + h}, {cx} {cy + h}, {cx} {cy}))",
            }
        )
    q = "bbox(geom, -30, -30, 30, 30)"
    out = {}
    for mesh in (None, make_mesh(4)):
        ds = DataStore(tile=32, mesh=mesh)
        ds.create_schema(sft)
        ds.write("polys", rows)
        out[mesh is None] = sorted(ds.query("polys", q).ids.tolist())
    assert out[True] == out[False]
    assert len(out[True]) > 0


def test_union_plans_on_mesh():
    """Cross-kind OR union plans execute per-branch mesh scans."""
    sft = FeatureType.from_spec(
        "um", "name:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    rng = np.random.default_rng(12)
    n = 3000
    t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
    fc = FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {"name": np.array([f"n{i % 11}" for i in range(n)]),
         "dtg": t0 + rng.integers(0, 30 * 86400_000, n),
         "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n))},
    )
    q = "bbox(geom, -20, -15, 10, 10) OR name = 'n4'"
    out = {}
    for mesh in (None, make_mesh(8)):
        ds = DataStore(mesh=mesh)
        ds.create_schema(sft)
        ds.write("um", fc)
        plan = ds.planner.plan("um", q)
        assert plan.union is not None
        out[mesh is None] = sorted(ds.query("um", q).ids.tolist())
    assert out[True] == out[False] and len(out[True]) > 0


def test_timeout_on_mesh():
    from geomesa_tpu.planning.errors import QueryTimeout
    from geomesa_tpu.planning.hints import QueryHints

    ds = _store(make_mesh(4), n=2000)
    q = QUERIES[0]
    with pytest.raises(QueryTimeout):
        ds.query("pts", q, hints=QueryHints(timeout=1e-9))
    assert len(ds.query("pts", q, hints=QueryHints(timeout=60.0))) > 0


def test_mesh_store_persist_roundtrip(tmp_path):
    """Mesh stores persist and reload (tables rebuilt sharded)."""
    from geomesa_tpu.storage import persist

    mesh = make_mesh(4)
    ds = _store(mesh, n=2500)
    root = str(tmp_path / "cat")
    persist.save(ds, root)
    back = persist.load(root, mesh=mesh)
    from geomesa_tpu.parallel import DistributedIndexTable

    assert isinstance(back._tables[("pts", "z3")], DistributedIndexTable)
    for q in QUERIES[:3]:
        assert sorted(back.query("pts", q).ids.tolist()) == sorted(
            ds.query("pts", q).ids.tolist()
        )


def test_multihost_mesh_layout_and_equality():
    """make_multihost_mesh: host-major 1-D ordering; a store sharded over
    the 2x4 'multi-host' mesh answers identically to single-device."""
    from geomesa_tpu.parallel import make_multihost_mesh

    mesh = make_multihost_mesh(hosts=2, devices_per_host=4)
    assert mesh.devices.shape == (8,)
    import jax
    assert list(mesh.devices) == jax.devices()[:8]  # one process: sliced

    # the grouping logic itself, against stub multi-process devices
    from collections import namedtuple

    from geomesa_tpu.parallel.mesh import _host_major

    D = namedtuple("D", "name process_index")
    stub = [D(f"d{h}_{i}", h) for i in (0, 1, 2, 3) for h in (1, 0)]
    got = _host_major(stub, hosts=2, devices_per_host=3)
    assert [d.name for d in got] == [
        "d0_0", "d0_1", "d0_2", "d1_0", "d1_1", "d1_2"
    ]
    with pytest.raises(ValueError, match="has 4 devices, need 5"):
        _host_major(stub, hosts=2, devices_per_host=5)

    sft = FeatureType.from_spec("mh", "dtg:Date,*geom:Point:srid=4326")
    rng = np.random.default_rng(8)
    n = 4000
    t0 = int(np.datetime64("2024-02-01", "ms").astype(np.int64))
    fc_cols = {
        "dtg": t0 + rng.integers(0, 86400_000 * 10, n),
        "geom": (rng.uniform(-90, 90, n), rng.uniform(-45, 45, n)),
    }
    q = ("bbox(geom, -20, -20, 20, 20) AND dtg DURING "
         "2024-02-02T00:00:00Z/2024-02-06T00:00:00Z")
    out = {}
    for mesh_ in (None, mesh):
        ds = DataStore(tile=32, mesh=mesh_)
        ds.create_schema(sft)
        ds.write("mh", FeatureCollection.from_columns(
            sft, [str(i) for i in range(n)], dict(fc_cols)))
        out[mesh_ is None] = sorted(ds.query("mh", q).ids.tolist())
    assert out[True] == out[False] and len(out[True]) > 0

    with pytest.raises(ValueError):
        make_multihost_mesh(hosts=3)  # 8 devices don't divide over 3
