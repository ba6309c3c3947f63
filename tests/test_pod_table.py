"""PodIndexTable (docs/distributed.md): per-host shard ownership must be
INVISIBLE to every read surface.

The pinned contract (ISSUE 20): a DataStore over a host group returns
results **bit-identical** to the same DataStore over the flat
single-process mesh on the same devices — same row sets, same ids, same
counts, same density grids, same bounds, same explain-visible plan — for
the full z2/z3/xz matrix of box and polygon configs, for the per-query
path AND the cross-host fused dispatch (query_many), on every available
driver. The sim driver runs everywhere (CPU CI); the distributed driver
skips via :class:`PodUnsupported` where the backend has no multi-process
collectives.
"""

import numpy as np
import pytest

from geomesa_tpu import fault
from geomesa_tpu import geometry as geo
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.pod import PodUnsupported, make_host_group
from geomesa_tpu.pod.table import PodIndexTable
from geomesa_tpu.sft import FeatureType

DAY = 86400_000
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DUR = "dtg DURING 2024-01-03T00:00:00Z/2024-01-12T00:00:00Z"
TRI = "POLYGON((-30 -20, 30 -20, 40 25, -25 20, -30 -20))"

# the z2/z3 matrix: box and polygon, timeless and time-bounded, plus the
# attribute / union / id / empty / full plan kinds riding along
Q_PTS = [
    "bbox(geom, -10, -10, 10, 10)",                      # z2 box
    f"intersects(geom, {TRI})",                          # z2 polygon
    f"bbox(geom, 5, 5, 40, 30) AND {DUR}",               # z3 box
    f"intersects(geom, {TRI}) AND {DUR}",                # z3 polygon
    "kind = 'b'",                                        # attribute index
    "bbox(geom, -5, -5, 5, 5) OR kind = 'c'",            # union plan
    "IN ('17', '99', 'nope')",                           # id lookup
    "bbox(geom, 170, 80, 175, 85)",                      # empty
    "INCLUDE",                                           # full scan
]

# the xz matrix (extent geometries): box and polygon, both epochs
Q_POLY = [
    "bbox(geom, -10, -10, 10, 10)",                      # xz2 box
    f"intersects(geom, {TRI})",                          # xz2 polygon
    f"bbox(geom, -20, -20, 30, 25) AND {DUR}",           # xz3 box
    f"intersects(geom, {TRI}) AND {DUR}",                # xz3 polygon
]


@pytest.fixture(scope="module", params=["sim", "distributed"])
def group(request):
    try:
        return make_host_group(hosts=4, devices_per_host=2, driver=request.param)
    except PodUnsupported as e:
        pytest.skip(f"pod driver {request.param!r} unavailable: {e}")


def _point_store(mesh, n=20_000, seed=7):
    sft = FeatureType.from_spec(
        "pts", "kind:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    # tile=64: enough blocks that every host owns a real span and the
    # batch path genuinely packs fused chunks instead of routing singly
    ds = DataStore(tile=64, mesh=mesh)
    ds.create_schema(sft)
    rng = np.random.default_rng(seed)
    ds.write("pts", FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {
            "kind": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            "dtg": T0 + rng.integers(0, 20 * DAY, n),
            "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)),
        },
    ))
    return ds


def _poly_store(mesh, n=8000, seed=9):
    sft = FeatureType.from_spec("bld", "dtg:Date,*geom:Polygon:srid=4326")
    ds = DataStore(tile=64, mesh=mesh)
    ds.create_schema(sft)
    rng = np.random.default_rng(seed)
    x0 = rng.uniform(-60, 59, n)
    y0 = rng.uniform(-45, 44, n)
    polys = geo.PackedGeometryColumn.from_boxes(
        x0, y0, x0 + rng.uniform(0.01, 0.8, n), y0 + rng.uniform(0.01, 0.6, n)
    )
    ds.write("bld", FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {"dtg": T0 + rng.integers(0, 20 * DAY, n), "geom": polys},
    ))
    return ds


@pytest.fixture(scope="module")
def stores(group):
    """(pod store, flat-mesh referee) pairs for the point and extent
    schemas — the equal-device-budget differential the acceptance pins."""
    return {
        "pts": (_point_store(group), _point_store(group.flat_mesh())),
        "bld": (_poly_store(group), _poly_store(group.flat_mesh())),
    }


def _ids(fc):
    return sorted(np.asarray(fc.ids, dtype=str).tolist())


class TestDifferentialMatrix:
    def test_pod_tables_built(self, stores):
        pod, _ = stores["pts"]
        tables = [t for (tn, _), t in pod._tables.items() if tn == "pts"]
        assert any(isinstance(t, PodIndexTable) for t in tables)

    @pytest.mark.parametrize("qi", range(len(Q_PTS)))
    def test_point_queries_bit_identical(self, stores, qi):
        pod, flat = stores["pts"]
        q = Q_PTS[qi]
        a, b = pod.query("pts", q), flat.query("pts", q)
        assert _ids(a) == _ids(b)
        assert pod.count("pts", q) == flat.count("pts", q) == len(b)

    @pytest.mark.parametrize("qi", range(len(Q_POLY)))
    def test_extent_queries_bit_identical(self, stores, qi):
        pod, flat = stores["bld"]
        q = Q_POLY[qi]
        assert _ids(pod.query("bld", q)) == _ids(flat.query("bld", q))
        assert pod.count("bld", q) == flat.count("bld", q)

    def test_results_nonvacuous(self, stores):
        pod, _ = stores["pts"]
        hits = [len(pod.query("pts", q)) for q in Q_PTS[:6]]
        assert all(h > 0 for h in hits), hits
        podp, _ = stores["bld"]
        assert all(len(podp.query("bld", q)) > 0 for q in Q_POLY)

    @pytest.mark.parametrize("tn,queries", [("pts", Q_PTS), ("bld", Q_POLY)])
    def test_explain_plan_shape_identical(self, stores, tn, queries):
        """The pod is a storage-layer move: the planner's explain trace
        (index choice, strategy, range counts) must be byte-identical
        to the flat mesh's."""
        pod, flat = stores[tn]
        for q in queries:
            assert pod.explain(tn, q) == flat.explain(tn, q)

    def test_density_and_bounds_identical(self, stores):
        pod, flat = stores["pts"]
        env = (-60, -45, 60, 45)
        for q in (Q_PTS[0], Q_PTS[2]):
            np.testing.assert_array_equal(
                pod.density("pts", q, envelope=env, width=32, height=16),
                flat.density("pts", q, envelope=env, width=32, height=16),
            )
            assert pod.bounds("pts", q) == flat.bounds("pts", q)


class TestFusedCrossHost:
    def test_query_many_fused_and_identical(self, stores, monkeypatch):
        """The cross-host fused dispatch: one batched leg per owning
        host per chunk (shard-level ``_fused_raw_finishes``), merged at
        the coordinator — and the batch must actually TAKE the fused
        path, not fall back to per-query routing."""
        from geomesa_tpu.parallel.dtable import DistributedIndexTable

        pod, flat = stores["pts"]
        calls = {"raw": 0}
        orig = DistributedIndexTable._fused_raw_finishes

        def spy(self, *a, **kw):
            calls["raw"] += 1
            return orig(self, *a, **kw)

        monkeypatch.setattr(DistributedIndexTable, "_fused_raw_finishes", spy)
        # >8 same-variant members per table: the packer must form real
        # fused chunks (route-single handles only tiny batches)
        rng = np.random.default_rng(21)
        boxes = []
        for _ in range(10):
            x0, y0 = rng.uniform(-55, 30), rng.uniform(-40, 20)
            boxes.append(
                f"bbox(geom, {x0:.3f}, {y0:.3f}, {x0 + 18:.3f}, {y0 + 14:.3f})"
            )
        batch = boxes + Q_PTS
        outs = pod.query_many("pts", batch)
        refs = flat.query_many("pts", batch)
        assert sum(len(o) for o in outs[:10]) > 0
        for a, b in zip(outs, refs):
            assert _ids(a) == _ids(b)
        assert calls["raw"] >= 1, "pod batch never took the fused dispatch"

    def test_extent_query_many_identical(self, stores):
        pod, flat = stores["bld"]
        for a, b in zip(pod.query_many("bld", Q_POLY),
                        flat.query_many("bld", Q_POLY)):
            assert _ids(a) == _ids(b)


class TestPodFaultPoints:
    def test_dispatch_fault_surfaces_and_recovers(self, stores):
        """pod.dispatch / pod.join are real seams: an injected IO error
        on one host's scan leg propagates to the caller, and the next
        query — same table, same compiled kernels — is clean."""
        pod, flat = stores["pts"]
        with fault.inject("pod.dispatch", kind="io_error", times=1):
            with pytest.raises(OSError):
                pod.query("pts", Q_PTS[0])
        assert _ids(pod.query("pts", Q_PTS[0])) == _ids(flat.query("pts", Q_PTS[0]))

    def test_join_fault_surfaces_and_recovers(self, stores):
        pod, flat = stores["pts"]
        with fault.inject("pod.join", kind="io_error", times=1):
            with pytest.raises(OSError):
                pod.count("pts", Q_PTS[0])
        assert pod.count("pts", Q_PTS[0]) == flat.count("pts", Q_PTS[0])
