"""Multi-index union plans for cross-kind ORs (reference FilterSplitter
DNF options, FilterSplitter.scala:61-147): `bbox(...) OR attr = 'x'` runs
one scan per disjunct on its own index and dedup-unions the results,
instead of falling to a full host scan."""

import numpy as np
import pytest

from geomesa_tpu import DataStore, FeatureCollection, FeatureType
from geomesa_tpu.filter import ecql
from geomesa_tpu.planning.planner import QueryGuardError

SPEC = "name:String:index=true,age:Int,dtg:Date,*geom:Point:srid=4326"
N = 6000


@pytest.fixture(scope="module")
def ds():
    sft = FeatureType.from_spec("u", SPEC)
    store = DataStore()
    store.create_schema(sft)
    rng = np.random.default_rng(8)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    fc = FeatureCollection.from_columns(
        sft,
        [str(i) for i in range(N)],
        {
            "name": np.array([f"n{i % 23}" for i in range(N)]),
            "age": np.arange(N) % 80,
            "dtg": t0 + rng.integers(0, 30 * 86400_000, N),
            "geom": (rng.uniform(-60, 60, N), rng.uniform(-45, 45, N)),
        },
    )
    store.write("u", fc)
    return store


def brute(ds, q):
    fc = ds.features("u")
    mask = np.asarray(ecql.parse(q).evaluate(fc.batch))
    return sorted(fc.ids[mask].tolist())


class TestUnionPlans:
    def test_bbox_or_attribute(self, ds):
        q = "bbox(geom, -20, -15, 10, 10) OR name = 'n3'"
        plan = ds.planner.plan("u", q)
        assert plan.union is not None and len(plan.union) == 2
        assert plan.strategy.startswith("union(")
        got = sorted(ds.query("u", q).ids.tolist())
        assert got == brute(ds, q)
        assert len(got) > 0

    def test_dedup_overlapping_branches(self, ds):
        # many n5 rows also fall inside the box: union must not double-count
        q = "bbox(geom, -60, -45, 60, 45) OR name = 'n5'"
        out = ds.query("u", q)
        assert len(out.ids) == len(set(out.ids.tolist()))
        assert sorted(out.ids.tolist()) == brute(ds, q)

    def test_three_way_union_with_conjunctions(self, ds):
        q = (
            "(bbox(geom, -20, -15, 10, 10) AND dtg DURING "
            "2024-01-02T00:00:00Z/2024-01-12T00:00:00Z) "
            "OR name = 'n7' OR name = 'n11'"
        )
        plan = ds.planner.plan("u", q)
        assert plan.union is not None and len(plan.union) == 3
        assert sorted(ds.query("u", q).ids.tolist()) == brute(ds, q)

    def test_disjoint_branch_dropped(self, ds):
        # name='a' AND name='b' is unsatisfiable: only the bbox branch scans
        q = "bbox(geom, -20, -15, 10, 10) OR (name = 'a' AND name = 'b')"
        plan = ds.planner.plan("u", q)
        assert plan.union is None  # one live branch -> its single-index plan
        assert sorted(ds.query("u", q).ids.tolist()) == brute(ds, q)

    def test_all_branches_disjoint(self, ds):
        q = "(name = 'a' AND name = 'b') OR (name = 'c' AND name = 'd')"
        assert len(ds.query("u", q)) == 0

    def test_unindexable_disjunct_falls_back_to_full_scan(self, ds):
        # `age > 70` has no attribute index: a union would still need a
        # full scan for that branch, so the planner keeps one full scan
        q = "bbox(geom, -20, -15, 10, 10) OR age > 70"
        plan = ds.planner.plan("u", q)
        assert plan.union is None and plan.strategy == "full-scan"
        assert sorted(ds.query("u", q).ids.tolist()) == brute(ds, q)

    def test_guard_allows_union_blocks_full_scan(self, ds):
        ds.block_full_table_scans = True
        try:
            out = ds.query("u", "bbox(geom, -20, -15, 10, 10) OR name = 'n3'")
            assert len(out) > 0
            with pytest.raises(QueryGuardError):
                ds.query("u", "bbox(geom, -20, -15, 10, 10) OR age > 70")
        finally:
            ds.block_full_table_scans = False

    def test_not_pushdown(self, ds):
        # NOT(a AND b) -> NOT a OR NOT b; neither side indexable -> full
        # scan, but results stay exact
        q = "NOT (name = 'n1' AND age = 5)"
        assert sorted(ds.query("u", q).ids.tolist()) == brute(ds, q)

    def test_explain_shows_union(self, ds):
        text = ds.explain("u", "bbox(geom, -20, -15, 10, 10) OR name = 'n3'")
        assert "union(" in text

    def test_limit_applies_after_union(self, ds):
        q = "bbox(geom, -60, -45, 60, 45) OR name = 'n5'"
        out = ds.query("u", q, limit=7)
        assert len(out) == 7


UNIONS = {
    "two-indexes": ("bbox(geom, -20, -15, 10, 10) OR name = 'n3'", 2),
    "three-branches": (
        "(bbox(geom, -20, -15, 10, 10) AND dtg DURING "
        "2024-01-02T00:00:00Z/2024-01-12T00:00:00Z) OR name = 'n7' OR name = 'n11'", 3),
}


@pytest.mark.parametrize("case", sorted(UNIONS))
def test_a_unions_simple_branches_share_one_dispatch_span(case, ds):
    """PR 47: the branches that are simple index scans are staged together
    (fused where they share a table; one alone on its index nests its own
    ``dispatch`` inside), then pulled in order: ONE ``dispatch`` and a
    ``scan`` + ``decode`` a branch directly under the root, the answer and
    the audit (one query recorded, not one a branch) as before."""
    from geomesa_tpu import conf, obs

    q, branches = UNIONS[case]
    plan = ds.planner.plan("u", q)
    assert len(plan.union) == branches and all(ds.planner._is_simple(p) for p in plan.union)
    audited = []
    real = ds.record_query
    ds.record_query = lambda plan, hits, scan_s: audited.append(plan) or real(plan, hits, scan_s)
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    try:
        out = ds.query("u", q)
        (tr,) = obs.tracer().traces()
    finally:
        conf.OBS_TRACE_SAMPLE.clear()
        obs.install(obs.Tracer())
        del ds.record_query
    assert sorted(out.ids.tolist()) == brute(ds, q)
    top = [s for s in tr.spans if s.parent_id == tr.root.span_id]
    (dispatch,) = [s for s in top if s.name == "dispatch"]
    assert dispatch.attrs["members"] == branches
    assert [s.name for s in top].count("scan") == [s.name for s in top].count("decode") == branches
    nested = [s for s in tr.spans if s.name == "dispatch" and s.parent_id == dispatch.span_id]
    by_index = {}
    for p in plan.union:
        by_index[p.index] = by_index.get(p.index, 0) + 1
    assert len(nested) == sum(1 for n in by_index.values() if n == 1)
    assert len(audited) == 1 and audited[0].union is not None


def test_union_branches_under_seam_crossing_and():
    """Mixed-kind OR (time/attribute) ANDed with a seam-crossing bbox:
    union plans + antimeridian normalization must compose (caught
    divergent in a soak harness that lacked wrap semantics — the engine
    was right; this pins it)."""
    import numpy as np

    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    rng = np.random.default_rng(5)
    sft = FeatureType.from_spec(
        "w", "code:Integer:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    n = 4000
    t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    t = t0 + rng.integers(0, 30 * 86400_000, n)
    code = rng.integers(0, 50, n)
    ds.write("w", FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {"code": code.astype(np.int64), "dtg": t, "geom": (x, y)},
    ))
    lo = np.datetime64("2024-01-16", "ms").astype(np.int64)
    hi = np.datetime64("2024-01-20", "ms").astype(np.int64)
    expr = (
        "((dtg DURING 2024-01-16T00:00:00Z/2024-01-20T00:00:00Z) OR "
        "(code = 47)) AND bbox(geom, 131.7, -90, 191.7, 90)"
    )
    inner = ((t >= lo) & (t < hi)) | (code == 47)
    wrapped = inner & ((x >= 131.7) | (x <= 191.7 - 360.0))
    got = np.sort(np.asarray(ds.query("w", expr).ids, np.int64))
    np.testing.assert_array_equal(got, np.flatnonzero(wrapped))
    assert len(got) > 0
