"""The planner's index choice is the static decision: an index's cost is
its exact candidate rows (+1) times ``index_priority`` of its name, and
the explain trail says that and nothing else."""

import re
from types import SimpleNamespace

import numpy as np
import pytest

from geomesa_tpu import geometry as geo
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.planning.planner import INDEX_PRIORITY, QueryPlanner, index_priority
from geomesa_tpu.sft import FeatureType

DAY = 86400_000
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DURING = "dtg DURING 2024-01-03T00:00:00Z/2024-01-09T00:00:00Z"
FILTERS = {
    "pt": f"bbox(geom, -10, -10, 10, 10) AND {DURING} AND kind = 'a'",
    "poly": f"bbox(geom, -10, -10, 10, 10) AND {DURING}",
}
NAMES = sorted(INDEX_PRIORITY) + ["attr_kind"]


def _tables(rows_by_index):
    """As much of a store as ``cost()`` reads: ``table(type, index)``,
    its ``candidate_spans(cfg)`` and their ``n_rows()``."""
    def table(type_name, index_name):
        rows = rows_by_index[index_name]
        spans = SimpleNamespace(n_rows=lambda: rows)
        return SimpleNamespace(candidate_spans=lambda cfg: spans)

    return SimpleNamespace(table=table)


@pytest.fixture(scope="module")
def stores():
    """A point type with every point index (s2/s3 are opt-in) and an
    extent type: between them every index the store can build."""
    rng = np.random.default_rng(5)
    n = 600
    ds = DataStore(tile=64)
    pt = FeatureType.from_spec(
        "pt", "kind:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    pt.user_data["geomesa.indices.enabled"] = "z3,z2,s3,s2,attr"
    ds.create_schema(pt)
    ds.write("pt", FeatureCollection.from_columns(
        pt, [str(i) for i in range(n)],
        {
            "kind": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            "dtg": T0 + rng.integers(0, 20 * DAY, n),
            "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)),
        },
    ))
    poly = FeatureType.from_spec("poly", "dtg:Date,*geom:Polygon:srid=4326")
    ds.create_schema(poly)
    m = 200
    x, y = rng.uniform(-60, 60, m), rng.uniform(-45, 45, m)
    ds.write("poly", FeatureCollection.from_columns(
        poly, [str(i) for i in range(m)],
        {
            "dtg": T0 + rng.integers(0, 20 * DAY, m),
            "geom": [geo.box(a, b, a + 1.5, b + 1.5) for a, b in zip(x, y)],
        },
    ))
    served = {
        idx.name: t for t in ("pt", "poly") for idx in ds.indexes(t)
    }
    assert set(served) == {"z3", "z2", "s3", "s2", "attr_kind", "xz3", "xz2"}
    return ds, served


@pytest.mark.parametrize("name", NAMES)
def test_cost_is_rows_by_the_static_priority(stores, name):
    # the arithmetic, through a table of a known size and through none
    assert QueryPlanner(_tables({name: 41})).cost("t", name, None) == (
        42 * index_priority(name)
    )
    assert QueryPlanner(_tables({})).cost("t", name, None) == index_priority(name)
    # the planner holds no second source of a multiplier
    assert not hasattr(QueryPlanner(_tables({})), "reweighter")
    ds, served = stores
    if name not in served:
        # "id" plans by lookup and "attr" is the family's shared
        # multiplier: neither names a table
        assert name in ("id", "attr")
        return
    # ... and through a store: the trail names the same cost, and is
    # made of the decomposition and the choice only
    type_name = served[name]
    f = ecql.parse(FILTERS[type_name])
    idx = next(i for i in ds.indexes(type_name) if i.name == name)
    cfg = idx.scan_config(f)
    rows = ds.table(type_name, name).candidate_spans(cfg).n_rows()
    want = (rows + 1) * index_priority(name)
    assert ds.planner.cost(type_name, name, cfg) == want
    exp = Explainer()
    plan = ds.planner.plan(type_name, FILTERS[type_name], explain=exp)
    said = [ln.strip() for ln in exp.lines if ln.strip().startswith(f"Index {name}:")]
    assert said == [f"Index {name}: {cfg.n_ranges} ranges, cost {want:.1f}"]
    assert "reweight" not in exp.render()
    costs = {
        m.group(1): float(m.group(2))
        for m in (re.match(r"\s*Index (\w+): \d+ ranges, cost ([\d.]+)", ln) for ln in exp.lines)
        if m
    }
    assert plan.index == min(costs, key=costs.get)
