"""Store kind ``datastore_extents``: ``stores/datastore.py``'s store for a
schema whose default geometry is not a point. The generator's pool of
vertices is handed over as ONE ``PackedGeometryColumn`` through
``FeatureCollection.from_columns`` (no ``Geometry`` object a row): every
footprint a single-part polygon of one ring, its bbox column the exact f64
bounds rounded to f32 and widened one step outward, as
``PackedGeometryColumn.from_geometries`` makes it. The handle is
``stores/datastore.py``'s ``Store``."""

from __future__ import annotations

import time

import numpy as np

from stores.datastore import Store


def packed_column(cols):
    """The generator's footprints as the program's packed column."""
    from geomesa_tpu import geometry as geo

    n = len(cols)
    if cols.offsets[-1] >= 2 ** 31:
        raise ValueError("the packed column's offsets are int32")
    one_each = np.arange(n + 1, dtype=np.int32)  # one ring a part, one part a footprint
    lo = np.nextafter(cols.bounds[:, :2].astype(np.float32), np.float32(-np.inf))
    hi = np.nextafter(cols.bounds[:, 2:].astype(np.float32), np.float32(np.inf))
    return geo.PackedGeometryColumn(
        coords=cols.coords.copy(), ring_offsets=cols.offsets.astype(np.int32),
        part_ring_offsets=one_each, geom_part_offsets=one_each.copy(),
        types=np.full(n, geo.POLYGON, np.int8), bboxes=np.concatenate([lo, hi], axis=1))


def build(config: dict, cols, run_dir: str) -> Store:
    """create_schema + write + every index table resident on the device."""
    import jax

    from geomesa_tpu import conf, native
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    if native._load() is None:
        raise RuntimeError("the native host tier did not build/load (g++ output is logged above)")
    for name, value in config["properties"].items():
        conf.REGISTRY[name].set(value)
    name = config["type_name"]
    sft = FeatureType.from_spec(name, config["schema"])
    sft.user_data["geomesa.indices.enabled"] = ",".join(config["indices"])
    sft.user_data["geomesa.z3.interval"] = config["z3_interval"]  # read by an xz3 index alone
    ds = DataStore()
    ds.create_schema(sft)
    n = len(cols)
    t0 = time.perf_counter()
    # the store is handed its own pool of vertices (the reference reads the
    # generator's); the attribute columns are read-only to both sides
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: packed_column(cols)})
    fc = FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)
    ds.write(name, fc, check_ids=False)
    for index in config["indices"]:
        table = ds.table(name, index)
        jax.block_until_ready(list(table.cols3.values()))
        if table.n != n:
            raise RuntimeError(f"index {index} holds {table.n} of {n} rows")
    return Store(ds, name, config["indices"], time.perf_counter() - t0)
