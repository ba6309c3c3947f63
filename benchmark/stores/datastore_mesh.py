"""Store kind ``datastore_mesh``: the program's ``DataStore`` over a 1-D
mesh of the configuration's ``chips`` (``DataStore(mesh=make_mesh(chips))``:
every index a ``DistributedIndexTable``, its scan blocks dealt round-robin,
one shard a chip), bulk-loaded through the same public API as
``stores/datastore.py`` and driven through the same ``Store`` handle.
After the load it checks the layout the configuration states: every index
table sharded, every column on ``chips`` distinct devices, a ``chips``-th of
the blocks on each."""

from __future__ import annotations

import time

import numpy as np

from stores.datastore import Store


def check_layout(ds, type_name: str, indices, n: int, chips: int) -> None:
    """Raises where an index is not laid out as ``chips`` shards of equal
    size on ``chips`` devices."""
    import jax

    from geomesa_tpu.parallel.dtable import DistributedIndexTable

    for index in indices:
        table = ds.table(type_name, index)
        if not isinstance(table, DistributedIndexTable):
            raise RuntimeError(f"index {index} is a {type(table).__name__}, not a mesh table")
        jax.block_until_ready(list(table.cols3.values()))
        if table.n != n:
            raise RuntimeError(f"index {index} holds {table.n} of {n} rows")
        if table.n_blocks % chips:
            raise RuntimeError(f"index {index}: {table.n_blocks} blocks over {chips} chips")
        local = table.n_blocks // chips
        for col, arr in table.cols3.items():
            shards = arr.addressable_shards
            devices = {s.device for s in shards}
            if len(shards) != chips or len(devices) != chips:
                raise RuntimeError(f"{index}.{col}: {len(shards)} shards on {len(devices)} "
                                   f"devices, the configuration says {chips}")
            if any(s.data.shape[:2] != (1, local) for s in shards):
                raise RuntimeError(f"{index}.{col}: shard shapes "
                                   f"{[s.data.shape for s in shards]}, {local} blocks a chip said")


def build(config: dict, cols, run_dir: str) -> Store:
    """create_schema + write over the mesh + the layout check."""
    from geomesa_tpu import conf, native
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.parallel import make_mesh
    from geomesa_tpu.sft import FeatureType

    if native._load() is None:
        raise RuntimeError("the native host tier did not build/load (g++ output is logged above)")
    for name, value in config["properties"].items():
        conf.REGISTRY[name].set(value)
    name, chips = config["type_name"], int(config["chips"])
    sft = FeatureType.from_spec(name, config["schema"])
    sft.user_data["geomesa.indices.enabled"] = ",".join(config["indices"])
    sft.user_data["geomesa.z3.interval"] = config["z3_interval"]
    ds = DataStore(mesh=make_mesh(chips))
    ds.create_schema(sft)
    n = len(cols)
    t0 = time.perf_counter()
    # as stores/datastore.py: copies of the key columns (the store may sort
    # them in place); the attribute columns are read-only to both sides
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
    fc = FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)
    ds.write(name, fc, check_ids=False)
    check_layout(ds, name, config["indices"], n, chips)
    return Store(ds, name, config["indices"], time.perf_counter() - t0)
