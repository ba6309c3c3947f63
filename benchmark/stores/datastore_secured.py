"""Store kind ``datastore_secured``: ``stores/datastore.py``'s store opened
as a reader with authorizations opens it. The schema's spec carries the
user data ``geomesa.vis.field`` (the attribute that holds each row's
visibility label; ``FeatureType.from_spec`` reads it off the spec's ``;``
part) and the store is ``DataStore(auths=<the configuration's auths>)``:
upstream's data store parameter ``geomesa.security.auths``. Everything else
(``create_schema``, one ``write`` of every column, the tables resident) is
``stores/datastore.py``'s, and the handle is its ``Store``."""

from __future__ import annotations

import time

import numpy as np

from stores.datastore import Store


def build(config: dict, cols, run_dir: str) -> Store:
    """create_schema + write + every index table resident on the device."""
    import jax

    from geomesa_tpu import conf, native
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.security import VIS_FIELD_KEY
    from geomesa_tpu.sft import FeatureType

    if native._load() is None:
        raise RuntimeError("the native host tier did not build/load (g++ output is logged above)")
    for name, value in config["properties"].items():
        conf.REGISTRY[name].set(value)
    name = config["type_name"]
    sft = FeatureType.from_spec(name, config["schema"])
    if sft.user_data.get(VIS_FIELD_KEY) not in cols.attrs:
        raise RuntimeError(f"the schema's {VIS_FIELD_KEY} names no attribute of the data")
    sft.user_data["geomesa.indices.enabled"] = ",".join(config["indices"])
    sft.user_data["geomesa.z3.interval"] = config["z3_interval"]
    ds = DataStore(auths=tuple(config["auths"]))
    ds.create_schema(sft)
    n = len(cols)
    t0 = time.perf_counter()
    # the store is handed copies of the key columns (it may sort them in
    # place); the attribute columns, the labels among them, are read-only
    # to both sides
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
    fc = FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)
    ds.write(name, fc, check_ids=False)
    for index in config["indices"]:
        table = ds.table(name, index)
        jax.block_until_ready(list(table.cols3.values()))
        if table.n != n:
            raise RuntimeError(f"index {index} holds {table.n} of {n} rows")
    return Store(ds, name, config["indices"], time.perf_counter() - t0)
