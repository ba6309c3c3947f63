"""Store kind ``datastore``: the program's ``DataStore``, bulk-loaded
through its public API with every attribute of the configuration's
``schema``. ``build(config, cols, run_dir)`` returns the handle the ops
and the clients drive: ``ds``, ``type_name``, ``load_s``, ``serve()``,
``warmup()``, ``close()``."""

from __future__ import annotations

import time

import numpy as np


class Store:
    def __init__(self, ds, type_name: str, indices, load_s: float):
        self.ds, self.type_name, self.indices, self.load_s = ds, type_name, list(indices), load_s
        self._served = None

    def nbytes_device(self) -> dict:
        return {k: self.ds.table(self.type_name, k).nbytes_device for k in self.indices}

    def warmup(self) -> int:
        """The program's own warm-up of its kernel ladder."""
        return self.ds.warmup(self.type_name)

    def serve(self):
        """The HTTP front end on a free port, started once: (host, port)."""
        if self._served is None:
            srv = self.ds.serve(port=0)
            self._served = (srv.host, srv.port)
        return self._served

    def close(self) -> None:
        if self.ds.server is not None:
            self.ds.server.close()
        if self.ds.scheduler is not None:
            self.ds.scheduler.close()


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
    sft.user_data["geomesa.z3.interval"] = config["z3_interval"]
    ds = DataStore()
    ds.create_schema(sft)
    n = len(cols)
    t0 = time.perf_counter()
    # the store is handed copies of the key columns (it may sort them in
    # place); the attribute columns are read-only to both sides
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
    fc = FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)
    ds.write(name, fc, check_ids=False)
    for index in config["indices"]:
        table = ds.table(name, index)
        jax.block_until_ready(list(table.cols3.values()))
        if table.n != n:
            raise RuntimeError(f"index {index} holds {table.n} of {n} rows")
    return Store(ds, name, config["indices"], time.perf_counter() - t0)
