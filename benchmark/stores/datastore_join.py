"""Store kind ``datastore_join``: ``stores/datastore.py``'s point store,
its handle also holding the broadcast side of a join: the data set's
polygon layers (``cols.layers``), each as one ``FeatureCollection`` over
ONE ``PackedGeometryColumn`` as ``stores/datastore_extents.py`` packs the
footprints (a ``Layer`` has the columns it reads; no ``Geometry`` object
a polygon: the join makes those of the polygons it is handed).
The layers are built once, here, after the rows are loaded: ``load_s`` is
the rows' alone.
"""

from __future__ import annotations

import numpy as np

from stores import datastore
from stores.datastore_extents import packed_column

ZONE_SPEC = "zone:Integer,*geom:Polygon:srid=4326"


def layer_collection(layer):
    """One ``Layer`` of the generator as the program's collection."""
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    n = len(layer)
    return FeatureCollection.from_columns(
        FeatureType.from_spec(layer.name, ZONE_SPEC), np.arange(n, dtype=np.int64),
        {"zone": np.arange(n, dtype=np.int32), "geom": packed_column(layer)})


def build(config: dict, cols, run_dir: str):
    store = datastore.build(config, cols, run_dir)
    store.layers = {name: layer_collection(layer) for name, layer in cols.layers.items()}
    return store
