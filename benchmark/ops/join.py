"""Op ``join``: one broadcast spatial join through the program's public
entry, ``geomesa_tpu.sql.spatial_join_indexed(ds, type, left, predicate)``:
``left`` the polygons ``subset`` (ascending indices) of the store's layer
``layer``, the point side the indexed table. The answer is the pairs as
the program returns them, two int64 arrays sorted by (polygon, point):
``k`` the polygon's place in ``subset`` and ``ids`` the point's ordinal,
which is its feature id (row i of the generator has id i and the store
keeps the rows in the order written). Embedded only: no front end serves
a join.

``compare`` holds the pairs, each polygon under its index in the layer,
to ``harness/reference_join.py`` over all rows, in the program's own order
(the guarantee says sorted), and counts pairs answered twice, under
``check.LIMITS``' own names."""

import numpy as np

from harness import reference_join as ref


def embedded(store, req):
    from geomesa_tpu.sql import spatial_join_indexed

    left = store.layers[req["layer"]].take(np.asarray(req["subset"], np.int64))
    k, ids = spatial_join_indexed(store.ds, store.type_name, left, req["predicate"])
    return {"k": k, "ids": ids}


def members(req) -> int:
    return 1


def size(answer) -> int:
    return len(answer["ids"])


def pair_keys(n_rows: int, k, ids) -> np.ndarray:
    """One int64 a pair, ascending where the pairs are sorted by (k, id)."""
    return np.asarray(k, np.int64) * int(n_rows) + np.asarray(ids, np.int64)


def compare(tally, cols, req, answer) -> None:
    want_k, want_ids = ref.join_pairs(cols, req["layer"], req["subset"])
    tally["rows_compared"] += len(want_ids)
    subset = np.asarray(req["subset"], np.int64)
    got = pair_keys(len(cols), subset[np.asarray(answer["k"], np.int64)], answer["ids"])
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    tally["wrong_answers"] += int(not np.array_equal(got, pair_keys(len(cols), want_k, want_ids)))
