"""Op ``query_extents``: one ECQL filter over a store of polygons,
answered with whole rows: ``BBOX(geom, ...)`` or, where the request has a
``ring``, ``INTERSECTS(geom, POLYGON(...))`` (``requests.ecql``), every
attribute column and the packed polygons gathered. The answer is the
rows' ids and, as witness, the last row with its ring read out of the
gathered packed column. Embedded only: GeoJSON and Arrow answers of
polygons are kept for a served cell (PERF.md section 7).

``compare`` holds the id set to ``harness/reference_extents.py`` over all
rows, counts ids answered twice, and holds the witness's attributes AND
vertices to the generator's row of that id, under ``check.LIMITS``' own
names."""

import numpy as np

from harness import reference_extents as ref
from harness import requests as rq


def witness_ring(col, i: int):
    """Polygon ``i`` of a packed geometry column as a list of [x, y]; None
    where it is not one part of one ring (no footprint here is)."""
    p0, p1 = int(col.geom_part_offsets[i]), int(col.geom_part_offsets[i + 1])
    r0, r1 = int(col.part_ring_offsets[p0]), int(col.part_ring_offsets[p1])
    if p1 - p0 != 1 or r1 - r0 != 1:
        return None
    return np.asarray(col.coords[int(col.ring_offsets[r0]):int(col.ring_offsets[r0 + 1])]).tolist()


def embedded(store, req):
    fc = store.ds.query(store.type_name, rq.ecql(req))
    ids = np.asarray(fc.ids)
    row = None
    if len(ids):
        last = len(ids) - 1
        row = {name: (witness_ring(col, last) if hasattr(col, "ring_offsets")
                      else col[last].item()) for name, col in fc.columns.items()}
    return rq.rows_answer(ids, row)


def members(req) -> int:
    return 1


def size(answer) -> int:
    return len(answer["ids"])


def compare(tally, cols, req, answer) -> None:
    want = ref.ref_ids(cols, req["box"], req.get("ring"))
    tally["rows_compared"] += len(want)
    got = np.sort(np.asarray(answer["ids"]).astype(np.int64))
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    tally["wrong_answers"] += int(not np.array_equal(got, want))
    w = answer["witness"]
    if w is not None and 0 <= w["id"] < len(cols):
        tally["witnesses"] += 1
        tally["wrong_attributes"] += int(w["row"] != cols.row(w["id"]))
