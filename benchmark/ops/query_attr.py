"""Op ``query_attr``: a filter that names taxis, answered with whole rows.

A request carries ``ids`` (the ``taxiId`` strings: one is ``taxiId = 'X'``,
several ``taxiId IN (...)``), optional ``box`` [x0, y0, x1, y1] and ``win``
[lo_ms, hi_ms] as ``harness/requests.py`` gives them, and optional ``sort``
(``"dtg"`` / ``"-dtg"``: the ``sort_by`` hint) and ``limit``. With
``members`` (a list of such requests, unsorted and unlimited) it is ONE
``query_many`` of their filters: its members count as queries and it is one
latency sample, as ``ops/query_many.py``. Embedded only.

``compare`` holds an answer to ``harness/reference_attr.py`` over all rows:
the id SET of an unsorted request (``wrong_answers``), the id SEQUENCE of a
sorted one (``wrong_sequences``: this op's own comparison, its key brought
into ``check.LIMITS`` here with the limit 0, as the README says), an id
twice (``doubled_rows``), and the answer's last row attribute by attribute
against the generator's row of that id (``wrong_attributes``)."""

import numpy as np

from harness import check
from harness import reference_attr as ref
from harness import requests as rq
from ops.knn import compare_witness

check.LIMITS.setdefault("wrong_sequences", 0)  # sorted answers whose id order differs


def ecql(req) -> str:
    ids = [str(s).replace("'", "''") for s in req["ids"]]
    parts = [f"taxiId = '{ids[0]}'" if len(ids) == 1
             else "taxiId IN (" + ", ".join(f"'{s}'" for s in ids) + ")"]
    if req.get("box") is not None:
        parts.append("bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*(float(v) for v in req["box"])))
    if req.get("win") is not None:
        parts.append(f"dtg DURING {rq.iso(req['win'][0])}/{rq.iso(req['win'][1])}")
    return " AND ".join(parts)


def embedded(store, req):
    if "members" in req:
        outs = store.ds.query_many(store.type_name, [ecql(m) for m in req["members"]])
        return [rq.collection_answer(fc) for fc in outs]
    hints = None
    if req.get("sort") is not None:
        from geomesa_tpu.planning.hints import QueryHints

        hints = QueryHints(sort_by=req["sort"])
    return rq.collection_answer(
        store.ds.query(store.type_name, ecql(req), limit=req.get("limit"), hints=hints))


def members(req) -> int:
    return len(req["members"]) if "members" in req else 1


def size(answer) -> int:
    if isinstance(answer, list):
        return sum(len(a["ids"]) for a in answer)
    return len(answer["ids"])


def compare_one(tally, cols, req, answer) -> None:
    want = ref.answer(cols, req)
    tally["rows_compared"] += len(want)
    got = np.asarray(answer["ids"], np.int64)
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    if req.get("sort") is not None:
        tally["wrong_sequences"] += int(not np.array_equal(got, want))
    else:
        tally["wrong_answers"] += int(not np.array_equal(np.sort(got), want))
    compare_witness(tally, cols, answer)


def compare(tally, cols, req, answer) -> None:
    if "members" in req:
        for member, got in zip(req["members"], answer):
            compare_one(tally, cols, member, got)
    else:
        compare_one(tally, cols, req, answer)
