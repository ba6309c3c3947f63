"""Op ``knn``: one ``geomesa_tpu.process.knn_search`` through the store's
own API: the ``k`` rows nearest ``point`` among those inside ``win``
(``DURING``) and within ``max_distance_m``; ``estimated_distance_m`` None
leaves the start radius to the store's statistics. Embedded only (upstream
runs it as WPS inside GeoServer, the data store in its own process).

The answer is the rows' ids in the program's order (nearest first), their
coordinates as answered, and the last row whole. ``compare`` holds it to
``harness/reference_process.knn`` over all rows: the ordered id list, and
the distance of every answered coordinate from the point against the
reference's, to ``DISTANCE_TOLERANCE_M``; an id twice; the witness row
attribute by attribute."""

import numpy as np

from harness import check
from harness import reference_process as ref
from harness import requests as rq

#: two f64 haversines of one pair of points agree to the last few digits of
#: 1e5 m: 1e-10 m; a row's coordinates that are another row's differ by metres
DISTANCE_TOLERANCE_M = 1e-6


def time_filter(store, win):
    from geomesa_tpu.filter.predicates import During

    return During(store.ds.get_schema(store.type_name).dtg_field, int(win[0]), int(win[1]))


def answer_of(fc) -> dict:
    """A collection as this op's answer: ids, the answered coordinates, the witness."""
    out = rq.collection_answer(fc)
    out["x"], out["y"] = (np.asarray(v, np.float64) for v in fc.representative_xy())
    return out


def embedded(store, req):
    from geomesa_tpu.process import knn_search

    x, y = req["point"]
    return answer_of(knn_search(
        store.ds, store.type_name, float(x), float(y), int(req["k"]),
        estimated_distance_m=req.get("estimated_distance_m"),
        max_distance_m=float(req["max_distance_m"]), filter=time_filter(store, req["win"])))


def members(req) -> int:
    return 1


def size(answer) -> int:
    return len(answer["ids"])


def compare_witness(tally, cols, answer) -> None:
    """The answer's whole row against the generator's row of that id, as
    ``check.rows`` holds a query's."""
    w = answer["witness"]
    if w is not None and 0 <= w["id"] < len(cols):
        tally["witnesses"] += 1
        tally["wrong_attributes"] += int(check._canonical(cols, w["row"]) != cols.row(w["id"]))


def compare_one(tally, cols, req, point, answer) -> None:
    want, want_d = ref.knn(cols, point[0], point[1], req["k"], req["win"],
                           req["max_distance_m"])
    tally["rows_compared"] += len(want)
    got = np.asarray(answer["ids"], np.int64)
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    same = np.array_equal(got, want)
    if same and len(got):
        got_d = ref.haversine_m(point[0], point[1], answer["x"], answer["y"])
        same = bool(np.max(np.abs(got_d - want_d)) <= DISTANCE_TOLERANCE_M)
    tally["wrong_answers"] += int(not same)
    compare_witness(tally, cols, answer)


def compare(tally, cols, req, answer) -> None:
    compare_one(tally, cols, req, req["point"], answer)
