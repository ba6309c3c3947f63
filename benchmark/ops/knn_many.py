"""Op ``knn_many``: one ``geomesa_tpu.process.knn_many`` of ``points`` at
one ``win``: every round's windows in one fused dispatch. Counts its
points as operations and is one latency sample, as ``query_many`` does;
each point's answer is compared as ``ops/knn.py`` compares its one."""

from ops import knn


def embedded(store, req):
    from geomesa_tpu.process import knn_many

    outs = knn_many(
        store.ds, store.type_name, [(float(x), float(y)) for x, y in req["points"]],
        int(req["k"]), estimated_distance_m=req.get("estimated_distance_m"),
        max_distance_m=float(req["max_distance_m"]), filter=knn.time_filter(store, req["win"]))
    return [knn.answer_of(fc) for fc in outs]


def members(req) -> int:
    return len(req["points"])


def size(answer) -> int:
    return sum(len(a["ids"]) for a in answer)


def compare(tally, cols, req, answer) -> None:
    for point, got in zip(req["points"], answer):
        knn.compare_one(tally, cols, req, point, got)
