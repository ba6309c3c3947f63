"""Op ``query_many``: ``members`` filters in one embedded call; counts
its members as queries and is one latency sample."""

from harness import check
from harness import requests as rq


def embedded(store, req):
    outs = store.ds.query_many(store.type_name, [rq.ecql(m) for m in req["members"]])
    return [rq.collection_answer(fc) for fc in outs]


def members(req) -> int:
    return len(req["members"])


def size(answer) -> int:
    return sum(len(a["ids"]) for a in answer)


def compare(tally, cols, req, answer) -> None:
    for member, got in zip(req["members"], answer):
        check.rows(tally, cols, member, got)
