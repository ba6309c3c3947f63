"""Op ``count``: the exact count of a filter, embedded."""

from harness import check
from harness import requests as rq


def embedded(store, req):
    return int(store.ds.count(store.type_name, rq.ecql(req)))


def members(req) -> int:
    return 1


def size(answer) -> int:
    return int(answer)


def compare(tally, cols, req, answer) -> None:
    check.count(tally, cols, req, answer)
