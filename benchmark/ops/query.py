"""Op ``query``: one ECQL filter answered with whole rows. Embedded, the
call returns a collection with every attribute column gathered; served,
GeoJSON features with all properties or an Arrow stream with all columns."""

from harness import check
from harness import requests as rq


def embedded(store, req):
    return rq.collection_answer(store.ds.query(store.type_name, rq.ecql(req)))


def http(req, type_name):
    return "GET", rq.query_path(req, type_name), None, {}


def parse(req, data):
    return rq.arrow_answer(data) if req.get("fmt") == "arrow" else rq.geojson_answer(data)


def members(req) -> int:
    return 1


def size(answer) -> int:
    return len(answer["ids"])


def compare(tally, cols, req, answer) -> None:
    check.rows(tally, cols, req, answer)
