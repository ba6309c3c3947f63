"""Op ``density``: a ``grid`` x ``grid`` heat map of a filter over its
box, embedded (the device aggregation, f32 semantics)."""

import numpy as np

from harness import check
from harness import requests as rq


def embedded(store, req):
    return np.asarray(store.ds.density(store.type_name, rq.ecql(req), envelope=tuple(req["box"]),
                                       width=req["grid"], height=req["grid"]))


def members(req) -> int:
    return 1


def size(answer) -> int:
    return int(answer.sum())


def compare(tally, cols, req, answer) -> None:
    check.density(tally, cols, req, answer)
