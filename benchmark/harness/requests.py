"""What a generated request is, and the pieces the ops share.

A request is a plain dict (it crosses a process boundary by pickle and
is readable as JSON): ``op`` (the name of a module under ``ops/``),
``klass`` (the class its latency is printed under), and what the op needs:
for the read ops ``box`` [x0, y0, x1, y1], optional ``win`` [lo_ms, hi_ms]
and ``ring`` [[x, y], ...]; ``fmt`` for served queries; ``members`` for
query_many; ``grid`` for density.

An answer with rows is {"ids": int64 array, "witness": None or {"id", "row"}}:
every id, and one whole row (the last) with all its attributes, which the
check holds against the generator's row of that id.

Imports nothing of the program and nothing of JAX: the load generators'
processes import this module.
"""

from __future__ import annotations

import importlib
import json
from urllib.parse import quote

import numpy as np


def generate(role: dict, rng_key, n: int, gctx: dict) -> list:
    """The requests of one client: ``generators/<role's generator>.py``
    with the role's parameters and the stream ``rng_key`` of the seed.
    The parent (for the check) and the client's own process (to send them)
    both call this and get the same list: only the key crosses the
    process boundary, not the requests."""
    gen = importlib.import_module(f"generators.{role['generator']}")
    return gen.generate(role["params"], np.random.default_rng(list(rng_key)), n, gctx)


def op_of(req):
    """``ops/<req's op>.py``."""
    return importlib.import_module(f"ops.{req['op']}")


def iso(ms: int) -> str:
    return f"{np.datetime64(int(ms), 'ms')}Z"


def ecql(req) -> str:
    ring, win = req.get("ring"), req.get("win")
    if ring is not None:
        pts = ", ".join(f"{float(px)!r} {float(py)!r}" for px, py in list(ring) + [ring[0]])
        spatial = f"INTERSECTS(geom, POLYGON(({pts})))"
    else:
        spatial = "bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*(float(v) for v in req["box"]))
    if win is None:
        return spatial
    return f"{spatial} AND dtg DURING {iso(win[0])}/{iso(win[1])}"


def query_path(req, type_name: str) -> str:
    return f"/query/{quote(type_name)}?cql={quote(ecql(req))}&fmt={req.get('fmt', 'geojson')}"


def rows_answer(ids, witness_row) -> dict:
    ids = np.asarray(ids, np.int64)
    return {"ids": ids,
            "witness": {"id": int(ids[-1]), "row": witness_row} if len(ids) else None}


def collection_answer(fc) -> dict:
    """An embedded answer: the collection's ids and its last row, read
    out of the gathered columns."""
    ids = np.asarray(fc.ids)
    row = None
    if len(ids):
        row = {name: ([float(col.x[-1]), float(col.y[-1])] if hasattr(col, "x")
                      else col[-1].item()) for name, col in fc.columns.items()}
    return rows_answer(ids, row)


def geojson_answer(data: bytes) -> dict:
    feats = json.loads(data)["features"]
    row = None
    if feats:
        row = dict(feats[-1]["properties"], __geom__=feats[-1]["geometry"]["coordinates"])
    return rows_answer([int(f["id"]) for f in feats], row)


def arrow_answer(data: bytes) -> dict:
    import pyarrow.ipc as ipc

    table = ipc.open_stream(data).read_all()
    ids = [int(v) for v in table.column("id").to_pylist()]
    row = None
    if ids:
        row = table.slice(len(ids) - 1, 1).to_pylist()[0]
        row.pop("id")
    return rows_answer(ids, row)
