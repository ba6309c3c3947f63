"""Op ``ingest``: one batch of new events posted to ``/ingest/<type>`` as a
GeoJSON FeatureCollection with every attribute and the feature id, and
what its acknowledgement promises.

Served, the answer is the acknowledgement's JSON; ``sound`` says whether
it is the one the configuration's ``writes`` guarantee names (200 is the
client's to see): ``durable`` true and every row of the batch ``acked``.
Embedded (after the window, for the check) the batch is read back whole by
feature id through the store's own ``query``; ``compare`` holds it to the
generator's batch: every row there (``acked_rows_lost``), every attribute
equal (``acked_rows_changed``). ``count`` holds the store's row count to
preloaded + acknowledged (``count_gap``). Exact counts, limit 0.
"""

import json
from urllib.parse import quote

import numpy as np

from datagen import gdelt_live
from harness import check

check.LIMITS.setdefault("acked_rows_lost", 0)     # rows of an acknowledged batch not read back
check.LIMITS.setdefault("acked_rows_changed", 0)  # read back with an attribute that differs
check.LIMITS.setdefault("count_gap", 0)           # |store rows - (preloaded + acknowledged)|


def http(req, type_name):
    return ("POST", f"/ingest/{quote(type_name)}", gdelt_live.geojson_body(req["spec"]),
            {"Content-Type": "application/geo+json"})


def parse(req, data):
    return json.loads(data)


def sound(req, answer) -> bool:
    return (isinstance(answer, dict) and answer.get("durable") is True
            and answer.get("acked") == req["spec"]["rows"])


def members(req) -> int:
    return 1


def size(answer) -> int:
    return int(answer["acked"])


def embedded(store, req):
    """The batch read back by feature id: {"ids", "x", "y", "t", "attrs"}."""
    spec = req["spec"]
    ids = ", ".join(f"'{i}'" for i in range(spec["first_id"], spec["first_id"] + spec["rows"]))
    fc = store.lam.query(f"IN ({ids})")
    t = np.asarray(fc.columns[spec["dtg"]])
    if t.dtype.kind == "M":
        t = t.astype("datetime64[ms]")
    geom = fc.columns[spec["geom"]]
    return {"ids": np.asarray(fc.ids).astype(np.int64), "t": t.astype(np.int64),
            "x": np.asarray(geom.x), "y": np.asarray(geom.y),
            "attrs": {a: np.asarray(fc.columns[a]) for a in spec["attrs"]}}


def compare(tally, cols, req, answer) -> None:
    want = cols.batch(req["spec"])
    ids, first = np.unique(answer["ids"], return_index=True)
    tally["doubled_rows"] += len(answer["ids"]) - len(ids)
    here = np.isin(want["ids"], ids)
    tally["acked_rows_lost"] += int((~here).sum())
    tally["rows_compared"] += len(want["ids"])
    # the rows read back, in the batch's order
    at = first[np.searchsorted(ids, want["ids"][here])]
    same = np.ones(int(here.sum()), bool)
    for key in ("x", "y", "t"):
        same &= answer[key][at] == want[key][here]
    for a, col in want["attrs"].items():
        same &= answer["attrs"][a][at] == col[here]
    tally["acked_rows_changed"] += int((~same).sum())


def count(tally, store_rows: int, preloaded: int, acknowledged: int) -> None:
    tally["count_gap"] += abs(int(store_rows) - (int(preloaded) + int(acknowledged)))
