"""The comparison that decides ``correct``: answers kept from the window
against the plain reference. Every number compared is an exact count with
the limit 0; ``LIMITS`` names them and ``verdict`` prints each beside its
limit. An op's module (``ops/<op>.py``) says which of the comparisons
below its answers get."""

from __future__ import annotations

import datetime

import numpy as np

from harness import reference as ref

LIMITS = {
    "wrong_answers": 0,       # sampled answers whose ids or count differ from the reference
    "doubled_rows": 0,        # ids an answer held more than once
    "wrong_attributes": 0,    # witness rows with an attribute that is not the row's own
    "density_sum_gap": 0,     # |grid total - rows the f32 reference keeps|, summed
    "density_bad_pixels": 0,  # pixels outside what their rows allow
}


def new_tally() -> dict:
    return {k: 0 for k in LIMITS} | {"compared": 0, "rows_compared": 0, "witnesses": 0}


def _canonical(cols, row: dict) -> dict:
    """A witness row as ``cols.row`` gives it, whichever wire it came by:
    GeoJSON (date as ISO text, point under ``__geom__``), Arrow (datetime,
    point as a list of two) or the embedded columns."""
    row = dict(row)
    g = row.pop("__geom__", None)
    if g is None:
        g = row.pop(cols.geom, None)
    row[cols.geom] = None if g is None else [float(v) for v in g]
    t = row.get(cols.dtg)
    if isinstance(t, str):
        t = np.datetime64(t.rstrip("Z"), "ms")
    if isinstance(t, (datetime.datetime, np.datetime64)):
        t = int(np.datetime64(t, "ms").astype(np.int64))
    row[cols.dtg] = t
    return row


def rows(tally, cols, req, answer) -> None:
    """One answer with rows: the exact id set, no id twice, and the
    witness row equal to the generator's row of that id in every attribute."""
    want = ref.ref_ids(cols, req["box"], req.get("win"), req.get("ring"))
    tally["rows_compared"] += len(want)
    got = np.sort(np.asarray(answer["ids"]).astype(np.int64))
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    tally["wrong_answers"] += int(not np.array_equal(got, want))
    w = answer["witness"]
    if w is not None and 0 <= w["id"] < len(cols):
        tally["witnesses"] += 1
        tally["wrong_attributes"] += int(_canonical(cols, w["row"]) != cols.row(w["id"]))


def count(tally, cols, req, answer) -> None:
    want = ref.ref_ids(cols, req["box"], req.get("win"), req.get("ring"))
    tally["rows_compared"] += len(want)
    tally["wrong_answers"] += int(int(answer) != len(want))


def density(tally, cols, req, answer) -> None:
    x32, y32 = ref.loose_rows(cols, req["box"], req.get("win"))
    d = ref.check_density(answer, x32, y32, req["box"], req["grid"], req["grid"])
    tally["density_sum_gap"] += abs(d["sum_gap"])
    tally["density_bad_pixels"] += d["bad_pixels"]
    tally["rows_compared"] += d["rows"]


def verdict(tally, emit) -> bool:
    ok = True
    for name, limit in LIMITS.items():
        emit("compared", number=name, value=tally[name], limit=limit)
        ok &= tally[name] <= limit
    return bool(ok)
