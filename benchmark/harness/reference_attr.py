"""The plain NumPy reference for the track-history cell: which rows a
request that names taxis is owed, and in what order. Straight from the
guarantee ``configs/tdrive-tracks-1chip.json`` states, over EVERY row of the
generator's columns (``taxi``: each row's taxi number; ``x``, ``y``, ``t``):
no index, no lexicode, no sorted table, no row span of a value, so a
neighbouring value's rows let in by a clip that is a row off, a prefix that
leaks (``"1"`` into ``"10"``), a window's open end taken closed or a limit
cut before the sort shows as a difference. It imports nothing of the program
and is handed nothing the program made.

``answer(cols, req)``: the ids (row numbers) owed to ``req``:

- a row is in if its taxi's id, AS A STRING, equals one of ``req["ids"]``
  (an id that is no decimal number of the fleet's, such as ``"007"`` or
  ``"12a"``, equals no taxi's), its point lies in the closed ``box``
  [x0, y0, x1, y1] where one is given (f64), and its time in ``win``
  ``lo <= t < hi`` where one is given (epoch millis; ``DURING`` as the store
  documents it);
- ``sort`` ``"dtg"`` puts them in ascending time, ``"-dtg"`` in descending
  (a taxi's times are distinct, so the order is total within a taxi, and the
  mix sorts only requests that name one; rows of several taxis that share a
  second keep their ids' order, which the guarantee does not promise);
- ``limit`` keeps the first rows of that order, after the sort;
- unsorted, the ids ascend and stand for a set.
"""

from __future__ import annotations

import numpy as np


def taxi_numbers(ids) -> np.ndarray:
    """The taxi numbers whose decimal string is one of ``ids``: an id
    equals a taxi's only as the canonical decimal string of its number."""
    out = [int(s) for s in ids if s.isascii() and s.isdigit() and str(int(s)) == s]
    return np.asarray(sorted(set(out)), np.int64)


def answer(cols, req) -> np.ndarray:
    """The ids owed to ``req``, in the order the guarantee gives."""
    numbers = taxi_numbers(req["ids"])
    keep = cols.taxi == numbers[0] if len(numbers) == 1 else np.isin(cols.taxi, numbers)
    rows = np.flatnonzero(keep)
    box, win = req.get("box"), req.get("win")
    if win is not None:
        t = cols.t[rows]
        rows = rows[(t >= int(win[0])) & (t < int(win[1]))]
    if box is not None:
        x, y = cols.x[rows], cols.y[rows]
        rows = rows[(x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])]
    sort = req.get("sort")
    if sort is not None:
        if sort not in ("dtg", "-dtg"):
            raise ValueError(f"the guarantee orders by dtg, not {sort!r}")
        t = cols.t[rows]
        rows = rows[np.argsort(-t if sort == "-dtg" else t, kind="stable")]
    if req.get("limit") is not None:
        rows = rows[: int(req["limit"])]
    return rows.astype(np.int64)
