"""``harness/check.py``'s comparisons for a store opened with auths: the
same numbers, each an exact count with the limit 0, against
``harness/reference_secured.py`` (the rows the configuration's auths may
read, ``cols.auths``), and one number more, brought into ``check.LIMITS``
here as the README says:

``vis_leaks``  rows of a sampled answer whose OWN label the reference's
               evaluator refuses for those auths. A leaked row also makes
               its answer a wrong one; this number says that the fault is a
               label, and how many rows it let through.

A ``count`` or a ``density`` holds no row to look up: a leak there is a
``wrong_answers`` or a pixel over what its rows allow.
"""

from __future__ import annotations

import numpy as np

from harness import check
from harness import reference_secured as ref

check.LIMITS.setdefault("vis_leaks", 0)  # answered rows the caller's auths may not read


def rows(tally, cols, req, answer) -> None:
    """``check.rows`` over the visible rows, and the answer's own labels."""
    want = ref.ref_ids(cols, cols.auths, req["box"], req.get("win"), req.get("ring"))
    tally["rows_compared"] += len(want)
    got = np.sort(np.asarray(answer["ids"]).astype(np.int64))
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    tally["wrong_answers"] += int(not np.array_equal(got, want))
    tally["vis_leaks"] += ref.leaks(cols, cols.auths, got)
    w = answer["witness"]
    if w is not None and 0 <= w["id"] < len(cols):
        tally["witnesses"] += 1
        tally["wrong_attributes"] += int(check._canonical(cols, w["row"]) != cols.row(w["id"]))


def count(tally, cols, req, answer) -> None:
    want = ref.ref_ids(cols, cols.auths, req["box"], req.get("win"), req.get("ring"))
    tally["rows_compared"] += len(want)
    tally["wrong_answers"] += int(int(answer) != len(want))


def density(tally, cols, req, answer) -> None:
    bounds = ref.density_bounds(cols, cols.auths, req["box"], req.get("win"),
                                req["grid"], req["grid"])
    d = ref.check_density(answer, bounds, req["grid"], req["grid"])
    tally["density_sum_gap"] += d["sum_gap"]
    tally["density_bad_pixels"] += d["bad_pixels"]
    tally["rows_compared"] += bounds["sure"]
