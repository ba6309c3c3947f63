"""Op ``query_live``: op ``query`` over a store that is appended to while
it is read (a Lambda store), and the plain reference of what such an
answer must and may hold.

With M = the preloaded matches plus the matches among rows of batches
whose 200 was wholly read before this request was sent, and P = the
matches among rows of batches sent before this answer was wholly read:
ids must hold all of M (``missing_acked_rows`` counts those it lacks), may
hold nothing outside M and P (``unknown_rows``), no id twice
(``doubled_rows``), and the witness row has to be the generator's in every
attribute, an appended row too (``wrong_attributes``). All exact counts
with the limit 0. NumPy over the generator's columns; nothing of the
program.
"""

import numpy as np

from harness import check
from harness import reference as ref
from ops import query

check.LIMITS.setdefault("missing_acked_rows", 0)  # acknowledged before the request, not answered
check.LIMITS.setdefault("unknown_rows", 0)        # answered, neither preloaded nor sent by then

http, parse, members, size = query.http, query.parse, query.members, query.size


class Appended:
    """The rows the writers sent in one run, and when: every row of batch
    b could be in an answer from ``sent[b]`` on and has to be from
    ``acked[b]`` on (inf where no sound 200 was read). ``batches``:
    [(spec, sent, acked)] on the clock the readers' times are on."""

    def __init__(self, cols, batches):
        self.n_rows = len(cols)
        self.specs = {spec["batch"]: spec for spec, _, _ in batches}
        parts = [cols.batch(spec) for spec, _, _ in batches]
        sizes = [len(p["ids"]) for p in parts]

        def cat(key, dtype):
            return np.concatenate([p[key] for p in parts]) if parts else np.zeros(0, dtype)

        self.ids, self.t = cat("ids", np.int64), cat("t", np.int64)
        self.x, self.y = cat("x", np.float64), cat("y", np.float64)
        self.sent = np.repeat([float(s) for _, s, _ in batches], sizes)
        self.acked = np.repeat([float(a) for _, _, a in batches], sizes)

    def must_may(self, req, sent: float, done: float):
        """(ids an answer to ``req`` sent at ``sent`` must hold, ids an
        answer wholly read at ``done`` may hold besides), appended rows
        alone, each ascending."""
        rows = ref._kept(self.x, self.y, self.t, req["box"], req.get("win"), req.get("ring"))
        must = rows[self.acked[rows] < sent]
        may = rows[(self.sent[rows] <= done) & ~(self.acked[rows] < sent)]
        return np.sort(self.ids[must]), np.sort(self.ids[may])

    def spec_of(self, fid: int):
        """The key of the batch that holds feature id ``fid``, or None."""
        if fid < self.n_rows or not self.specs:
            return None
        rows = next(iter(self.specs.values()))["rows"]
        return self.specs.get((int(fid) - self.n_rows) // rows)


def compare(tally, cols, req, answer, when) -> None:
    """``when``: {"sent", "done", "appended": Appended} of this answer."""
    base = ref.ref_ids(cols, req["box"], req.get("win"), req.get("ring"))
    must, may = when["appended"].must_may(req, when["sent"], when["done"])
    want = np.concatenate([base, must])
    tally["rows_compared"] += len(want)
    got = np.sort(np.asarray(answer["ids"]).astype(np.int64))
    tally["doubled_rows"] += len(got) - len(np.unique(got))
    missing = len(np.setdiff1d(want, got))
    unknown = len(np.setdiff1d(got, np.concatenate([want, may])))
    tally["missing_acked_rows"] += missing
    tally["unknown_rows"] += unknown
    tally["wrong_answers"] += int(bool(missing or unknown))
    w = answer["witness"]
    if w is None:
        return
    if 0 <= w["id"] < len(cols):
        mine = cols.row(w["id"])
    else:
        spec = when["appended"].spec_of(w["id"])
        if spec is None:
            return  # an id nobody sent: counted above
        mine = cols.appended_row(spec, w["id"])
    tally["witnesses"] += 1
    tally["wrong_attributes"] += int(check._canonical(cols, w["row"]) != mine)
