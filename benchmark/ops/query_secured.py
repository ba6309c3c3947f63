"""Op ``query_secured``: ``ops/query.py``'s request, asked of a store that
was opened with auths (``stores/datastore_secured.py``) in the same way, and
held to the rows those auths may read (``harness/check_secured.py``)."""

from harness import check_secured
from ops.query import embedded, members, size  # noqa: F401  (the op's own, unchanged)


def compare(tally, cols, req, answer) -> None:
    check_secured.rows(tally, cols, req, answer)
