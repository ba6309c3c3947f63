"""Op ``count_secured``: ``ops/count.py``'s request under the store's
auths: the exact count of the rows the filter keeps AND the caller may
read."""

from harness import check_secured
from ops.count import embedded, members, size  # noqa: F401  (the op's own, unchanged)


def compare(tally, cols, req, answer) -> None:
    check_secured.count(tally, cols, req, answer)
