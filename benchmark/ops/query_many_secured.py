"""Op ``query_many_secured``: ``ops/query_many.py``'s request under the
store's auths; every member's answer is masked and compared on its own."""

from harness import check_secured
from ops.query_many import embedded, members, size  # noqa: F401  (the op's own, unchanged)


def compare(tally, cols, req, answer) -> None:
    for member, got in zip(req["members"], answer):
        check_secured.rows(tally, cols, member, got)
