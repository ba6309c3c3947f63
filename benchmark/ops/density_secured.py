"""Op ``density_secured``: ``ops/density.py``'s request under the store's
auths: no row the caller may not read lies in a pixel
(``harness/reference_secured.py`` ``density_bounds`` has what a grid may
hold under either of the density semantics the configuration states)."""

from harness import check_secured
from ops.density import embedded, members, size  # noqa: F401  (the op's own, unchanged)


def compare(tally, cols, req, answer) -> None:
    check_secured.density(tally, cols, req, answer)
