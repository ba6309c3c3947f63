"""The plain reference for a store whose rows carry visibility labels:
``harness/reference.py``'s answers over the rows the caller may see.

Which rows those are is decided HERE, by ``visible``: a recursive-descent
evaluator of the label grammar the configuration states (GeoMesa manual,
"Data Security"; Accumulo's ColumnVisibility): a label is a token
(letters, digits, ``_ . - :``), ``a&b`` (both), ``a|b`` (either), ``&``
binding tighter than ``|``, parentheses; blanks between symbols mean
nothing; the empty or blank label is everybody's. It evaluates as it
parses: no tree, no cache, nothing of the program's (this module imports
nothing of ``geomesa_tpu``). ``visible_rows`` applies it to EVERY row of the
generator's label column, one call a row, before any box is tested: no
candidate set, no table of distinct labels. The mask is kept on the columns
object, so a run pays the pass once (a few seconds at 2^21 rows, after the
window).

A label that does not parse raises ``ValueError``: the generator writes
none, and a reference that guessed would hide a fault.
"""

from __future__ import annotations

import numpy as np

from harness import reference as ref

_TOKEN_EXTRA = frozenset("_.-:")
#: the generator's attribute that holds the labels (the schema's ``geomesa.vis.field``)
LABEL_FIELD = "visibility"


def visible(label: str, auths) -> bool:
    """May a caller holding ``auths`` read a row labelled ``label``?"""
    text = label.strip()
    if not text:
        return True
    value, pos = _either(text, 0, frozenset(auths))
    if pos < len(text):
        raise ValueError(f"the label {label!r} has trailing input {text[pos:]!r}")
    return value


def _blanks(text: str, pos: int) -> int:
    while pos < len(text) and text[pos].isspace():
        pos += 1
    return pos


def _either(text: str, pos: int, held):
    """``a | b | ...`` from ``pos``: (its value, where it ends, blanks
    skipped). Both sides are always parsed: a label is checked whole."""
    value, pos = _both(text, pos, held)
    while pos < len(text) and text[pos] == "|":
        right, pos = _both(text, pos + 1, held)
        value = value or right
    return value, pos


def _both(text: str, pos: int, held):
    """``a & b & ...``: tighter than ``|``."""
    value, pos = _atom(text, pos, held)
    while pos < len(text) and text[pos] == "&":
        right, pos = _atom(text, pos + 1, held)
        value = value and right
    return value, pos


def _atom(text: str, pos: int, held):
    """A token, or a parenthesised expression."""
    pos = _blanks(text, pos)
    if pos < len(text) and text[pos] == "(":
        value, pos = _either(text, pos + 1, held)
        if pos >= len(text) or text[pos] != ")":
            raise ValueError(f"unbalanced parentheses in the label {text!r}")
        return value, _blanks(text, pos + 1)
    start = pos
    while pos < len(text) and (text[pos].isalnum() or text[pos] in _TOKEN_EXTRA):
        pos += 1
    if pos == start:
        raise ValueError(f"the label {text!r} does not parse at {text[start:]!r}")
    return text[start:pos] in held, _blanks(text, pos)


def visible_rows(cols, auths) -> np.ndarray:
    """Boolean over every row of ``cols``: the rows ``auths`` may read.
    One ``visible`` call a row; kept on ``cols`` by the auths asked."""
    key = tuple(sorted(set(auths)))
    kept = cols.__dict__.setdefault("_visible_rows", {})
    if key not in kept:
        labels, held = cols.attrs[LABEL_FIELD].tolist(), frozenset(key)
        kept[key] = np.fromiter((visible(s, held) for s in labels), bool, len(labels))
    return kept[key]


def ref_ids(cols, auths, box, win=None, ring=None) -> np.ndarray:
    """Ascending ids of the rows the filter keeps AND the caller may read."""
    seen = visible_rows(cols, auths)
    ids = ref.ref_ids(cols, box, win, ring)
    return ids[seen[ids]]


def leaks(cols, auths, ids) -> int:
    """How many of the answered ``ids`` carry a label the caller's auths
    do not satisfy (an id that is no row's counts as one)."""
    ids = np.asarray(ids, np.int64)
    inside = (ids >= 0) & (ids < len(cols))
    return int((~inside).sum()) + int((~visible_rows(cols, auths)[ids[inside]]).sum())


def density_bounds(cols, auths, box, win, width: int, height: int) -> dict:
    """What a ``width`` x ``height`` heat map of the visible rows in
    ``box`` (its own envelope) and ``win`` may hold, under EITHER of the
    semantics the configuration states for a density: the exact f64 filter
    (the host route a store with auths takes today) or the device
    aggregation's documented one (``harness.reference.loose_rows`` and
    ``check_density``: f32 columns against the f32 envelope, whole-second
    bounds). A row the exact filter keeps is in under both; a row only the
    f32 compare keeps may be in or out; a row the caller may not read is in
    neither.

    A row's pixel is decided unless its coordinate lies within ``tol`` of a
    pixel edge, ``tol`` being ``reference.PIXEL_EPS`` plus what rounding the
    coordinate and the envelope to f32 can move it (three f32 spacings at
    the envelope's largest magnitude, in pixels). Returns ``lower`` (decided
    pixels of rows that are surely in), ``reach`` (``lower`` plus every
    pixel an undecided or a maybe-row can reach), ``sure`` and ``maybe``
    (row counts)."""
    if win is not None and (win[0] % 1000 or win[1] % 1000):
        raise ValueError("second-aligned window")
    lo_i, hi_i = 0, len(cols.t)
    if win is not None:
        lo_i, hi_i = (int(v) for v in np.searchsorted(cols.t, [win[0], win[1]], "left"))
    seen = visible_rows(cols, auths)[lo_i:hi_i]
    x, y = cols.x[lo_i:hi_i][seen], cols.y[lo_i:hi_i][seen]
    x0, y0, x1, y1 = (float(v) for v in box)
    exact = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
    f32 = np.float32
    x32, y32 = x.astype(f32), y.astype(f32)
    # the device's grid holds the rows of its widened mask that lie inside
    # the envelope as f32 compares them (reference.check_density's ``m``)
    maybe = (x32 >= f32(x0)) & (x32 <= f32(x1)) & (y32 >= f32(y0)) & (y32 <= f32(y1)) & ~exact
    n = width * height
    tx = ref.PIXEL_EPS + 3 * float(np.spacing(f32(max(abs(x0), abs(x1))))) * width / (x1 - x0)
    ty = ref.PIXEL_EPS + 3 * float(np.spacing(f32(max(abs(y0), abs(y1))))) * height / (y1 - y0)

    def cell(f, size):
        return np.clip(np.floor(f).astype(np.int64), 0, size - 1)

    def corners(rows):
        """[4, rows]: the pixel at each corner of a row's tolerance box."""
        fx = (x[rows] - x0) / (x1 - x0) * width
        fy = (y[rows] - y0) / (y1 - y0) * height
        return np.sort(np.stack([cell(fy + sy * ty, height) * width + cell(fx + sx * tx, width)
                                 for sy in (-1, 1) for sx in (-1, 1)]), axis=0)

    def reachable(c):
        """Every pixel a row can reach, counted once a row."""
        first = np.ones(c.shape, bool)
        first[1:] = c[1:] != c[:-1]
        return np.bincount(c[first], minlength=n)

    sure = corners(exact)
    decided = sure[0] == sure[3]
    lower = np.bincount(sure[0][decided], minlength=n)
    reach = lower + reachable(sure[:, ~decided]) + reachable(corners(maybe))
    return {"lower": lower, "reach": reach, "sure": int(exact.sum()), "maybe": int(maybe.sum())}


def check_density(grid, bounds: dict, width: int, height: int) -> dict:
    """``grid`` against ``density_bounds``: ``sum_gap`` how far its total
    lies outside [sure, sure + maybe], ``bad_pixels`` the pixels outside
    [lower, reach]; both must be 0."""
    g = np.asarray(grid)
    if g.shape != (height, width):
        return {"sum_gap": bounds["sure"], "bad_pixels": width * height}
    g = np.rint(g).astype(np.int64).ravel()
    total = int(g.sum())
    gap = max(bounds["sure"] - total, total - bounds["sure"] - bounds["maybe"], 0)
    return {"sum_gap": gap,
            "bad_pixels": int(((g < bounds["lower"]) | (g > bounds["reach"])).sum())}
