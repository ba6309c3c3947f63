"""Order-preserving u64 lexicoding of attribute values.

Reference: the attribute index lexicodes values into sortable row-key
strings (AttributeIndexKey.scala:21-70 over org.locationtech.geomesa.utils
lexicoders). The TPU redesign lexicodes into one u64 sort key — weakly
order-preserving (v1 <= v2 implies code(v1) <= code(v2)), so searchsorted
range pruning over the sorted key column is a correct superset and exact
semantics come from host refinement:

- strings: first 8 UTF-8 bytes big-endian (longer strings collide onto
  their prefix — collisions only widen the scanned span)
- signed ints: sign-bit flip
- floats: IEEE-754 total-order trick (flip sign bit for positives, all
  bits for negatives)
- dates: epoch-millis as signed ints
"""

from __future__ import annotations

import numpy as np

U64_MAX = np.uint64(0xFFFFFFFFFFFFFFFF)
SIGN = np.uint64(0x8000000000000000)


def lex_int(col) -> np.ndarray:
    c = np.asarray(col).astype(np.int64)
    return c.view(np.uint64) ^ SIGN


def lex_float(col) -> np.ndarray:
    c = np.asarray(col, dtype=np.float64)
    b = c.view(np.uint64)
    neg = (b & SIGN) != 0
    return np.where(neg, ~b, b | SIGN)


def _utf8_words(col, n_words: int) -> np.ndarray:
    """u64 ``[n, n_words]``: word j is UTF-8 bytes [8j, 8j + 8) of each
    value, big-endian, null-padded. ONE encode pass at the full width, and
    every 8-byte window read from the same bytes (np.char.encode is per
    element: repeating it a word made ingest pay a full-column pass each,
    and a query's bounds sixteen calls a value)."""
    c = np.asarray(col)
    n = len(c)
    if n == 0:
        return np.zeros((0, n_words), dtype=np.uint64)
    # a UTF-8 char is >= 1 byte, so ``width`` chars always cover the bytes
    width = n_words * 8
    raw = np.char.encode(c.astype(f"U{width}"), "utf-8").astype(f"S{width}")
    return np.frombuffer(raw.tobytes(), dtype=">u8").reshape(n, n_words).astype(np.uint64)


def lex_string(col, word: int = 0) -> np.ndarray:
    """u64 lexicode word ``word`` of a string column: UTF-8 bytes
    [8*word, 8*word+8) big-endian, null-padded. Word 0 is the primary
    sort key; word 1 the tie-breaking secondary (WriteKeys.sub). Byte
    order of UTF-8 == code-point order, so each word is weakly
    order-preserving even when truncation splits a multi-byte sequence."""
    return np.ascontiguousarray(_utf8_words(col, word + 1)[:, word])


_INT_TYPES = ("Integer", "Int", "Long", "Date")
_FLOAT_TYPES = ("Float", "Double")


def lex_column(col, attr_type: str) -> np.ndarray:
    """Lexicode one column according to its SFT attribute type."""
    if attr_type in _INT_TYPES:
        return lex_int(col)
    if attr_type in _FLOAT_TYPES:
        return lex_float(col)
    return lex_string(col)


# cap on secondary sort words: 7 words -> values distinct within their
# first 64 UTF-8 bytes prune exactly; longer shared prefixes only widen
# the scanned span (host refinement stays exact)
MAX_SUB_WORDS = 7


def lex_string_words(col) -> "np.ndarray | None":
    """Variable-width secondary sort words for a string column: u64 words
    1..W of the lexicode ([n, W], big-endian bytes [8, 8+8W)), where W is
    just wide enough to cover the longest encoded value (capped at
    MAX_SUB_WORDS). None when every value fits the 8-byte primary word.
    Zero-padding IS the correct order semantics: a shorter string sorts
    before any extension of it, and 0 is the pad byte."""
    c = np.asarray(col)
    if len(c) == 0:
        return None
    enc = np.char.encode(c.astype(str), "utf-8")
    max_len = int(np.char.str_len(enc).max())
    n_words = min(max(0, -(-(max_len - 8) // 8)), MAX_SUB_WORDS)
    if n_words == 0:
        return None
    return np.ascontiguousarray(_utf8_words(c, 1 + n_words)[:, 1:])


def lex_bounds(los, his, attr_type: str) -> "tuple[np.ndarray, np.ndarray]":
    """Inclusive u64 code bounds ``(lo, hi)``, each ``[n, W]``, of n
    attribute value bounds ``[los[k], his[k]]`` (None: unbounded on that
    side), every value of both sides lexicoded in ONE pass (an equality's
    ``lo is hi``: once). Column 0 is the scan range over the primary sort
    key; W = 1 for a numeric type, and for a string 1 + MAX_SUB_WORDS: the
    further columns are the secondary-word bounds (word j of each bound
    value, zero-padded past the value's length: its exact key; tables
    narrow with their own word count and ignore the rest). An unbounded
    side is the open extreme in every column. Exclusive query bounds still
    map to the inclusive code range (string prefixes collide; refinement
    is exact)."""
    n = len(los)
    lo_at = [k for k, v in enumerate(los) if v is not None]
    hi_at = [k for k, v in enumerate(his) if v is not None and v is not los[k]]
    # dtype=object: each value converts on its own, as a column of one did
    values = np.empty(len(lo_at) + len(hi_at), dtype=object)
    values[:] = [los[k] for k in lo_at] + [his[k] for k in hi_at]
    if attr_type in _INT_TYPES or attr_type in _FLOAT_TYPES:
        codes = lex_column(values, attr_type)[:, None]
    else:
        codes = _utf8_words(values, 1 + MAX_SUB_WORDS)
    lo = np.zeros((n, codes.shape[1]), dtype=np.uint64)
    hi = np.full((n, codes.shape[1]), U64_MAX, dtype=np.uint64)
    lo[lo_at] = codes[: len(lo_at)]
    hi[hi_at] = codes[len(lo_at):]
    same = [k for k in lo_at if his[k] is los[k]]
    hi[same] = lo[same]
    return lo, hi


def bounds_to_range(lo, hi, attr_type: str) -> tuple[np.uint64, np.uint64]:
    """Inclusive [lo, hi] u64 scan range of ONE pair of value bounds:
    column 0 of :func:`lex_bounds`' one-row case."""
    code_lo, code_hi = lex_bounds([lo], [hi], attr_type)
    return code_lo[0, 0], code_hi[0, 0]
