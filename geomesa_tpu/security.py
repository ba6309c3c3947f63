"""Visibility security: per-feature labels evaluated against auths.

Reference: geomesa-security (/root/reference/geomesa-security/src/main/
scala/org/locationtech/geomesa/security/ — VisibilityEvaluator.scala,
AuthorizationsProvider). Visibility expressions use the Accumulo grammar:

    admin                  requires the "admin" auth
    admin&user             both
    admin|ops              either
    a&(b|c)                grouping; & binds tighter than |

Empty visibility = visible to everyone. A store configured with ``auths``
masks every query result through the evaluator (row-level security); the
visibility column is named by the schema's ``geomesa.vis.field`` user-data
key.

Hostile input: labels arrive over the network once a store is served
(docs/serving.md "The data plane" — the ingest endpoint carries
client-authored visibility columns), so the parser is bounded: input
over :data:`MAX_EXPRESSION_LENGTH` or nested past
:data:`MAX_EXPRESSION_DEPTH` raises :class:`VisibilityError` (a
``ValueError``) instead of recursing toward a ``RecursionError`` that
would traceback a worker thread. Every rejection path raises the same
type, so callers can map it to one clean 4xx.
"""

from __future__ import annotations

import re
from functools import lru_cache

import numpy as np

VIS_FIELD_KEY = "geomesa.vis.field"

_TOKEN = re.compile(r"\s*(?:(?P<label>[\w.\-:]+)|(?P<op>[&|()]))")

#: hard cap on expression bytes accepted by the parser — a 4 KiB label
#: is already absurd; anything longer is an attack or a bug
MAX_EXPRESSION_LENGTH = 4096

#: hard cap on paren-nesting depth — the recursive-descent parser (and
#: the recursive evaluator) consume one stack frame per level, so an
#: unbounded "(((((..." from the network would otherwise RecursionError
MAX_EXPRESSION_DEPTH = 64


class VisibilityError(ValueError):
    """A visibility expression that does not parse (bad token,
    unbalanced parens, trailing input, over the length/depth caps).
    Subclasses ``ValueError`` so pre-existing callers keep working."""


def validate(expression: str) -> None:
    """Reject a malformed visibility label BEFORE it is stored: raises
    :class:`VisibilityError`, accepts empty/blank (public). The served
    ingest path runs every incoming distinct label through this so a
    hostile expression 4xxes at the door instead of detonating inside a
    later query's mask."""
    if expression and expression.strip():
        _compile(expression.strip())


@lru_cache(maxsize=4096)
def _compile(expression: str):
    """Parse a visibility expression into a nested tuple AST."""
    if len(expression) > MAX_EXPRESSION_LENGTH:
        raise VisibilityError(
            f"visibility expression over {MAX_EXPRESSION_LENGTH} chars "
            f"({len(expression)})"
        )
    pos = 0
    text = expression

    def parse_or(depth):
        nonlocal pos
        left = parse_and(depth)
        while True:
            m = _TOKEN.match(text, pos)
            if m and m.group("op") == "|":
                pos = m.end()
                left = ("or", left, parse_and(depth))
            else:
                return left

    def parse_and(depth):
        nonlocal pos
        left = parse_atom(depth)
        while True:
            m = _TOKEN.match(text, pos)
            if m and m.group("op") == "&":
                pos = m.end()
                left = ("and", left, parse_atom(depth))
            else:
                return left

    def parse_atom(depth):
        nonlocal pos
        m = _TOKEN.match(text, pos)
        if m is None:
            raise VisibilityError(
                f"bad visibility {expression!r} at {text[pos:]!r}"
            )
        if m.group("label"):
            pos = m.end()
            return ("label", m.group("label"))
        if m.group("op") == "(":
            if depth >= MAX_EXPRESSION_DEPTH:
                raise VisibilityError(
                    f"visibility expression nested past "
                    f"{MAX_EXPRESSION_DEPTH} levels"
                )
            pos = m.end()
            inner = parse_or(depth + 1)
            m2 = _TOKEN.match(text, pos)
            if not m2 or m2.group("op") != ")":
                raise VisibilityError(f"unbalanced parens in {expression!r}")
            pos = m2.end()
            return inner
        raise VisibilityError(
            f"bad visibility {expression!r} at {text[pos:]!r}"
        )

    ast = parse_or(0)
    if text[pos:].strip():
        # any leftover input is an error — a silently-truncated label like
        # "admin,ops" would otherwise grant access on its first token
        raise VisibilityError(f"trailing input in visibility {expression!r}")
    return ast


def _eval(ast, auths: frozenset) -> bool:
    kind = ast[0]
    if kind == "label":
        return ast[1] in auths
    if kind == "and":
        return _eval(ast[1], auths) and _eval(ast[2], auths)
    return _eval(ast[1], auths) or _eval(ast[2], auths)


def visible(expression: str, auths) -> bool:
    """Can ``auths`` see a feature labeled ``expression``? Empty/blank
    labels are public (reference VisibilityEvaluator)."""
    if not expression or not expression.strip():
        return True
    return _eval(_compile(expression.strip()), frozenset(auths))


def visibility_mask(labels: np.ndarray, auths) -> np.ndarray:
    """Boolean mask over a visibility-label column (distinct labels are
    few; evaluate each once). Object-dtype columns (mixed None/str from
    a network ingest) normalize first — ``None`` is public, like the
    empty label — so a hostile payload can neither crash ``np.unique``'s
    sort nor smuggle a non-string past the parser."""
    return _mask_and_labels(labels, auths)[0]


def _mask_and_labels(labels, auths) -> tuple[np.ndarray, int]:
    """(:func:`visibility_mask`'s mask, the distinct labels it evaluated)."""
    labels = _label_strings(labels)
    auths = frozenset(auths)
    out = np.zeros(len(labels), dtype=bool)
    distinct = np.unique(labels)
    for v in distinct:
        out[labels == v] = visible(str(v), auths)
    return out, len(distinct)


class LabelCodes:
    """One chunk's visibility column as a dictionary: ``labels``, its
    distinct label strings (normalised as :func:`visibility_mask`
    normalises them), and ``codes``, a small integer a row that indexes
    them. A row's visibility is then a table lookup by the row's ORDINAL
    (:func:`mask_ordinals`): no label string is read, sorted or compared
    while a query is answered. The store keeps one beside each chunk
    (``DataStore.label_codes``), built when the chunk is written, or from
    older chunks' where a mutation only moved their rows (:meth:`joined`).

    ``table(auths)`` evaluates each distinct label ONCE an auth set with
    :func:`visible` and keeps the answer. A label that does not parse is
    a third state of that table, not an error of building it: it fails
    the answers that contain such a row (:meth:`visible`), as the string
    route does, and no other query."""

    __slots__ = ("labels", "codes", "_table")

    #: rows encoded a pass: ``np.unique`` sorts strings with the
    #: interpreter lock held, so a large chunk goes in slices whose
    #: dictionaries merge through a dict (also the faster way: a slice's
    #: sort stays in cache)
    _SLICE = 1 << 16

    def __init__(self, column):
        column = _label_strings(column)
        index: dict = {}
        codes = np.empty(len(column), dtype=np.int32)
        for s in range(0, len(column), self._SLICE):
            found, inverse = np.unique(
                column[s : s + self._SLICE], return_inverse=True
            )
            remap = np.array(
                [index.setdefault(v, len(index)) for v in found.tolist()],
                dtype=np.int32,
            )
            codes[s : s + self._SLICE] = remap[inverse]
        self._set(tuple(index), codes)

    def _set(self, labels: tuple, codes: np.ndarray) -> None:
        self.labels = labels
        self.codes = codes.astype(
            np.uint8 if len(labels) <= 1 << 8
            else np.uint16 if len(labels) <= 1 << 16 else np.int32,
            copy=False,
        )
        #: (auths, :meth:`table`'s answer for them): a store asks with ONE
        #: auth set, so one memo, replaced when another set asks
        self._table = None

    @classmethod
    def joined(cls, parts, keep=None) -> "LabelCodes":
        """The dictionary of a chunk that holds the rows of ``parts``
        (the dictionaries of older chunks, in row order), of which
        ``keep`` (a Boolean mask or indices over those rows; None: all)
        stay: what a fold, a delete or a compaction makes of chunks whose
        rows it only moves, from their code arrays alone, with no label
        string read. A label no row carries any more stays in the
        dictionary: an entry of the table that no code indexes."""
        out = cls.__new__(cls)
        if len(parts) == 1:
            labels, codes = parts[0].labels, parts[0].codes
        else:
            index: dict = {}
            codes = np.concatenate([
                np.array(
                    [index.setdefault(v, len(index)) for v in p.labels],
                    dtype=np.int32,
                )[p.codes]
                for p in parts
            ])
            labels = tuple(index)
        out._set(labels, codes if keep is None else codes[keep])
        if len(parts) == 1:
            out._table = parts[0]._table  # the same labels: the same table
        return out

    def __len__(self) -> int:
        return len(self.codes)

    def table(self, auths: frozenset):
        """(visible a code, does-not-parse a code or None) for ``auths``."""
        memo = self._table
        if memo is None or memo[0] != auths:
            seen = np.zeros(len(self.labels), dtype=bool)
            broken = np.zeros(len(self.labels), dtype=bool)
            for code, label in enumerate(self.labels):
                try:
                    seen[code] = visible(label, auths)
                except VisibilityError:
                    broken[code] = True
            memo = self._table = (
                auths, (seen, broken if broken.any() else None)
            )
        return memo[1]

    def visible(self, rows, auths: frozenset) -> np.ndarray:
        """Boolean mask over ``rows``, the chunk's ordinals: may ``auths``
        read the row. Raises :class:`VisibilityError` where one of them
        carries a label that does not parse."""
        seen, broken = self.table(auths)
        codes = self.codes[rows]
        if broken is not None and broken[codes].any():
            visible(self.labels[int(codes[broken[codes]][0])], auths)  # raises
        return seen[codes]

    def present(self, rows) -> list:
        """The distinct labels among ``rows`` (a traced mask's ``labels``:
        it runs only under a span): a count a code, cheaper than a sort."""
        met = np.flatnonzero(np.bincount(self.codes[rows]))
        return [self.labels[c] for c in met.tolist()]


def _label_strings(labels) -> np.ndarray:
    """A label column as strings: an object column (mixed None/str from a
    network ingest) normalised, ``None`` to the empty label."""
    labels = np.asarray(labels)
    if labels.dtype == object:
        labels = np.array(
            ["" if v is None else str(v) for v in labels.tolist()]
        )
    return labels


def mask_ordinals(dictionaries, ordinals: np.ndarray, auths) -> np.ndarray:
    """Which of the table ``ordinals`` (an index scan's candidates, an id
    lookup's rows) the ``auths`` may read: a Boolean mask aligned with
    them, looked up from the label codes of the chunks the ordinals
    number (``dictionaries``: a :class:`LabelCodes` a chunk, in chunk
    order; ``DataStore.label_codes``) BEFORE any row is gathered. The
    planner's row-level stage wherever a route has ordinals; equal to
    :func:`visibility_mask` over the same rows' label strings.

    Traced as the span ``vis`` (docs/observability.md): ``rows`` the
    candidates that reached it, ``kept`` those visible, ``labels`` the
    distinct labels among them, ``coded`` 1."""
    from geomesa_tpu.obs.trace import NULL_SPAN, span

    auths = frozenset(auths)
    with span("vis", rows=len(ordinals), coded=1) as sp:
        traced = sp is not NULL_SPAN
        present: set = set()  # the labels met, counted only under a span

        def decide(codes: LabelCodes, rows) -> np.ndarray:
            if traced:
                present.update(codes.present(rows))
            return codes.visible(rows, auths)

        if len(dictionaries) == 1:
            seen = decide(dictionaries[0], ordinals)
        else:
            # the searchsorted over chunk bases that ``DataStore.gather`` does
            ordinals = np.asarray(ordinals, dtype=np.int64)
            bases = np.cumsum([0] + [len(d) for d in dictionaries])
            which = np.searchsorted(bases, ordinals, side="right") - 1
            seen = np.zeros(len(ordinals), dtype=bool)
            for ci, d in enumerate(dictionaries):
                sel = np.flatnonzero(which == ci)
                if len(sel):
                    seen[sel] = decide(d, ordinals[sel] - bases[ci])
        if traced:
            sp.annotate(kept=int(np.count_nonzero(seen)), labels=len(present))
    return seen


def mask_collection(fc, vis_field: str, auths):
    """The rows of ``fc`` whose label in column ``vis_field`` the
    ``auths`` satisfy, decided from the label STRINGS: the row-level
    stage of a collection that has no ordinals to look codes up by (the
    served handler's per-request auths over an answer; the planner's
    ``_post`` where no route decided before it). Traced as a span ``vis``
    (docs/observability.md) with ``rows`` in, ``kept`` out, ``labels``,
    the distinct labels evaluated, and ``coded`` 0; its segments are
    ``labels`` (the mask: ``np.unique`` over the label strings, one
    evaluation and one comparison pass a distinct label) and ``copy``
    (the rows kept taken out of every column). An answer that is
    visible whole is handed back as it came, not copied."""
    from geomesa_tpu.obs.trace import span

    with span("vis", rows=len(fc), coded=0) as sp:
        sp.event("labels")
        m, n_labels = _mask_and_labels(fc.columns[vis_field], auths)
        sp.event("copy")
        if not m.all():
            fc = fc.mask(m)
        sp.annotate(kept=len(fc), labels=n_labels)
    return fc
