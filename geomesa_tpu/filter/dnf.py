"""Disjunctive-normal-form rewrite for union planning.

Reference: FilterSplitter rewrites filters into DNF before computing query
options, so each disjunct can pick its own index and the results union
(/root/reference/geomesa-filter/src/main/scala/org/locationtech/geomesa/
filter/package.scala `rewriteFilterInDnf` + geomesa-index-api/.../planning/
FilterSplitter.scala:61-147 — `(bbox AND a=1) OR (b=2)` becomes one
spatial-index option and one attribute-index option with deduplication).

The expansion is capped: distributing ANDs over ORs is exponential in the
worst case, and past a handful of disjuncts a union plan loses to a single
scan anyway (the reference caps at 32 options and falls back to a single
full-filter strategy the same way).

The cap has a second meaning (:func:`time_slices`): an ``Or`` of MORE
than ``MAX_DISJUNCTS`` disjuncts that each carry a bounded interval of the
type's date field (a tube's box-and-interval slices, a WFS client's own
``OR`` of them) is not one scan either, since one scan would ask every
disjunct's box for the union of all their intervals: it is cut, in time
order, into consecutive groups of at most ``MAX_DISJUNCTS``, and the
planner plans each group as a scan of its own and unions them. Slices
that are a box and a window each travel as the two arrays of a
``predicates.Slices`` carrier, from a tube's bins to the indexes.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.filter.extract import MAX_MS, MIN_MS, extract_intervals
from geomesa_tpu.filter.predicates import And, Filter, Not, Or, Slices

MAX_DISJUNCTS = 16


def rewrite_dnf(f: Filter, limit: int = MAX_DISJUNCTS) -> list[Filter] | None:
    """``f`` as a bounded list of disjuncts (each free of top-level ORs),
    or None when the expansion would exceed ``limit`` disjuncts.

    NOT is pushed through And/Or by De Morgan; other predicates are leaves.
    A single-element result means the filter has no OR structure at all.
    """
    out = _dnf(_push_not(f), limit)
    return out


def time_slices(
    f: Filter, dtg_field: "str | None", limit: int = MAX_DISJUNCTS
) -> list[Filter] | None:
    """``f`` as ``ceil(n / limit)`` filters whose union it is, where ``f``
    is an ``Or`` or a :class:`Slices` carrier (or an ``And`` holding
    exactly one of them beside other conjuncts) of ``n`` > ``limit``
    disjuncts that EACH constrain ``dtg_field`` to bounded intervals; None
    for any other filter, which keeps its single plan.

    The disjuncts go in order of their interval's start into consecutive
    groups of even size, under the ``And``'s other conjuncts where there
    are any. Each group's scan then asks its own boxes for its own stretch
    of time, not every box for the whole duration.

    Slices travel as arrays: a carrier is argsorted by its windows' starts
    and cut into carriers over row slices, no object a slice, and an ``Or``
    whose every disjunct is ``And(BBox, During)`` (a WFS client's own) is
    converted into the carrier once (``Slices.of``) and cut the same way.
    An ``Or`` the carrier cannot express (a disjunct with a polygon, a
    second predicate, two intervals) is cut as objects: a group is
    ``Or(group)``."""
    kinds = (Or, Slices)
    if isinstance(f, kinds):
        union, rest = f, ()
    elif isinstance(f, And):
        ors = [c for c in f.filters if isinstance(c, kinds)]
        if len(ors) != 1:
            return None
        union, rest = ors[0], tuple(c for c in f.filters if c is not ors[0])
    else:
        return None
    n = len(union) if isinstance(union, Slices) else len(union.filters)
    if n <= limit or dtg_field is None:
        return None
    if isinstance(union, Or):
        rows = Slices.of(union, dtg_field)
        union = union if rows is None else rows
    if isinstance(union, Slices):
        w = union.windows
        if union.dtg != dtg_field or (w[:, 0] <= MIN_MS).any() or (w[:, 1] >= MAX_MS).any():
            return None  # not the type's date, or open-ended
        order = np.argsort(w[:, 0], kind="stable")
        group = union.take
    else:
        starts = []
        for d in union.filters:
            ivs = extract_intervals(d, dtg_field).values
            if not ivs:
                return None  # no time predicate (or none satisfiable): not a slice
            lo, hi = min(iv.lo for iv in ivs), max(iv.hi for iv in ivs)
            if lo <= MIN_MS or hi >= MAX_MS:
                return None  # open-ended: it would span every group's stretch
            starts.append(lo)
        order = sorted(range(n), key=starts.__getitem__)

        def group(rows):
            return Or(tuple(union.filters[i] for i in rows))

    k = -(-n // limit)
    cuts = [g * n // k for g in range(k + 1)]
    groups = [group(order[a:b]) for a, b in zip(cuts, cuts[1:])]
    return [And((part,) + rest) for part in groups] if rest else groups


def _push_not(f: Filter) -> Filter:
    """De Morgan: push NOT down to the leaves so distribution sees the
    whole And/Or structure."""
    if isinstance(f, Not):
        inner = f.filter
        if isinstance(inner, And):
            return _push_not(Or([Not(c) for c in inner.filters]))
        if isinstance(inner, Or):
            return _push_not(And([Not(c) for c in inner.filters]))
        if isinstance(inner, Not):
            return _push_not(inner.filter)
        return f
    if isinstance(f, And):
        return And([_push_not(c) for c in f.filters])
    if isinstance(f, Or):
        return Or([_push_not(c) for c in f.filters])
    return f


def _dnf(f: Filter, limit: int) -> list[Filter] | None:
    if isinstance(f, Or):
        out: list[Filter] = []
        for c in f.filters:
            part = _dnf(c, limit)
            if part is None:
                return None
            out.extend(part)
            if len(out) > limit:
                return None
        return out
    if isinstance(f, And):
        # cross-product of the children's disjunct lists
        terms: list[list[Filter]] = [[]]
        for c in f.filters:
            part = _dnf(c, limit)
            if part is None:
                return None
            terms = [t + [d] for t in terms for d in part]
            if len(terms) > limit:
                return None
        return [t[0] if len(t) == 1 else And(t) for t in terms]
    return [f]
