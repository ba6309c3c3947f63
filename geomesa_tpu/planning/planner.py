"""QueryPlanner: pick an index, build a scan plan, execute, refine.

Reference call stack (SURVEY.md §3.1): QueryPlanner.runQuery ->
StrategyDecider.getFilterPlan -> keySpace.getIndexValues/getRanges ->
adapter.createQueryPlan -> scan -> client-side reduce
(/root/reference/geomesa-index-api/src/main/scala/org/locationtech/
geomesa/index/planning/QueryPlanner.scala:40-161, StrategyDecider.scala:
47-181). The TPU pipeline: extract filter values -> per-index ScanConfig ->
priority/cost selection -> tile-pruned device scan -> host gather ->
residual full-filter refinement (the `useFullFilter` tier, always exact
f64) -> limit.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass
from typing import Optional

import numpy as np

from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.filter.extract import extract_ids
from geomesa_tpu.filter.predicates import Filter, Include
from geomesa_tpu.index.api import ScanConfig
from geomesa_tpu.obs.trace import NULL_SPAN as _NULL_SPAN
from geomesa_tpu.obs.trace import add as _oadd
from geomesa_tpu.obs.trace import span as _ospan
from geomesa_tpu.obs.trace import tracer as _otracer
from geomesa_tpu.planning.explain import Explainer, ExplainNull

# index selection priority when multiple indexes can serve a filter;
# mirrors the reference's cost multipliers (SpatioTemporalFilterStrategy:
# z3 = 1.1 with bounded time; SpatialFilterStrategy z2 = 2.0; attribute =
# 1.0 with equality...). Lower = preferred.
INDEX_PRIORITY = {
    "z3": 1.1, "xz3": 1.1, "s3": 1.2,
    "z2": 2.0, "xz2": 2.0, "s2": 2.1,
    "attr": 2.5, "id": 0.5,
}


def index_priority(name: str) -> float:
    """Cost multiplier for an index name; attribute indexes are named
    ``attr_<attribute>`` and share the ``attr`` multiplier."""
    return INDEX_PRIORITY.get(name, INDEX_PRIORITY.get(name.split("_")[0], 3.0))


@dataclass
class QueryPlan:
    """A chosen execution strategy for one query."""

    type_name: str
    filter: Filter
    index: Optional[str]  # None = full-table host scan
    config: Optional[ScanConfig]
    ids: Optional[list] = None  # id-lookup plan
    limit: Optional[int] = None
    planning_s: float = 0.0  # wall-clock spent planning (audit/metrics)
    # multi-index union plan (reference FilterSplitter OR options): each
    # sub-plan scans one DNF disjunct on its own index; results dedup-union
    union: Optional[list["QueryPlan"]] = None
    # degraded-mode notices (quarantined partitions excluded from results);
    # populated at plan time from the store's health, counted at execute
    warnings: Optional[list] = None
    # result-cache outcome for this execution ("hit"|"miss"|"coalesced"|
    # None = cache not consulted) + time spent probing the cache, kept
    # SEPARATE from scan time so cache regressions are attributable in
    # explain traces and the geomesa.query.cache_probe timer
    cache_status: Optional[str] = None
    cache_probe_s: float = 0.0
    # serving-tier attribution (geomesa_tpu.serving): wall-clock this plan
    # spent queued behind the micro-batch window before its fused dispatch
    # — kept SEPARATE from scan time so queue wait is attributable in
    # explain traces and the geomesa.serving.queue_wait histogram
    queue_wait_s: float = 0.0
    # estimate accountability (docs/observability.md): the stats-sketch
    # row estimate resolved at plan time (None = no sketch covered the
    # filter, or geomesa.plan.estimate.enabled off) and the rows the
    # executed scan actually produced — record_query feeds the pair into
    # the geomesa.plan.estimate.error histogram + per-index accuracy
    estimated_rows: Optional[float] = None
    actual_rows: Optional[int] = None

    @property
    def strategy(self) -> str:
        if self.union is not None:
            return "union(" + "+".join(p.strategy for p in self.union) + ")"
        if self.ids is not None:
            return "id-lookup"
        if self.index is None:
            return "full-scan"
        return self.index


# re-exported for existing importers; the definitions live in the leaf
# module planning.errors so the storage layer can use them too
from geomesa_tpu.planning.errors import (  # noqa: E402
    QueryGuardError, QueryTimeout, check_deadline, deadline_from,
)


def _filter_leaf_kinds(
    f: Filter, geom_field: str | None, dtg_field: str | None
) -> set | None:
    """The set of predicate kinds ({"spatial", "temporal"}) this filter is
    built from, or None when any predicate is outside the indexable
    spatio-temporal subset (And of leaves; Or only of same-kind leaves)."""
    from geomesa_tpu.filter.predicates import (
        And, BBox, Between, Cmp, During, Include, Intersects, Or,
    )

    def leaf_kind(p) -> str | None:
        if isinstance(p, (BBox, Intersects)) and p.prop == geom_field:
            return "spatial"
        if isinstance(p, (During, Between)) and p.prop == dtg_field:
            return "temporal"
        if isinstance(p, Cmp) and p.prop == dtg_field and p.op in ("<", "<=", ">", ">=", "="):
            return "temporal"
        return None

    def walk(p) -> set | None:
        if isinstance(p, Include):
            return set()
        if isinstance(p, And):
            out: set = set()
            for c in p.filters:
                k = walk(c)
                if k is None:
                    return None
                out |= k
            return out
        if isinstance(p, Or):
            kinds = {leaf_kind(c) for c in p.filters}
            return kinds if len(kinds) == 1 and None not in kinds else None
        k = leaf_kind(p)
        return {k} if k else None

    return walk(f)


def _referenced_props(f: Filter) -> set:
    """Every attribute name a filter tree references (``prop`` fields of
    leaf predicates, a ``Slices`` carrier's two, recursing into
    And/Or/Not)."""
    from geomesa_tpu.filter.predicates import And, Not, Or, Slices

    out: set = set()
    if isinstance(f, Slices):
        out.update((f.geom, f.dtg))
    elif isinstance(f, (And, Or)):
        for c in f.filters:
            out |= _referenced_props(c)
    elif isinstance(f, Not):
        out |= _referenced_props(f.filter)
    else:
        prop = getattr(f, "prop", None)
        if prop is not None:
            out.add(prop)
    return out


def mask_decides_filter(
    f: Filter, config: Optional[ScanConfig], sft, for_aggregation: bool = False
) -> bool:
    """True when the device scan mask decides this filter entirely, so
    loose mode / aggregation push-down may skip host refinement. Requires
    (a) every predicate to be an indexable spatial/temporal leaf, (b) the
    config to be precise on both axes, and (c) the chosen index to actually
    enforce each predicate kind present — an atemporal index (z2) leaves
    ``windows=None`` and must not satisfy a temporal filter. Gate for the
    LOOSE_BBOX fast path (reference Z3IndexKeySpace.useFullFilter,
    Z3IndexKeySpace.scala:240-254).

    ``for_aggregation``: device aggregation kernels evaluate the BOX wide
    plane only — a polygon-tier config (config.poly / config.rast)
    decides the filter for row scans (certainty vector + host
    boundary-residue refinement) but NOT for gather-free aggregations,
    which would count the whole bbox."""
    if config is None or not (config.geom_precise and config.time_precise):
        return False
    if for_aggregation and (config.poly is not None or config.rast is not None):
        return False
    kinds = _filter_leaf_kinds(f, sft.geom_field, sft.dtg_field)
    if kinds is None:
        return False
    if "spatial" in kinds and config.boxes is None:
        return False
    if "temporal" in kinds and config.windows is None:
        return False
    return True


# scan-config memo bound: repeated dashboard queries re-plan constantly;
# the z/xz range decomposition is the dominant planning cost and a PURE
# function of (index instance, filter), so memoizing it is always safe
_CONFIG_MEMO_MAX = 4096


class QueryPlanner:
    """Plans and runs queries for one DataStore."""

    def __init__(self, store):
        import threading

        self.store = store
        # (index instance, canonical filter key) -> ScanConfig | None.
        # Keyed by the index OBJECT, so a dropped-and-recreated schema
        # (fresh index instances, possibly different resolution) can never
        # serve a stale decomposition; LRU-bounded. The lock makes
        # concurrent plan() calls safe (the serving tier plans in caller
        # threads): an OrderedDict mutating under two threads corrupts.
        self._config_memo: "OrderedDict" = OrderedDict()
        self._memo_lock = threading.Lock()
        self._memo_epoch = 0  # bumped by every invalidation (see below)

    @property
    def mutation_epoch(self) -> int:
        """Monotonic count of committed mutations (config-memo
        invalidations). The serving tier scopes in-window coalescing to
        one epoch so a query admitted after a write never shares a
        pre-write leader's result."""
        return self._memo_epoch

    def invalidate_config_memo(self) -> None:
        """Drop every memoized scan config. The store calls this after
        EVERY committed mutation: scan_config is pure only between
        mutations (bin_range clamping in z3/xz3/s2/attribute indexes
        depends on the data), so a memo entry may not outlive a write.
        Bumping the epoch stops a decomposition computed BEFORE the
        mutation (outside the lock) from being inserted after it."""
        with self._memo_lock:
            self._memo_epoch += 1
            self._config_memo.clear()

    def _scan_config(self, idx, f: Filter):
        """``idx.scan_config(f)`` through the memo: :meth:`_scan_configs`
        of one filter."""
        from geomesa_tpu.filter.predicates import canonical_key

        return self._scan_configs(
            idx, [canonical_key(f)], lambda _: [idx.scan_config(f)]
        )[0]

    def _scan_configs(self, idx, keys: list, decompose) -> list:
        """One scan config (or None) a canonical filter key, through the
        memo (planner half of the cache tier's "probe before scan": a warm
        repeat query skips the range decomposition entirely): the keys are
        probed under ONE lock hold, and ``decompose(positions)`` computes
        the configs of the misses (a key met twice, once) in ONE call.
        Only valid between mutations — see invalidate_config_memo. The
        decomposition itself runs outside the lock: two racing planners may
        both compute (benign — the result is pure), but never block each
        other on it."""
        out: list = [None] * len(keys)
        miss: dict = {}  # key -> its positions
        with _ospan("plan.probe", index=idx.name, members=len(keys)):
            with self._memo_lock:
                memo = self._config_memo
                for k, key in enumerate(keys):
                    if (idx, key) in memo:
                        memo.move_to_end((idx, key))
                        out[k] = memo[(idx, key)]
                    else:
                        miss.setdefault(key, []).append(k)
                epoch = self._memo_epoch
        if not miss:
            return out
        with _ospan("plan.decompose", index=idx.name, members=len(miss)) as sp:
            cfgs = decompose([pos[0] for pos in miss.values()])
            if sp is not _NULL_SPAN and sp.trace.retain:
                # what it emitted: tells a cheap decomposition from a small one
                sp.annotate(ranges=sum(
                    len(c.range_lo) for c in cfgs if c is not None
                ))
        for pos, cfg in zip(miss.values(), cfgs):
            for k in pos:
                out[k] = cfg
        with self._memo_lock:
            if self._memo_epoch != epoch:
                # a mutation invalidated mid-compute: these decompositions
                # reflect pre-write data — usable for THIS call (the
                # inherent plan/execute race) but never memoizable
                return out
            memo = self._config_memo
            for key, cfg in zip(miss, cfgs):
                memo[(idx, key)] = cfg
            while len(memo) > _CONFIG_MEMO_MAX:
                memo.popitem(last=False)
        return out

    # -- planning --------------------------------------------------------
    def plan(
        self,
        type_name: str,
        f: "Filter | str",
        limit: Optional[int] = None,
        explain: Explainer | None = None,
        intercept: bool = True,
        guard: "bool | None" = None,
    ) -> QueryPlan:
        """One filter's plan: :meth:`plan_many`'s stages for a batch of one.
        ``intercept=False`` skips the interceptor rewrite — for internal
        maintenance scans (age-off sweeps, delete_features, which guards
        must not reject either: ``guard`` defaults to ``intercept``) and
        for callers that already applied the rewrite themselves (pass
        ``guard=True`` to keep guarding those)."""
        if guard is None:
            guard = intercept
        return self._plan_members(
            type_name, [f], limit, [explain or ExplainNull()], intercept, guard
        )[0]

    def plan_many(
        self,
        type_name: str,
        filters: "list[Filter | str]",
        limit: Optional[int] = None,
    ) -> "list[QueryPlan]":
        """:meth:`plan` of every filter, each stage run ONCE for the batch
        (``DataStore.query_many``'s planning; ``plan`` is the batch of one):
        every plan equals, field for field, what ``plan(type_name, f,
        limit)`` returns for its filter on the same store; ``planning_s``
        is the batch's wall over its members.

        1. *parse, extract*: a member is parsed, normalized, intercepted
           and checked for hidden attributes on its own; its geometries,
           intervals and bounds are extracted once
           (``filter.extract.extract_filter``) for every index and the row
           estimate (an attribute index takes its own attribute's value
           bounds of the extraction's ``filter``).
        2. *decompose*, once an index: the memo is probed a member, the
           misses go to ``idx.scan_configs`` (one native call), and enter
           the memo under the epoch check of :meth:`_scan_configs`.
        3. *spans*, once a table: ``table.candidate_rows_many`` searches
           every member's ranges in one pass and leaves each config's
           candidate spans in its slot, where the dispatch finds them.
        4. *select, estimate, guard*: the cheapest index a member by
           ``(rows + 1) x multiplier``; the row estimates in array calls;
           guards and the health warning a plan.

        The array stages serve what they can see in the input: a type
        whose every index offers ``scan_configs`` and whose tables offer
        ``candidate_rows_many`` (z3, z2 and attribute indexes over plain or
        delta-tiered tables), and of its filters those some index serves;
        the ``plan`` span's ``costed``, ``attr_offered`` and ``attr_won``
        count them as :meth:`_select_single` counts its own. Any other
        member (an id filter, a filter no index serves, which may plan a
        union or a full scan; every member of any other type) goes, in
        its position, through :meth:`_select`: an index at a time.

        Traced: ONE ``plan`` span (``members``, ``batched`` = members
        through stages 2-3 as arrays; segments ``parse``, ``extract``,
        ``decompose``, ``spans``, ``estimate``), with a ``plan.probe`` and a
        ``plan.decompose`` child an index (``members``, ``ranges``)."""
        filters = list(filters)
        if not filters:
            return []
        return self._plan_members(
            type_name, filters, limit, [ExplainNull()] * len(filters), True, True
        )

    def _plan_members(
        self, type_name: str, filters: list, limit, exps: list,
        intercept: bool, guard: bool,
    ) -> list:
        """The plans of ``filters`` under ONE ``plan`` span; ``exps``: an
        Explainer a member.

        A member that is an ``Or`` of more than ``MAX_DISJUNCTS``
        box-and-interval slices (``filter.dnf.time_slices``) is planned as
        the union of its time-ordered groups, not as one scan of every box
        for the whole duration: the groups go through the stages as
        members of the batch, after the callers' own, and the member's plan
        is ``QueryPlan(union=groups)``; the single scan is not planned
        beside it. The span's ``sliced`` counts the groups planned, its
        ``slice_rows`` the slices that reached the indexes as the rows of
        two arrays (a ``Slices`` carrier's: a tube's, or the one
        ``time_slices`` made of an ``Or`` of ``And(BBox, During)``), 0 for
        every other filter."""
        from geomesa_tpu.conf import SCAN_RANGES_TARGET
        from geomesa_tpu.filter.dnf import time_slices

        t0 = time.perf_counter()
        n = len(filters)
        with _ospan("plan", cpu=True, type=type_name, members=n) as sp:
            sp.event("parse")
            filters = [self._prepare(type_name, f, intercept) for f in filters]
            for f, exp in zip(filters, exps):
                exp(f"Planning query on '{type_name}': {type(f).__name__}")
            dtg_field = self.store.get_schema(type_name).dtg_field
            sliced: dict = {}  # member -> the positions of its groups
            exps = list(exps)
            for m in range(n):
                groups = time_slices(filters[m], dtg_field)
                if groups is not None:
                    sliced[m] = range(len(filters), len(filters) + len(groups))
                    filters.extend(groups)
                    exps.extend([exps[m]] * len(groups))
            plans: list = [None] * len(filters)
            indexes = self.store.indexes(type_name)
            tables = self._batch_tables(type_name, indexes)
            arrays: set = set()  # the members the array stages planned
            slice_rows = 0
            if tables is not None:
                # the callers' own members at the range target each, then a
                # sliced member's groups, which share their query's target
                target = SCAN_RANGES_TARGET.get()
                for members, max_ranges in [(range(n), None)] + [
                    (groups, max(1, target // len(groups))) for groups in sliced.values()
                ]:
                    batch = [
                        m for m in members
                        if m not in sliced and extract_ids(filters[m]).empty
                    ]
                    if batch:
                        slice_rows += self._plan_arrays(
                            type_name, filters, batch, indexes, tables, limit, plans,
                            exps, sp, max_ranges,
                        )
                        arrays.update(m for m in batch if plans[m] is not None)
            sp.event("estimate")
            # the groups before the callers' members: a sliced one is their union
            for m in [*range(n, len(filters)), *range(n)]:
                if m in sliced:
                    plans[m] = self._union_of(
                        type_name, filters[m], limit, [plans[g] for g in sliced[m]],
                        exps[m], "time-ordered groups",
                    )
                    if plans[m] is not None and arrays.issuperset(sliced[m]):
                        arrays.add(m)
                if plans[m] is None:
                    # not one for the array stages: an index at a time
                    plans[m] = self._select(type_name, filters[m], limit, exps[m])
                    self._estimate_rows([plans[m]], [exps[m]])
            for m in range(n):
                self._finish(plans[m], guard, exps[m])
            sp.annotate(
                batched=sum(m < n for m in arrays),
                sliced=sum(map(len, sliced.values())),
                slice_rows=slice_rows,
            )
            if sp is not _NULL_SPAN:
                won = {plan.strategy for plan in plans[:n]}
                sp.annotate(index=won.pop() if len(won) == 1 else "mixed")
        share = (time.perf_counter() - t0) / n
        for plan in plans[:n]:
            plan.planning_s = share
        return plans[:n]

    @staticmethod
    def _union_of(
        type_name: str, f: Filter, limit, subs: list, exp, what: str
    ) -> Optional[QueryPlan]:
        """The union plan of ``f`` from the plans ``subs`` of the filters
        whose union it is (a DNF's disjuncts, a time-sliced member's
        groups; ``what`` names them in the explain trail): a branch its
        index finds disjoint is dropped, every branch is unlimited (the
        union's ``_post`` limits once), the row estimate is the branches'
        summed where each has one. None where a branch needs a full scan:
        one full scan of the whole filter beats a full scan a branch."""
        live = [p for p in subs if not (p.config is not None and p.config.disjoint)]
        if len(live) < len(subs):
            exp(f"Union: {len(subs) - len(live)} unsatisfiable, dropped")
        if any(p.strategy == "full-scan" for p in live):
            exp("Union: a branch needs a full scan -> single-scan plan")
            return None  # one full scan beats full scan + index scans
        if not live:
            return QueryPlan(type_name, f, None, ScanConfig.empty("union"), ids=[])
        if len(live) == 1:
            # every other branch was unsatisfiable: the live one IS the
            # query (its filter is equivalent to the whole filter)
            exp(f"Strategy: {live[0].strategy} (other branches unsatisfiable)")
            live[0].limit = limit
            return live[0]
        for p in live:
            p.limit = None
        exp(
            f"Strategy: union of {len(live)} {what} ("
            + ", ".join(p.strategy for p in live) + ")"
        )
        plan = QueryPlan(type_name, f, None, None, limit=limit, union=live)
        ests = [p.estimated_rows for p in live]
        if None not in ests:
            plan.estimated_rows = float(sum(ests))
        return plan

    def _plan_arrays(
        self, type_name, filters, batch, indexes, tables, limit, plans, exps, sp,
        max_ranges=None,
    ) -> int:
        """Stages 1 (extraction) to 4 of :meth:`plan_many` for the members
        ``batch`` of ``filters``: fills their ``plans`` where some index
        serves the member. ``max_ranges``: the most ranges a decomposition
        may emit (the branches of one query share its target), part of the
        memo's key where given; default the target, each. Returns the
        slices whose extraction is a ``Slices`` carrier's arrays."""
        from geomesa_tpu.filter.extract import extract_filter
        from geomesa_tpu.filter.predicates import canonical_key

        sp.event("extract")
        sft = self.store.get_schema(type_name)
        extractions = {
            m: extract_filter(filters[m], sft.geom_field, sft.dtg_field)
            for m in batch
        }
        keys = {m: canonical_key(filters[m]) for m in batch}
        budget = {}
        if max_ranges is not None:
            keys = {m: (key, max_ranges) for m, key in keys.items()}
            budget = {"max_ranges": max_ranges}

        # as _select_single: the indexes in order, and a member an index
        # finds disjoint is planned there (no later index decomposes it)
        sp.event("decompose")
        alive, served = batch, []
        for idx in indexes:
            cfgs = self._scan_configs(
                idx, [keys[m] for m in alive],
                lambda pos, at=alive: idx.scan_configs(
                    [extractions[at[k]] for k in pos], **budget
                ),
            )
            keep, kept, dead = [], [], []
            for m, cfg in zip(alive, cfgs):
                if cfg is not None and cfg.disjoint:
                    dead.append(m)
                    plans[m] = QueryPlan(type_name, filters[m], idx.name, cfg, limit=limit)
                    continue
                keep.append(m)
                if cfg is not None:
                    kept.append((m, cfg))
            served.append((idx.name, kept, dead))
            alive = keep

        sp.event("spans")
        best: dict = {}  # member -> (cost, index name, config), the first cheapest
        offers: dict = {}  # member -> the indexes that offered it a plan, in order
        for name, kept, dead in served:
            table = tables[name]
            mult = index_priority(name)
            if table is not None and kept:
                uniq = list({id(cfg): cfg for _, cfg in kept}.values())
                rows = dict(zip(map(id, uniq), table.candidate_rows_many(uniq).tolist()))
            for m, cfg in kept:
                cost = mult
                if table is not None:
                    cost *= rows[id(cfg)] + 1
                exps[m](f"Index {name}: {cfg.n_ranges} ranges, cost {cost:.1f}")
                offers.setdefault(m, []).append(name)
                if m not in best or cost < best[m][0]:
                    best[m] = (cost, name, cfg)
            for m in dead:
                exps[m](f"Index {name}: filter disjoint -> empty plan")
                # the indexes before this one costed it for nothing
                best.pop(m, None)
                offers.pop(m, None)

        sp.event("estimate")
        for m, (cost, name, cfg) in best.items():
            exps[m](f"Strategy: {name} (cost {cost:.1f})")
            plans[m] = QueryPlan(type_name, filters[m], name, cfg, limit=limit)
        # the decider's record, as _select_single writes it a plan: the
        # indexes costed, and of the members an attribute index offered a
        # plan for, those it won
        sp.add("costed", sum(map(len, offers.values())))
        offered = [
            m for m, names in offers.items() if any(n.startswith("attr_") for n in names)
        ]
        if offered:
            sp.add("attr_offered", len(offered))
            sp.add("attr_won", sum(best[m][1].startswith("attr_") for m in offered))
        self._estimate_rows(
            [plans[m] for m in best], [exps[m] for m in best],
            [extractions[m] for m in best],
        )
        return sum(
            len(ex.bounds) for ex in extractions.values() if isinstance(ex.bounds, np.ndarray)
        )

    def _batch_tables(self, type_name: str, indexes) -> "dict | None":
        """index name -> its table (None: no data written yet) where every
        index and table of the type offers the batched entries
        (``scan_configs``, ``candidate_rows_many``), else None."""
        if not all(hasattr(idx, "scan_configs") for idx in indexes):
            return None
        tables: dict = {}
        for idx in indexes:
            try:
                table = self.store.table(type_name, idx.name)
            except KeyError:
                table = None
            if table is not None and not hasattr(table, "candidate_rows_many"):
                return None
            tables[idx.name] = table
        return tables

    def _prepare(self, type_name: str, f: "Filter | str", intercept: bool) -> Filter:
        """A caller's filter as the stages plan it: parsed, normalized,
        and (``intercept``) rewritten by the interceptors and checked for
        attributes the auths may not see."""
        from geomesa_tpu.filter.predicates import normalize_antimeridian

        if isinstance(f, str):
            f = ecql.parse(f)
        f = normalize_antimeridian(f)
        if intercept:
            f = self.store.apply_interceptors(type_name, f)
            # attribute-level visibility closes at PLAN depth: a predicate
            # over a hidden attribute would evaluate against the hidden
            # values during scan/refinement, letting unauthorized auths
            # reconstruct them by probing (the reference's cell-level
            # visibility makes the cell unreadable to the scan itself)
            self._check_attr_visibility(type_name, f)
        return f

    def _finish(self, plan: QueryPlan, guard: bool, exp) -> None:
        if guard:
            self.store.apply_guards(plan)
        # degraded mode: a store that quarantined damaged partitions at
        # load answers from the survivors and WARNS instead of raising
        health = getattr(self.store, "health", None)
        if health is not None:
            w = health.warning_for(plan.type_name)
            if w is not None:
                plan.warnings = [w]
                exp.warn(w)

    def _estimate_rows(
        self, plans: list, exps: list, extractions: "list | None" = None
    ) -> None:
        """Resolve the stats-sketch row estimate of finished plans of ONE
        type (docs/observability.md "Estimate accountability"): the
        marginal-histogram selectivity product first (every plan's boxes
        and intervals in one histogram pass each; ``exps`` and, where the
        caller has them, ``extractions`` aligned with ``plans``), the
        z-prefix sketch of
        the chosen index as the fallback — the same two tiers
        ``estimate_count`` trusts. Skipped for id lookups (exact by
        construction — they would dilute the staleness signal with
        perfect scores) and disjoint plans (nothing scans)."""
        from geomesa_tpu import conf
        from geomesa_tpu.filter.extract import extract_filter

        if not plans or not conf.PLAN_ESTIMATE.get():
            return
        type_name = plans[0].type_name
        stats = self.store.stats_for(type_name)
        if stats is None:
            return
        sft = self.store.get_schema(type_name)
        todo, asked = [], []
        for k, plan in enumerate(plans):
            if plan.ids is not None or (
                plan.config is not None and plan.config.disjoint
            ):
                continue
            if isinstance(plan.filter, Include):
                self._note_estimate(plan, float(stats.total_count()), exps[k])
                continue
            todo.append(k)
            asked.append(
                extractions[k] if extractions is not None
                else extract_filter(plan.filter, sft.geom_field, sft.dtg_field)
            )
        for k, est in zip(todo, stats.estimate_extractions(sft, asked)):
            plan = plans[k]
            if est is None and plan.index is not None and plan.config is not None:
                est = stats.estimate_scan(plan.index, plan.config)
            if est is not None:
                self._note_estimate(plan, est, exps[k])

    @staticmethod
    def _note_estimate(plan: QueryPlan, est: float, exp) -> None:
        plan.estimated_rows = float(est)
        exp(f"Estimated rows: ~{est:.0f} (stats sketch)")

    def _check_attr_visibility(self, type_name: str, f: Filter) -> None:
        auths = getattr(self.store, "auths", None)
        if auths is None:
            return
        sft = self.store.get_schema(type_name)
        from geomesa_tpu.security import visible

        hidden = {
            a.name
            for a in sft.attributes
            if a.options.get("vis")
            and not visible(str(a.options["vis"]), frozenset(auths))
        }
        if not hidden:
            return
        used = _referenced_props(f)
        blocked = sorted(hidden & used)
        if blocked:
            raise QueryGuardError(
                f"filter references attribute(s) {blocked} whose "
                "visibility the configured auths do not satisfy"
            )

    def _select(
        self, type_name: str, f: Filter, limit: Optional[int], exp
    ) -> QueryPlan:
        plan = self._select_single(type_name, f, limit, exp)
        if plan.index is not None or plan.ids is not None:
            return plan
        # no single index serves the whole filter: try a multi-index union
        # over the DNF disjuncts (reference FilterSplitter.scala:61-147)
        union = self._select_union(type_name, f, limit, exp)
        return union if union is not None else plan

    def _select_union(
        self, type_name: str, f: Filter, limit: Optional[int], exp
    ) -> Optional[QueryPlan]:
        from geomesa_tpu.filter.dnf import rewrite_dnf

        disjuncts = rewrite_dnf(f)
        if disjuncts is None or len(disjuncts) < 2:
            return None
        subs: list[QueryPlan] = []
        for d in disjuncts:
            subs.append(self._select_single(type_name, d, None, exp))
            if subs[-1].strategy == "full-scan":
                break  # no union: the others need no plan
        return self._union_of(type_name, f, limit, subs, exp, "index scans")

    def _select_single(
        self, type_name: str, f: Filter, limit: Optional[int], exp
    ) -> QueryPlan:
        # id filters take absolute priority (reference IdFilterStrategy)
        ids = extract_ids(f)
        if ids.disjoint:
            exp("Id extraction: disjoint -> empty plan")
            return QueryPlan(type_name, f, None, ScanConfig.empty("id"), ids=[])
        if ids.values:
            exp(f"Strategy: id-lookup ({len(ids.values)} ids)")
            return QueryPlan(type_name, f, "id", None, ids=list(ids.values), limit=limit)

        indexes = self.store.indexes(type_name)
        options: list[tuple[float, str, ScanConfig]] = []
        for idx in indexes:
            cfg = self._scan_config(idx, f)
            if cfg is None:
                continue
            if cfg.disjoint:
                exp(f"Index {idx.name}: filter disjoint -> empty plan")
                return QueryPlan(type_name, f, idx.name, cfg, limit=limit)
            cost = self.cost(type_name, idx.name, cfg)
            options.append((cost, idx.name, cfg))
            exp(
                f"Index {idx.name}: {cfg.n_ranges} ranges, cost {cost:.1f}"
            )
        if not options:
            exp("Strategy: full-table host scan (no index serves this filter)")
            return QueryPlan(type_name, f, None, None, limit=limit)
        options.sort(key=lambda o: o[0])
        cost, name, cfg = options[0]
        exp(f"Strategy: {name} (cost {cost:.1f})")
        # on the caller's ``plan`` span: the indexes costed, and whether an
        # attribute index offered a plan and won (the decider's record)
        _oadd("costed", len(options))
        if any(o[1].startswith("attr_") for o in options):
            _oadd("attr_offered", 1)
            _oadd("attr_won", int(name.startswith("attr_")))
        return QueryPlan(type_name, f, name, cfg, limit=limit)

    def cost(self, type_name: str, index_name: str, cfg: ScanConfig) -> float:
        """Cost = estimated rows scanned x index multiplier (reference
        CostBasedStrategyDecider: stats.getCount x costMultiplier,
        StrategyDecider.scala:143-180). The primary estimator is exact —
        the sum of the searchsorted row spans the ranges cover, since the
        sorted keys are host-resident; the sketch estimate (Z3Histogram)
        and the bare priority constant are fallbacks."""
        mult = index_priority(index_name)
        try:
            table = self.store.table(type_name, index_name)
        except KeyError:
            return mult  # no data written yet
        rows = table.candidate_spans(cfg).n_rows()
        return (rows + 1) * mult

    # -- execution -------------------------------------------------------
    def execute(
        self,
        plan: QueryPlan,
        explain: Explainer | None = None,
        hints=None,
        deadline=None,
    ) -> FeatureCollection:
        """``deadline``: an optional pre-anchored Deadline (the serving
        tier charges queue wait against the caller's budget); default
        starts the clock here, from the hint/store timeout."""
        t0 = time.perf_counter()
        try:
            out = self._execute_or_cached(plan, explain, hints, deadline)
        except QueryTimeout:
            self._record_timeout(plan)
            raise
        self.store.record_query(plan, len(out), time.perf_counter() - t0)
        return out

    def _execute_or_cached(
        self,
        plan: QueryPlan,
        explain: Explainer | None = None,
        hints=None,
        deadline=None,
    ) -> FeatureCollection:
        """The result-cache tier around :meth:`_execute` (docs/caching.md):
        probe by canonical fingerprint, single-flight the scan on a miss,
        populate under cost-aware admission. Generation validation inside
        the cache guarantees a served entry reflects every committed
        mutation; the ``cache`` hint bypasses or pins per query."""
        cache = getattr(self.store, "cache", None)
        mode = getattr(hints, "cache", None) if hints is not None else None
        if cache is None or not cache.result.enabled or mode == "bypass":
            return self._execute(plan, explain, hints, deadline=deadline)
        exp = explain or ExplainNull()
        sft = self.store.get_schema(plan.type_name)
        key = cache.fingerprint_plan(
            plan, hints, sft, getattr(self.store, "auths", None)
        )
        key_range = cache.key_range(plan.filter, sft)

        def compute():
            s0 = time.perf_counter()
            value = self._execute(plan, explain, hints, deadline=deadline)
            return value, time.perf_counter() - s0

        t_probe = time.perf_counter()
        out, status, probe_s = cache.result.get_or_compute(
            key, plan.type_name, key_range, compute, pinned=(mode == "pin")
        )
        plan.cache_status = status
        plan.cache_probe_s = probe_s
        # the probe phase is the get_or_compute prefix BEFORE any scan:
        # recorded retroactively from the measured probe_s so a hit's
        # trace shows probe ~= the whole execute
        tr = _otracer()
        tr.add_span(
            tr.current(), "probe", t0=t_probe, end=t_probe + probe_s,
            status=status,
        )
        exp(f"cache: {status} (probe {probe_s * 1e3:.3f}ms, key {key[:12]})")
        return out

    def _record_timeout(self, plan) -> None:
        """A timed-out scan must still be recorded (reference audit writes
        failed scans too): bump the timeout counter so overdue queries are
        visible in metrics instead of vanishing with the exception."""
        metrics = getattr(self.store, "metrics", None)
        if metrics is not None:
            metrics.counter("geomesa.query.timeout")

    def _deadline(self, hints):
        """Monotonic cutoff from the hint timeout or the store default."""
        timeout = getattr(hints, "timeout", None) if hints is not None else None
        if timeout is None:
            timeout = getattr(self.store, "query_timeout", None)
        return deadline_from(timeout)

    def _execute(
        self,
        plan: QueryPlan,
        explain: Explainer | None = None,
        hints=None,
        branch: bool = False,
        deadline=None,
    ) -> FeatureCollection:
        exp = explain or ExplainNull()
        if hints is not None:
            hints.validate()
        if deadline is None:
            deadline = self._deadline(hints)
        prog = getattr(self.store, "_fold_progress", {}).get(plan.type_name)
        if prog is not None:
            # lock-free snapshot of the sliced-fold progress surface
            # (docs/streaming.md): the query is interleaving with an
            # in-flight incremental fold — visible in explain alongside
            # the geomesa.stream.fold.progress gauge
            exp(f"Streaming fold in progress: slice {prog[0]}/{prog[1]}")

        if plan.union is not None:
            return self._execute_union(plan, exp, hints, deadline)

        hidden, masked = (0, 0), False  # did _visible decide the rows
        if plan.ids is not None:  # id lookup
            # one snapshot resolves AND gathers: a fold publishing in
            # between cannot shift the ordinals under the gather
            chunks = self.store.chunk_snapshot(plan.type_name)
            ordinals = self.store.id_lookup(
                plan.type_name, plan.ids, chunks=chunks
            )
            ordinals, _, hidden = self._visible(plan, ordinals, None, chunks, exp)
            masked = True
            candidates = self.store.gather(
                plan.type_name, ordinals, chunks=chunks
            )
        elif plan.index is None:  # full host scan
            fc = self.store.features(plan.type_name)
            check_deadline(deadline, "full-table scan start")
            with _ospan("scan", index="full"):
                with exp.span("Full-table host scan"):
                    mask = plan.filter.evaluate(fc.batch)
            check_deadline(deadline, "full-table scan")
            self._note_actual(plan, int(mask.sum()), exp)
            with _ospan("decode", candidates=plan.actual_rows) as sp:
                if getattr(self.store, "auths", None) is None:
                    return self._post(fc.mask(mask), plan, hints, exp, branch)
                # a table of one chunk has that chunk's dictionary: the
                # labels hide rows of the filter's mask before the ONE copy
                # of the rows kept. The concatenation of several chunks is
                # no chunk and has none: ``_post`` masks it by its strings
                kept = np.flatnonzero(mask)
                coded = any(fc is c for c in self.store.chunk_snapshot(plan.type_name))
                if coded:
                    kept = self._visible(plan, kept, None, [fc], exp, sp)[0]
                sp.event("post")
                return self._post(
                    fc.take(kept), plan, hints, exp, branch, rows_masked=coded,
                )
        elif plan.index is not None and self.store.row_count(plan.type_name) == 0:
            # schema exists but nothing written yet: no index tables. Rows
            # that a first write lands between the two reads came with no
            # ordinals: ``_post`` masks them by their strings
            candidates = self.store.features(plan.type_name)
        else:
            # simple index scan: the shared dispatch/finish implementation
            # (finish runs immediately here; query_many defers it)
            return self._submit_simple(
                plan, exp, hints, branch, deadline=deadline
            )()

        return self._refine_and_post(
            plan, candidates, None, hints, exp, deadline, branch,
            hidden=hidden, rows_masked=masked,
        )

    def _submit_simple(self, plan, exp, hints, branch=False,
                       finish_scan=None, deadline=None, chunks=None,
                       member=None):
        """Dispatch a simple index-scan plan's device work now; return
        ``finish()`` -> FeatureCollection. ONE implementation serves both
        the synchronous path (_execute calls finish immediately) and the
        pipelined path (execute_many defers it). By default the deadline
        clock starts when finish() runs — matching sequential semantics,
        so a late pull in a long batch doesn't spuriously time out; an
        explicit ``deadline`` (a Deadline) overrides that — the serving
        tier anchors it at ADMISSION so queue wait is charged against
        the caller's budget instead of restarting it at dispatch.

        Candidates gather through ``store.gather`` (per-chunk takes), so
        a delta tier freshly grown by a streaming flush never makes a
        query pay the whole-table chunk concat. The chunk snapshot is
        PINNED at dispatch, next to the table capture: the scan's
        ordinals are table ordinals, and a fold/delete publishing during
        the dispatch->finish window must not shift the rows they gather
        (renumbering publishes swap in a fresh chunk list and leave the
        pinned one untouched).

        ``finish_scan``: an already-dispatched scan's finish (submit_many's
        fused group scans); default dispatches this plan's own scan.
        ``chunks``: the chunk snapshot captured when that scan was
        dispatched (submit_many); default captures one here.
        ``member``: this plan's position in a submit_many batch, carried
        on its ``scan`` and ``decode`` spans."""
        tag = {} if member is None else {"member": member}
        if finish_scan is None:
            with _ospan("dispatch", index=plan.index):
                table, chunks = self.store.pin_scan_state(
                    plan.type_name, plan.index
                )
                finish_scan = table.scan_submit(plan.config, deadline=None)
        elif chunks is None:
            chunks = self.store.chunk_snapshot(plan.type_name)

        def finish(deadline=deadline) -> FeatureCollection:
            if deadline is None:
                deadline = self._deadline(hints)
            with _ospan("scan", index=plan.index, **tag):
                with exp.span(f"Device scan [{plan.index}]"):
                    # single-chip and distributed tables share one engine
                    # and one contract: (ordinals, certainty vector)
                    ordinals, certain = finish_scan()
                check_deadline(deadline, "scan result pull")
            exp(f"Candidates: {len(ordinals)}")
            with _ospan(
                "decode", cpu=True, candidates=len(ordinals), **tag
            ) as sp:
                ordinals, certain, hidden = self._visible(
                    plan, ordinals, certain, chunks, exp, sp
                )
                sp.event("gather")
                candidates = self.store.gather(
                    plan.type_name, ordinals, chunks=chunks
                )
                sp.add("gather_native", int(candidates.gathered_native))
                return self._refine_and_post(
                    plan, candidates, certain, hints, exp, deadline,
                    branch, span=sp, hidden=hidden, rows_masked=True,
                )

        return finish

    def _visible(self, plan, ordinals, certain, chunks, exp, span=_NULL_SPAN):
        """Row-level security on a route's ORDINALS, before its gather:
        ``(ordinals, certain, hidden)`` narrowed to the candidates whose
        label the store's auths satisfy, looked up from the label codes
        of the ``chunks`` the ordinals number (``security.mask_ordinals``,
        the span ``vis``; ``span``: the caller's ``decode``, which gets a
        ``vis`` segment). ``hidden``: the candidates dropped, (those the
        device mask was certain of, the others), for
        :meth:`_refine_and_post`'s accuracy record alone. A store without
        auths, or a type without a label field, leaves all as it came:
        ONE ``auths is None`` test and nothing built."""
        auths = getattr(self.store, "auths", None)
        codes = None
        if auths is not None and len(ordinals):
            span.event("vis")
            codes = self.store.label_codes(plan.type_name, chunks)
        if codes is None:
            return ordinals, certain, (0, 0)
        from geomesa_tpu.security import mask_ordinals

        seen = mask_ordinals(codes, ordinals, auths)
        n_hidden = len(seen) - int(np.count_nonzero(seen))
        exp(f"Visibility filter: {len(seen) - n_hidden} visible")
        if not n_hidden:
            return ordinals, certain, (0, 0)
        sure = 0 if certain is None else int(np.count_nonzero(certain & ~seen))
        return (
            ordinals[seen], None if certain is None else certain[seen],
            (sure, n_hidden - sure),
        )

    def _refine_and_post(
        self, plan, candidates, certain, hints, exp, deadline,
        branch=False, span=_NULL_SPAN, hidden=(0, 0), rows_masked=False,
    ):
        """Refinement tiers (reference Z3IndexKeySpace.useFullFilter,
        Z3IndexKeySpace.scala:240-254, automatic since round 3):
        - the device mask decides the filter: only *uncertain* boundary
          rows (wide & ~inner; f32/offset rounding) re-check on host;
        - `loose` hint: accept the widened mask outright (reference
          LOOSE_BBOX semantics);
        - otherwise: exact full-filter refinement over all candidates.

        ``span``: the caller's ``decode`` span, cut here into its
        ``refine`` and ``post`` segments. ``rows_masked``: ``candidates``
        are what :meth:`_visible` left of the route's ordinals, gathered
        (``hidden``: the (certain, other) candidates it dropped); false
        for rows that came by no ordinals, which ``_post`` then masks."""
        span.event("refine")
        decided = mask_decides_filter(
            plan.filter, plan.config, self.store.get_schema(plan.type_name)
        )
        loose_ok = hints is not None and getattr(hints, "loose", False) and decided
        # of the hidden candidates: those the filter matches for sure and
        # those it was still to decide; ``undecided``: what it decided of
        # such rows among the visible (None: the mask is accepted whole)
        (hid_sure, hid_open), undecided = hidden, None
        if loose_ok or (decided and isinstance(plan.filter, Include)):
            exp("Loose mode: device mask accepted without refinement")
        elif decided and certain is not None:
            unc = np.flatnonzero(~certain)
            exp(f"Refinement: {len(unc)} uncertain of {len(certain)} candidates")
            undecided = unc
            if len(unc):
                check_deadline(deadline, "boundary refinement start")
                with exp.span("Boundary refinement"):
                    sub_mask = plan.filter.evaluate(candidates.take(unc).batch)
                undecided = sub_mask
                keep = certain.copy()
                keep[unc] = sub_mask
                # all-true keep: `candidates` is already a fresh gather
                # (fc.take above), so skipping the re-gather is safe and
                # halves the host cost when refinement drops nothing
                if not bool(keep.all()):
                    candidates = candidates.mask(keep)
        elif not isinstance(plan.filter, Include):
            check_deadline(deadline, "residual refinement start")
            # the whole filter over every candidate: an attribute
            # predicate the chosen index did not bind is decided here
            span.add("residual_rows", len(candidates))
            with exp.span("Residual filter refinement"):
                mask = plan.filter.evaluate(candidates.batch)
            hid_sure, hid_open, undecided = 0, hid_sure + hid_open, mask
            if not bool(np.all(mask)):  # see all-true note above
                candidates = candidates.mask(mask)
        check_deadline(deadline, "refinement")
        # estimate accountability: the POST-refinement row count — what
        # the sketch estimate actually predicts (filter selectivity) —
        # before _post's limit stage distorts it. The
        # pre-refinement candidate count would charge index
        # over-selection (a z2 scan serving a temporal filter) to the
        # sketches, flagging fresh stats stale forever. The sketches count
        # rows whatever their label, so the candidates the labels hid
        # before refinement are counted too, as the filter would have
        # decided them (the undecided ones at the share it kept of such
        # rows among the visible, one half where it decided none): a
        # secured store's estimates must not look stale for the rows its
        # callers may not read. This number enters no answer.
        if hid_open:
            share = 1.0 if undecided is None else (
                float(np.mean(undecided)) if len(undecided) else 0.5
            )
            hid_sure += int(round(hid_open * share))
        self._note_actual(plan, len(candidates) + hid_sure, exp)
        span.event("post")
        return self._post(
            candidates, plan, hints, exp, branch, rows_masked=rows_masked
        )

    @staticmethod
    def _note_actual(plan, actual: int, exp) -> None:
        """Record one executed plan's matched-row count next to its
        sketch estimate (explain line; record_query feeds the pair to
        the error histogram and the per-index accuracy windows)."""
        plan.actual_rows = actual
        if plan.estimated_rows is not None:
            from geomesa_tpu.obs.accuracy import error_factor

            exp(
                f"Estimate vs actual: ~{plan.estimated_rows:.0f} est / "
                f"{actual} matched "
                f"({error_factor(plan.estimated_rows, actual):.2f}x)"
            )

    # -- pipelined multi-query execution ---------------------------------
    def _is_simple(self, plan: QueryPlan) -> bool:
        """True when the plan is a plain index scan whose device work can
        dispatch ahead of finish() (no union/id/full-scan special-casing).
        ONE predicate shared by submit and submit_many so their routing
        can never drift."""
        return (
            plan.union is None
            and plan.ids is None
            and plan.index is not None
            and plan.config is not None
            and self.store.row_count(plan.type_name) > 0
        )

    def submit(self, plan: QueryPlan, explain: Explainer | None = None,
               hints=None, deadline=None, member=None):
        """Stage one query: dispatch its device scan NOW, return a zero-arg
        ``finish()`` producing the FeatureCollection. Plans without a
        simple index scan (unions, id lookups, full scans) fall back to
        synchronous execution inside finish(); an explicit ``deadline``
        (a pre-anchored Deadline — the serving tier's admission time)
        bounds both paths, default starts each budget at finish()."""
        exp = explain or ExplainNull()
        if not self._is_simple(plan):
            return lambda: self.execute(
                plan, explain=exp, hints=hints, deadline=deadline
            )
        if hints is not None:
            hints.validate()
        return self._record_wrap(plan, self._submit_simple(
            plan, exp, hints, deadline=deadline, member=member
        ))

    def _record_wrap(self, plan, inner):
        """finish() wrapper adding query auditing (record_query timing) —
        ONE implementation for submit and submit_many's fused finishes, so
        batched and single queries are always audited identically."""

        def finish() -> FeatureCollection:
            t0 = time.perf_counter()
            try:
                out = inner()
            except QueryTimeout:
                self._record_timeout(plan)
                raise
            self.store.record_query(plan, len(out), time.perf_counter() - t0)
            return out

        return finish

    def submit_many(self, plans, hints=None, explains=None, deadlines=None) -> list:
        """Stage MANY queries: like per-plan :meth:`submit`, but simple
        index-scan plans sharing a (type, index) table route through the
        table's fused multi-query kernel (``scan_submit_many`` — one
        device dispatch per kernel-variant group instead of one per
        query). Returns one ``finish()`` per plan, in input order.
        Non-simple plans (unions, id lookups, full scans) fall back to
        :meth:`submit`, which executes them synchronously inside their
        finish() — only simple index scans dispatch ahead of the pulls.

        ``hints``: one QueryHints applied to every plan, or a sequence
        aligned with ``plans`` — the serving tier (geomesa_tpu.serving)
        batches independent callers carrying DIFFERENT hints into one
        fused dispatch; hints shape only post-processing and deadlines,
        never the device scan, so mixed-hints plans still fuse.
        ``explains``: optional per-plan Explainer sequence — fused
        members trace their device scan/refinement like sequential
        execution. ``deadlines``: optional per-plan Deadline sequence
        anchoring each plan's budget (fused scans AND non-simple
        fallbacks) at an earlier instant — the serving tier's admission
        time — instead of at its finish()."""
        def aligned(seq, what):
            if seq is None:
                return [None] * len(plans)
            if len(seq) != len(plans):
                raise ValueError(
                    f"{what} sequence length {len(seq)} != plans {len(plans)}"
                )
            return list(seq)

        if isinstance(hints, (list, tuple)):
            per = aligned(hints, "hints")
        else:
            per = [hints] * len(plans)
        exps = aligned(explains, "explains")
        dls = aligned(deadlines, "deadlines")
        with _ospan("dispatch", members=len(plans)):
            return self._stage_many(plans, per, exps, dls)

    def _stage_many(self, plans, per, exps, dls, branch: bool = False) -> list:
        """submit_many's staging, under its ``dispatch`` span (a member
        that dispatches alone nests its own ``dispatch`` inside).
        ``branch``: the plans are the simple branches of ONE union
        (:meth:`_execute_union`), which audits the query once and hides
        attributes once over the merged rows: no branch does either."""
        finishes: list = [None] * len(plans)
        groups: dict[tuple, list[int]] = {}
        for j, plan in enumerate(plans):
            if not self._is_simple(plan):
                finishes[j] = self.submit(
                    plan, explain=exps[j], hints=per[j], deadline=dls[j]
                )
            else:
                groups.setdefault((plan.type_name, plan.index), []).append(j)
        seen: set = set()  # validate each distinct hints object once
        for idxs in groups.values():
            for j in idxs:
                h = per[j]
                if h is not None and id(h) not in seen:
                    seen.add(id(h))
                    h.validate()
        for (tname, iname), idxs in groups.items():
            table, chunks = self.store.pin_scan_state(tname, iname)
            many = getattr(table, "scan_submit_many", None)
            if many is None or len(idxs) == 1:
                scan_fins, chunks = [None] * len(idxs), None  # each dispatches its own
            else:
                scan_fins = many([plans[j].config for j in idxs])
            for j, scan_fin in zip(idxs, scan_fins):
                finish = self._submit_simple(
                    plans[j], exps[j] or ExplainNull(), per[j], branch,
                    finish_scan=scan_fin, deadline=dls[j], chunks=chunks,
                    member=j,
                )
                finishes[j] = finish if branch else self._record_wrap(plans[j], finish)
        return finishes

    def execute_many(self, plans, hints=None) -> list:
        """Execute several plans with overlapped device work: every scan
        dispatches before any result is pulled, so per-query round-trip
        latency pipelines instead of serializing (a throughput API — the
        reference gets the same effect from server-side thread pools,
        utils/AbstractBatchScan; here jax async dispatch provides it).
        Scans sharing a table additionally fuse into one kernel dispatch
        per variant group (submit_many)."""
        finishes = self.submit_many(plans, hints=hints)
        return [f() for f in finishes]

    def _execute_union(self, plan: QueryPlan, exp, hints, deadline) -> FeatureCollection:
        """Run every union branch on its own index and dedup-union by
        feature id (reference: per-option scans merged client-side with
        deduplication, FilterSplitter OR semantics). Each branch refines
        with its own disjunct filter, so the union is exact; the rows come
        branch by branch. The branches that are simple index scans share
        ONE ``dispatch`` (:meth:`_stage_many`: fused where they share a
        table, as ``query_many``'s members are) with a ``scan`` and a
        ``decode`` each; any other branch executes on its own. The query's
        ONE deadline bounds all branches: each gets the remaining budget,
        not a fresh one. Each branch drops the candidates its labels hide
        on their ordinals (:meth:`_visible`), so the merged rows need no
        mask; attribute-level visibility runs once, in the final _post."""
        from geomesa_tpu.planning.hints import QueryHints

        def alone(sp):
            sub_hints = None
            if deadline is not None:
                check_deadline(deadline, f"union branch [{sp.strategy}]")
                sub_hints = QueryHints(timeout=max(deadline.remaining(), 1e-9))
            return self._execute(sp, explain=exp, hints=sub_hints, branch=True)

        # the branches that are simple index scans dispatch together,
        # fused a table; their pulls and every other branch follow in order
        simple = [sp for sp in plan.union if self._is_simple(sp)]
        staged: dict = {}
        if simple:
            check_deadline(deadline, "union dispatch")
            n = len(simple)
            with _ospan("dispatch", members=n):
                staged = dict(zip(map(id, simple), self._stage_many(
                    simple, [None] * n, [exp] * n, [deadline] * n, branch=True
                )))
        parts = []
        for sp in plan.union:
            with exp.span(f"Union branch [{sp.strategy}]"):
                finish = staged.get(id(sp))
                parts.append(alone(sp) if finish is None else finish())
        check_deadline(deadline, "union merge")
        # rows the branches' filters matched that their labels hid before
        # the gather (each branch's record less its answer: see
        # _refine_and_post), for the accuracy record alone
        hid = max(sum(sp.actual_rows or 0 for sp in plan.union) - sum(map(len, parts)), 0)
        nonempty = [p for p in parts if len(p)]
        if not nonempty:
            self._note_actual(plan, hid, exp)
            return self._post(parts[0], plan, hints, exp, rows_masked=True)
        out = nonempty[0] if len(nonempty) == 1 else FeatureCollection.concat(nonempty)
        n_parts = len(out)
        _, first = np.unique(np.asarray(out.ids), return_index=True)
        if len(first) != len(out):
            exp(f"Union dedup: {len(out)} -> {len(first)} rows")
            out = out.take(np.sort(first))
        # the union's matched rows BEFORE _post's limit
        # stage: record_query's hits fallback would compare the sketch
        # estimate against a truncated result (see _note_actual); the
        # hidden rows at the share of the visible that were distinct
        self._note_actual(
            plan, len(out) + int(round(hid * len(out) / n_parts)), exp
        )
        return self._post(out, plan, hints, exp, rows_masked=True)

    def _post(self, out, plan, hints, exp, branch: bool = False,
              rows_masked: bool = False):
        """Client-side reduce pipeline: visibility -> sample -> sort ->
        offset -> limit -> project (reference QueryPlanner.scala:66-102
        runs the same stages after the scan: reducer, sort, startIndex,
        maxFeatures, projection). ``rows_masked``: the route decided
        row-level visibility on its ordinals, before the gather
        (:meth:`_visible`); a collection that came without that is masked
        here, by its label strings. ``branch``: the rows of one branch of
        a union, whose own ``_post`` hides attributes once."""
        auths = getattr(self.store, "auths", None)
        if auths is not None:
            from geomesa_tpu.security import (
                VIS_FIELD_KEY, mask_collection, visible,
            )

            sft = self.store.get_schema(plan.type_name)
            vis_field = sft.user_data.get(VIS_FIELD_KEY)
            if vis_field and len(out) and not rows_masked:
                # row-level security (reference VisibilityEvaluator tier)
                # for rows no ordinals came with: traced as ``vis`` too
                # (``coded`` 0, under ``decode``'s ``post`` segment)
                out = mask_collection(out, vis_field, auths)
                exp(f"Visibility filter: {len(out)} visible")
            # attribute-level security (reference geomesa-security
            # SecurityUtils per-attribute labels): an attribute whose
            # ``vis=<label>`` option the auths cannot satisfy is PROJECTED
            # OUT of the result — rows stay visible, the value does not
            hidden = [] if branch else [
                a.name
                for a in out.sft.attributes
                if a.options.get("vis")
                and not visible(str(a.options["vis"]), frozenset(auths))
            ]
            if hidden:
                keep = [a.name for a in out.sft.attributes if a.name not in hidden]
                out = out.project(keep)
                exp(f"Attribute visibility: hid {hidden}")
        exp(f"Hits: {len(out)}")
        if hints is not None:  # validated at _execute entry
            if hints.sample is not None:
                out = out.sample(hints.sample, hints.sample_by)
                exp(f"Sampled: {len(out)}")
        sort_by = hints.sort_by if hints is not None else None
        off = hints.offset if hints is not None and hints.offset else 0
        paged = off or (plan.limit is not None and len(out) > plan.limit)
        if sort_by or paged:
            # ``sort`` (traced): the ordering of every matched row and the
            # page kept of it, ``rows`` in and ``kept`` out
            with _ospan("sort", rows=len(out)) as sp:
                if sort_by:
                    out = out.sort_values(sort_by)
                if paged:
                    # one gather for the page: materializing the whole
                    # post-offset tail before the limit would copy every
                    # column of a large result just to keep a page of it
                    lo = min(off, len(out))
                    hi = len(out) if plan.limit is None else min(lo + plan.limit, len(out))
                    out = out.take(np.arange(lo, hi))
                    if off:
                        exp(f"Offset {off}: rows [{lo}, {hi})")
                sp.annotate(kept=len(out))
        if hints is not None and hints.reproject is not None:
            from geomesa_tpu.crs import reproject_collection

            out = reproject_collection(out, hints.reproject)
            exp(f"Reprojected to {hints.reproject}")
        if hints is not None and hints.transforms is not None:
            out = out.transform(hints.transforms)
        return out
