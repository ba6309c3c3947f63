"""QueryScheduler: coalesce concurrent callers into fused device dispatches.

The serving story before this tier: the fused multi-query kernel path
(``block_scan_multi`` -> ``IndexTable.scan_submit_many`` ->
``QueryPlanner.submit_many``) only helps callers who already HOLD a list
of plans. N independent threads each calling ``DataStore.query()`` get N
serialized single-query dispatches, each paying the full per-dispatch
cost plus the device-pull floor (PERF.md §1). The reference gets
concurrency from server-side thread pools (utils/AbstractBatchScan); the
TPU build gets it from an admission layer in front of the device:

- callers ``submit()`` (plan, hints) into a bounded queue and receive a
  future; planning runs in the CALLER's thread so plan-time errors
  (parse, guards, visibility) raise synchronously at submit;
- a dispatcher thread drains the queue in a short micro-batch window —
  ADAPTIVE: it shrinks toward zero when batches come back singular (an
  idle store adds ~no latency) and grows toward the
  ``geomesa.serving.window_ms`` cap when batches fuse (load);
- each drained batch routes through ``QueryPlanner.submit_many``, which
  groups compatible simple index-scan plans per (type, index) and
  dispatches ONE fused kernel per variant group instead of one per
  caller (non-simple plans — unions, id lookups, full scans — ride along
  on their synchronous fallback);
- admission is cache-aware: a ResultCache peek before enqueue serves
  hits in the caller's thread (hits never queue), and identical
  fingerprints arriving in the same window collapse onto one slot
  (complementing the cache's single-flight, which only coalesces
  mid-scan); computed results populate the cache under its normal
  admission policy;
- admission is deadline-aware: a query whose timeout would expire inside
  the batch window (or already expired while queued) is shed immediately
  with QueryTimeout, and a full bounded queue applies backpressure
  (block) or sheds (``block=False`` -> ServingRejected) — both counted
  by ``geomesa.serving.shed`` — rather than buffering unboundedly.

Metrics: counters geomesa.serving.submitted / .shed / .coalesced /
.batches / .batched_queries (mean fused batch size =
batched_queries/batches); gauge geomesa.serving.window_ms (current
adaptive window); histogram geomesa.serving.queue_wait (via
record_query — live queue-wait quantiles, docs/observability.md).

Results are byte-identical to sequential ``DataStore.query()``: the
scheduler reuses the planner's plan/refine/post pipeline end to end
(tests/test_query_many.py threads the equivalence matrix through it).
A query racing a concurrent write answers as of its ADMISSION (plans
are built at submit; block pruning still runs against the
dispatch-time table) — the same snapshot semantics as a plain query()
whose plan/execute straddles the write; see docs/serving.md.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass
from typing import Optional


class ServingRejected(Exception):
    """The bounded admission queue was full and the caller asked not to
    wait (``submit(block=False)``): the query was shed, not queued."""


def _resolve(fut: Future, value=None, exc: Optional[BaseException] = None) -> None:
    """Resolve a caller future, tolerating a client-side ``cancel()``
    (disconnect): a cancelled future has no listener, and a bare
    set_result on it raises InvalidStateError — which must not poison
    the co-batched queries sharing the dispatch."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(value)
    except InvalidStateError:
        pass


@dataclass
class ServingConfig:
    """Scheduler knobs. Every field left unset resolves from the conf.py
    property tier (environment-overridable — see
    ``geomesa_tpu.conf.describe()``), so a partial override like
    ``ServingConfig(window_ms=5.0)`` still honors the operator's env
    settings for the other knobs."""

    window_ms: "float | None" = None   # adaptive micro-batch window CAP
    queue_max: "int | None" = None     # bounded admission queue depth
    batch_max: "int | None" = None     # max queries per fused dispatch

    def __post_init__(self):
        from geomesa_tpu import conf

        if self.window_ms is None:
            self.window_ms = conf.SERVING_WINDOW_MS.get()
        if self.queue_max is None:
            self.queue_max = conf.SERVING_QUEUE_MAX.get()
        if self.batch_max is None:
            self.batch_max = conf.SERVING_BATCH_MAX.get()

    @staticmethod
    def from_properties() -> "ServingConfig":
        return ServingConfig()


class _Item:
    """One admitted query waiting for dispatch."""

    __slots__ = (
        "plan", "hints", "future", "key", "key_range", "epoch", "timeout",
        "deadline", "t_enqueue", "t_admit", "explain", "trace", "tenant",
    )

    def __init__(self, plan, hints, future, explain):
        self.plan = plan
        self.hints = hints
        self.future = future
        self.explain = explain
        self.tenant = None     # fairness queue key (None = default pool)
        self.trace = None      # obs trace root (None when disarmed): the
        #                        query's span tree follows the item across
        #                        the submit -> dispatcher thread hop
        self.key = None        # cache fingerprint
        self.key_range = None  # cache invalidation range (cache-enabled)
        self.epoch = 0         # store mutation epoch at admission: the
        #                        coalescing key is (key, epoch), so a
        #                        query admitted after a write never
        #                        shares a pre-write leader's result
        self.timeout = None    # resolved budget in seconds
        self.deadline = None   # monotonic cutoff from submit time
        self.t_enqueue = 0.0
        self.t_admit = 0.0     # perf_counter after planning: the admit
        #                        phase (fingerprint/peek/backpressure) is
        #                        t_admit -> t_enqueue on the trace


class QueryScheduler:
    """Micro-batch scheduler between concurrent callers and one
    DataStore's planner. ``DataStore.serve()`` builds, starts and
    attaches one; standalone construction + ``start()`` works too (tests
    construct unstarted schedulers to stage deterministic queues)."""

    def __init__(self, store, config: "ServingConfig | None" = None,
                 metrics=None, tenants=None):
        from geomesa_tpu.metrics import resolve

        from geomesa_tpu.lockwitness import witness

        self.store = store
        self.conf = config or ServingConfig.from_properties()
        self.metrics = resolve(metrics if metrics is not None else store.metrics)
        # multi-tenant fairness (serving/tenancy.py): per-tenant quota +
        # DRR weights. The registry's lock is NEVER touched under _cond —
        # quotas read before admission, weights snapshot before each drain
        self.tenants = tenants
        self._cond = witness(threading.Condition(), "QueryScheduler._cond")
        # per-tenant FIFO queues (None key = the default pool when no
        # tenant was named); a single populated queue drains as plain
        # FIFO, several drain by weighted deficit round-robin
        self._queues: "dict[Optional[str], deque[_Item]]" = {}  # guarded-by: _cond
        self._depth = 0                # guarded-by: _cond
        self._closed = False           # guarded-by: _cond
        # DRR credit per backlogged tenant — dispatcher-thread-only state
        self._deficit: "dict[Optional[str], float]" = {}
        # adaptive window: grows under load, 0 when idle. Single-writer
        # (only the dispatcher thread mutates it); submit()'s lock-free
        # read of a slightly stale value only mistimes one shed decision
        self._window_s = 0.0
        self._thread: Optional[threading.Thread] = None  # guarded-by: _cond

    # -- lifecycle -------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def window_s(self) -> float:
        """The current adaptive micro-batch window in seconds."""
        return self._window_s

    @property
    def queue_depth(self) -> int:
        """Queries currently waiting in the admission queue, across all
        tenants (locked read — the ops plane's ``/health`` scheduler
        check)."""
        with self._cond:
            return self._depth

    def start(self) -> "QueryScheduler":
        with self._cond:
            if self._closed:
                raise RuntimeError("scheduler is closed")
            if self._thread is None:
                from geomesa_tpu.obs.trace import as_role

                self._thread = threading.Thread(
                    target=as_role("dispatcher", self._loop),
                    name="geomesa-serving", daemon=True,
                )
                self._thread.start()
        return self

    def close(self, timeout: float = 30.0) -> None:
        """Stop accepting queries, drain what's queued (the dispatcher
        finishes in-flight work), then fail anything still pending (a
        never-started scheduler, or a drain that exceeded ``timeout``)."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        with self._cond:
            pending = [it for q in self._queues.values() for it in q]
            self._queues.clear()
            self._depth = 0
        for it in pending:
            if not it.future.done():
                if it.trace is not None:
                    from geomesa_tpu.obs.trace import tracer

                    tracer().end(it.trace)
                _resolve(it.future, exc=RuntimeError("scheduler closed"))

    def __enter__(self) -> "QueryScheduler":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- admission -------------------------------------------------------
    def submit(
        self,
        type_name: str,
        f="INCLUDE",
        limit: Optional[int] = None,
        hints=None,
        explain=None,
        block: bool = True,
        tenant: Optional[str] = None,
    ) -> Future:
        """Admit one query; returns a Future resolving to its
        FeatureCollection. Plan-time errors (ECQL parse, guards,
        visibility) raise HERE, in the caller's thread; execution errors
        (QueryTimeout, scan failures) land on the future. ``block``:
        whether a full admission queue blocks the caller (backpressure)
        or sheds immediately with ServingRejected. ``tenant`` routes the
        query into that tenant's fairness queue (per-tenant quota + DRR
        share when a TenantRegistry is attached; None = default pool)."""
        if self._closed:
            raise RuntimeError("scheduler is closed")
        from geomesa_tpu.obs.trace import tracer

        planner = self.store.planner
        # captured BEFORE planning: the submitter's own completed writes
        # have already bumped it, so read-your-writes holds at admission
        epoch = planner.mutation_epoch
        # the query's trace roots HERE, in the caller's thread: planning
        # spans land now; queue/dispatch/scan phases attach later from
        # the dispatcher thread (the item carries the root across)
        otr = tracer()
        trace = otr.begin("query", type=type_name, serving=True)
        try:
            with otr.activate(trace.root if trace is not None else None):
                plan = planner.plan(type_name, f, limit=limit, explain=explain)
                if hints is not None:
                    # validate in the CALLER's thread: one submitter's bad
                    # hints must raise here, not fail the co-batched dispatch
                    hints.validate()
        except BaseException:
            otr.end(trace)  # plan-time error: the trace still closes
            raise
        if trace is not None:
            # rendered only if the slow-query log takes the trace
            trace.fingerprint = lambda: {
                "type": type_name,
                "strategy": plan.strategy,
                "filter": str(plan.filter),
            }
        fut: Future = Future()
        it = _Item(plan, hints, fut, explain)
        it.epoch = epoch
        it.trace = trace
        it.t_admit = time.perf_counter()
        it.timeout = getattr(hints, "timeout", None) if hints is not None else None
        if it.timeout is None:
            it.timeout = self.store.query_timeout
        if it.timeout is not None:
            it.deadline = time.monotonic() + it.timeout
        self.metrics.counter("geomesa.serving.submitted")
        # tenant resolution + quota read happen HERE, before the
        # condition is ever taken: TenantRegistry._lock must never nest
        # under QueryScheduler._cond (docs/concurrency.md rank order)
        it.tenant = tenant
        tcap = None
        if self.tenants is not None and tenant is not None:
            tcap = self.tenants.queue_cap(tenant)
            self.tenants.note_submitted(tenant)

        # cache-aware admission: fingerprint for in-window coalescing
        # (always, cache or not) and peek the result cache — hits are
        # served in the caller's thread through the NORMAL cached execute
        # (single-counted accounting) and never queue
        cache = getattr(self.store, "cache", None)
        mode = getattr(hints, "cache", None) if hints is not None else None
        if mode != "bypass":
            sft = self.store.get_schema(type_name)
            auths = getattr(self.store, "auths", None)
            if cache is not None:
                it.key = cache.fingerprint_plan(plan, hints, sft, auths)
                it.key_range = cache.key_range(plan.filter, sft)
                if cache.result.enabled and cache.result.peek(it.key) is not None:
                    try:
                        with otr.activate(
                            trace.root if trace is not None else None
                        ):
                            _resolve(
                                fut,
                                planner.execute(
                                    plan, explain=explain, hints=hints
                                ),
                            )
                    except BaseException as exc:
                        _resolve(fut, exc=exc)
                    finally:
                        otr.end(trace)
                    if self.tenants is not None and tenant is not None:
                        self.tenants.note_cache_hit(tenant)
                    return fut
            else:
                from geomesa_tpu.cache.fingerprint import fingerprint_plan

                it.key = fingerprint_plan(plan, hints, sft, auths)

        # deadline-aware shed: a budget that cannot survive the current
        # batch window is refused now, not after burning a queue slot
        if it.timeout is not None and it.timeout <= self._window_s:
            self._shed(it, (
                f"timeout {it.timeout:.3f}s cannot survive the "
                f"{self._window_s * 1e3:.1f}ms batch window"
            ))
            return fut

        # backpressure: the shared bound AND (when tenancy is on) the
        # caller's per-tenant quota — a flooding tenant hits its own
        # quota and sheds while other tenants' queues stay open. Sheds
        # resolve OUTSIDE the condition (nothing below takes a lock
        # under _cond except the tracer end on close)
        shed_why = shed_exc = None
        with self._cond:
            while not self._closed:
                tq = self._queues.get(tenant)
                over_tenant = tcap is not None and (
                    len(tq) if tq is not None else 0
                ) >= tcap
                if self._depth < self.conf.queue_max and not over_tenant:
                    break
                if not block:
                    if over_tenant and self._depth < self.conf.queue_max:
                        shed_why = "tenant admission quota full"
                        shed_exc = ServingRejected(
                            f"tenant {tenant!r} admission quota full ({tcap})"
                        )
                    else:
                        shed_why = "admission queue full"
                        shed_exc = ServingRejected(
                            f"admission queue full ({self.conf.queue_max})"
                        )
                    break
                rem = None
                if it.deadline is not None:
                    rem = it.deadline - time.monotonic()
                    if rem <= 0:
                        shed_why = "admission queue full past the deadline"
                        break
                self._cond.wait(rem if rem is not None else 0.1)
            if shed_why is None:
                if self._closed:
                    otr.end(trace)
                    _resolve(fut, exc=RuntimeError("scheduler closed"))
                    return fut
                it.t_enqueue = time.perf_counter()
                q = self._queues.get(tenant)
                if q is None:
                    q = self._queues[tenant] = deque()
                q.append(it)
                self._depth += 1
                self._cond.notify_all()
        if shed_why is not None:
            self._shed(it, shed_why, shed_exc)
        return fut

    def admission_gap(self, max_wait_s: float = 0.05) -> bool:
        """Wait (bounded) for the admission queue to DRAIN — every query
        admitted so far handed to the dispatcher — and return whether it
        did. The streaming fold calls this between slices
        (docs/streaming.md "Incremental fold"): a maintenance thread
        that yields here lets queued dashboard queries dispatch before
        the next slice's build competes for the host, instead of letting
        them queue behind the whole fold. An idle queue returns
        immediately; the bound keeps a saturating query load from
        stalling the fold forever."""
        deadline = time.monotonic() + max(max_wait_s, 0.0)
        with self._cond:
            while self._depth and not self._closed:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                self._cond.wait(rem)
            return True

    def query(
        self,
        type_name: str,
        f="INCLUDE",
        limit: Optional[int] = None,
        hints=None,
        explain=None,
        wait: Optional[float] = None,
    ):
        """Synchronous submit + wait — the thread-per-client server loop
        body. ``wait`` bounds the caller-side wait only (the query's own
        budget is the hint/store timeout)."""
        return self.submit(
            type_name, f, limit=limit, hints=hints, explain=explain
        ).result(wait)

    def _shed(self, it: _Item, why: str, exc: Optional[BaseException] = None) -> None:
        self.metrics.counter("geomesa.serving.shed")
        if self.tenants is not None and it.tenant is not None:
            self.tenants.note_shed(it.tenant)
        if exc is None:
            from geomesa_tpu.planning.errors import QueryTimeout

            exc = QueryTimeout(
                f"shed before dispatch: {why}", budget_s=it.timeout
            )
        if it.explain is not None:
            it.explain.warn(f"serving: shed ({why})")
        if it.trace is not None:
            from geomesa_tpu.obs.trace import tracer

            it.trace.root.annotate(shed=why)
            tracer().end(it.trace)
        _resolve(it.future, exc=exc)

    # -- dispatcher ------------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._cond:
                while not self._depth and not self._closed:
                    self._cond.wait()
                if not self._depth and self._closed:
                    return
            # micro-batch window: linger for more arrivals, up to the
            # adaptive window or the batch cap (skipped when idle-shrunk
            # to zero — a lone query dispatches immediately)
            w = self._window_s
            if w > 0:
                end = time.monotonic() + w
                with self._cond:
                    while (
                        self._depth < self.conf.batch_max
                        and not self._closed
                    ):
                        rem = end - time.monotonic()
                        if rem <= 0:
                            break
                        self._cond.wait(rem)
            # DRR weights snapshot BEFORE the condition: the registry's
            # lock never nests under _cond
            weights = (
                self.tenants.weights() if self.tenants is not None else None
            )
            with self._cond:
                batch = self._take_locked(weights)
                self._cond.notify_all()  # wake producers blocked on space
            self._adapt(len(batch))
            try:
                self._dispatch(batch)
            except BaseException as exc:  # defensive: never kill the loop
                for it in batch:
                    if not it.future.done():
                        _resolve(it.future, exc=exc)

    def _take_locked(self, weights: "dict | None") -> list:
        """Drain up to ``batch_max`` items under ``_cond``. One
        backlogged tenant drains plain FIFO (the pre-tenancy behavior,
        bit for bit); several interleave by weighted deficit round-robin
        — each pass grants every backlogged tenant ``weight/w_min``
        credit (>= 1, so every pass progresses) and takes that many of
        its items, so a compliant tenant's queries always ride the next
        batch regardless of how deep a flooding tenant's queue is."""
        nmax = self.conf.batch_max
        batch: "list[_Item]" = []
        live = [t for t, q in self._queues.items() if q]
        if not live:
            return batch
        if len(live) == 1:
            q = self._queues[live[0]]
            while q and len(batch) < nmax:
                batch.append(q.popleft())
            self._deficit.clear()
            self._depth -= len(batch)
            return batch
        live.sort(key=lambda t: (t is None, t))  # deterministic order
        w_min = 1.0
        if weights:
            w_min = min(
                max(weights.get(t, 1.0), 1e-3) for t in live
            )
        while live and len(batch) < nmax:
            for t in list(live):
                q = self._queues[t]
                w = max(weights.get(t, 1.0), 1e-3) if weights else 1.0
                cred = min(self._deficit.get(t, 0.0) + w / w_min, float(nmax))
                take = min(int(cred), len(q), nmax - len(batch))
                for _ in range(take):
                    batch.append(q.popleft())
                if q:
                    self._deficit[t] = cred - take
                else:
                    # an emptied queue forfeits leftover credit: deficit
                    # only accumulates while backlogged (classic DRR)
                    self._deficit.pop(t, None)
                    live.remove(t)
                if len(batch) >= nmax:
                    break
        self._depth -= len(batch)
        return batch

    def _adapt(self, drained: int) -> None:
        """Grow the window under load, shrink it when idle: a drain that
        actually fused (>1 queries) doubles the window toward the cap (a
        longer linger catches more of the arrival rate); a singular drain
        halves it toward zero (an idle store must not tax lone queries
        with the full window)."""
        cap = max(self.conf.window_ms, 0.0) / 1e3
        if drained > 1:
            self._window_s = min(cap, max(self._window_s * 2.0, cap / 8.0))
        elif self._window_s < cap / 16.0:
            self._window_s = 0.0
        else:
            self._window_s = self._window_s / 2.0
        self.metrics.gauge("geomesa.serving.window_ms", self._window_s * 1e3)

    def _dispatch(self, batch: list) -> None:
        # late deadline shed: the hint timeout expired while queued
        now = time.monotonic()
        live: list[_Item] = []
        for it in batch:
            if it.deadline is not None and now > it.deadline:
                self._shed(it, "deadline expired waiting for dispatch")
            else:
                live.append(it)
        if not live:
            return

        # identical-fingerprint coalescing: same (schema, strategy,
        # filter, limit, result-hints, auths) admitted in the SAME
        # mutation epoch in one window -> ONE slot in the fused dispatch,
        # one shared result (the epoch keeps a query admitted after a
        # write off a pre-write leader — its plan saw different data)
        leaders: list[_Item] = []
        followers: dict[int, list[_Item]] = {}
        by_key: dict[tuple, int] = {}
        for it in live:
            ck = (it.key, it.epoch) if it.key is not None else None
            j = by_key.get(ck) if ck is not None else None
            if j is None:
                if ck is not None:
                    by_key[ck] = len(leaders)
                leaders.append(it)
            else:
                followers.setdefault(j, []).append(it)
                self.metrics.counter("geomesa.serving.coalesced")

        cache = getattr(self.store, "cache", None)
        tick = cache.generations.tick() if cache is not None else None
        self.metrics.counter("geomesa.serving.batches")
        self.metrics.counter("geomesa.serving.batched_queries", len(leaders))

        from geomesa_tpu.obs.trace import tracer

        # one root ``batch`` a fused batch, in THIS thread, from the
        # staging to the last member resolved: its ``dispatch`` child is
        # the real span of what every member's retroactive ``dispatch``
        # repeats, and each member's ``query`` root names it
        # (``batch_trace``)
        otr = tracer()
        with otr.trace(
            "batch", capture=False, members=len(live), leaders=len(leaders),
            coalesced=len(live) - len(leaders),
        ) as btrace:
            self._run_batch(live, leaders, followers, cache, tick, btrace)

    def _run_batch(self, live, leaders, followers, cache, tick, btrace) -> None:
        """Stage the leaders' scans in one ``submit_many``, then resolve
        every member in turn (``_dispatch``'s second half)."""
        from geomesa_tpu.obs.trace import phase_breakdown, tracer

        otr = tracer()
        try:
            # per-leader explains (fused members trace their device scan
            # like sequential execution) and ADMISSION-anchored deadlines:
            # queue wait is charged against the caller's budget, not
            # restarted at dispatch. A coalesced follower shares its
            # leader's deadline and fate (single-flight semantics).
            from geomesa_tpu.planning.errors import Deadline

            t_sm0 = time.perf_counter()
            finishes = self.store.planner.submit_many(
                [it.plan for it in leaders],
                hints=[it.hints for it in leaders],
                explains=[it.explain for it in leaders],
                deadlines=[
                    None if it.deadline is None else Deadline(
                        start=it.deadline - it.timeout,
                        budget_s=it.timeout,
                        cutoff=it.deadline,
                    )
                    for it in leaders
                ],
            )
        except BaseException as exc:
            for it in live:
                if not it.future.done():
                    if it.trace is not None:
                        otr.end(it.trace)
                    _resolve(it.future, exc=exc)
            return

        t_dispatch = time.perf_counter()
        for it in live:
            if it.trace is not None:
                # the cross-thread phases, recorded retroactively onto the
                # caller's trace: admission (fingerprint/peek/backpressure
                # in the caller thread), time queued behind the window,
                # then the shared fused-dispatch staging
                root = it.trace.root
                otr.add_span(root, "admit", t0=it.t_admit, end=it.t_enqueue)
                otr.add_span(root, "queue", t0=it.t_enqueue, end=t_sm0)
                otr.add_span(
                    root, "dispatch", t0=t_sm0, end=t_dispatch,
                    batch=len(leaders),
                )
                if btrace is not None:
                    root.annotate(batch_trace=btrace.trace_id)
        for j, (it, fin) in enumerate(zip(leaders, finishes)):
            group = [it] + followers.get(j, [])
            for g in group:
                # queue wait lands on the plan BEFORE finish() so the
                # leader's record_query picks it up (the queue_wait
                # histogram)
                g.plan.queue_wait_s = t_dispatch - g.t_enqueue
            t0 = time.perf_counter()
            for g in group:
                if g.trace is not None:
                    # time between the fused dispatch and THIS member's
                    # turn in the pull loop: attributed as batch wait so
                    # a co-batched query's trace explains its whole wall
                    otr.add_span(
                        g.trace.root, "batch.wait",
                        t0=t_dispatch, end=t0, position=j,
                    )
            try:
                # the leader's span tree continues in THIS thread: the
                # device pull's scan/decode phases attach under its root
                with otr.activate(
                    it.trace.root if it.trace is not None else None
                ):
                    value = fin()
            except BaseException as exc:
                for g in group:
                    if g.trace is not None:
                        otr.end(g.trace)
                    if self.tenants is not None and g.tenant is not None:
                        self.tenants.note_error(g.tenant)
                    _resolve(g.future, exc=exc)
                continue
            cost_s = time.perf_counter() - t0
            mode = getattr(it.hints, "cache", None) if it.hints is not None else None
            if (
                cache is not None
                and it.key is not None
                and it.key_range is not None
                and mode != "bypass"
            ):
                # populate under the cache's normal admission policy; the
                # pre-scan tick rejects entries a mid-scan write staled
                cache.result.admit(
                    it.key, it.plan.type_name, it.key_range, value,
                    cost_s, tick, pinned=(mode == "pin"),
                )
            for g in followers.get(j, []):
                # audit coalesced followers like their own query; the
                # "coalesced" status keeps their (shared) timing out of
                # the tile tier's plain-scan baseline
                g.plan.cache_status = "coalesced"
                self.store.record_query(g.plan, len(value), cost_s)
            for g in group:
                if self.tenants is not None and g.tenant is not None:
                    # per-tenant attribution (no scheduler lock held
                    # here): queue wait at dispatch, full wall at answer
                    self.tenants.note_wait(g.tenant, g.plan.queue_wait_s)
                    self.tenants.note_served(
                        g.tenant, time.perf_counter() - g.t_admit
                    )
                if g.trace is not None:
                    if g is not it:
                        g.trace.root.annotate(coalesced=True)
                    otr.end(g.trace)
                if g.explain is not None:
                    g.explain(
                        f"serving: queue wait {g.plan.queue_wait_s * 1e3:.3f}ms, "
                        f"scan {cost_s * 1e3:.3f}ms, "
                        f"fused batch of {len(leaders)}"
                    )
                    if g.trace is not None:
                        for line in phase_breakdown(g.trace):
                            g.explain(line)
                        g.explain.trace = g.trace
                _resolve(g.future, value)
