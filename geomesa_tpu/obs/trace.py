"""Structured tracing: spans, thread-local propagation, trace retention.

The span model (docs/observability.md): a **root** span opens a
:class:`Trace` at an operation entry point (a query, a flush, a hot
write); **child** spans mark phases and attach to whichever span is
active on the current thread. Cross-thread hops — the serving
scheduler's dispatcher, the flush worker pool — re-activate the parent
span explicitly (:meth:`Tracer.activate`), so one query's trace stays
one tree across the caller thread, the dispatcher and the device pull.

Arming and cost: tracing is armed when ``geomesa.obs.trace.sample`` > 0
or ``geomesa.obs.slow.ms`` > 0 (the always-on slow-query log needs span
trees to capture). The knobs are read once per ROOT; a child
:func:`span` on a thread with no active trace is a single thread-local
probe returning a shared null context — the disarmed no-op. Armed,
a span is one small object append;
sampling decides at root creation whether the finished
tree is RETAINED in the bounded :class:`TraceBuffer` (slow roots are
always retained into the slow-query ring, independent of sampling).

Span timestamps are ``time.perf_counter`` (monotonic); each trace also
records a wall-clock anchor so exports are absolute. ``Tracer.dump``
writes Chrome trace-event JSON (``chrome://tracing`` / Perfetto
``ph:"X"`` complete events, microsecond units).

A leaf span can be cut into **segments** (:meth:`Span.event`: one
``perf_counter`` and a list append) where children would change the
parent's self time. Two dearer instruments are kept for RETAINED traces
(a sampling hit), never for trees built only for the slow log: a span
opened with ``cpu=True`` (a phase that never sleeps by design: ``plan``,
``decode``, ``encode``) reads ``time.thread_time`` at both ends, so
``dur_s - cpu_s`` is what its thread waited for the interpreter lock (a
syscall: 6 us on the host of a v5e, PERF.md section 6, which is why no
other span reads it); and every span or segment that opens and closes on
one thread is a ``jax.profiler.TraceAnnotation("geomesa:<span>
[.<segment>]")`` host event, so a profiler session shows it on the
thread's own line beside the device ops (no session active: a no-op;
``jax`` is imported by the first retained span, never before). All of it
rides ``attrs``, the one carrier every reader and exporter passes on.

**Runtime stalls** (docs/observability.md "Runtime stalls"): two things
stop this process that belong to no span, CPython's collector and JAX's
compiler. Both announce themselves through a hook (``gc.callbacks``;
``jax.monitoring``'s listeners), and both hooks write into ONE
process-wide record (:func:`stalls`, :func:`stall_totals`: a bounded
ring and totals) and onto the span that was open on the thread
(``gc_s``, ``gc_n``; ``compile_s``, ``compile_n``). The record is the
process's, not a :class:`Tracer`'s: ``install`` and ``reset`` leave it,
:func:`clear_stalls` empties it. The collector's hook goes in when a root
first finds tracing armed, the compiler's when the first ``DataStore``
is built. A collection stops every thread, so ``Tracer.end`` stamps
``gc_wait_s`` on each root it finishes: the seconds of the ring's pauses
that overlap it.

**The interpreter lock** (docs/observability.md "The interpreter
lock"): a second process-wide record under the stall record's rules
(:func:`lock_probe`, :func:`lock_cpu`; ``install`` and ``reset`` leave
it, :func:`clear_lock` empties it), written by ONE daemon thread,
``geomesa-lockprobe``: it sleeps ``PROBE_PERIOD_S`` in a native call
that lets the lock go as any native call does and samples what it then
waited to hold the lock again, and four times a second it reads the CPU
clock of every thread that named its role (:func:`name_role`). The
thread starts with the first RETAINED root or with
``DataStore.serve_ops``; without either it never exists. On a request's
path the program counts the places where it lets the lock go itself
(``handoffs`` on the active span, :func:`add`), the native tier stamps
its calls (``native_s``, ``reacquire_s``, ``native_n``:
``native._call``), and the roots the ``with`` form opens read their
thread's CPU clock like ``plan`` does (``cpu_s``), all three of the last
on retained traces only.

Locking: ``Tracer._lock`` (LOCKS rank 76, hot) guards only the
retention rings and the sampling counter — it is taken once per root
begin/end, never per child span (children append to their trace's own
span list, a GIL-atomic ``list.append``; see :class:`Span`), and
nothing blocking runs under it. Span finish never acquires it, so
spans are safe to close while arbitrary store locks are held. The
stall record has NO lock: the collector's hook runs wherever this thread
happens to be, under any lock it holds (``MetricsRegistry``'s and
``Tracer._lock`` included), so it appends to structures that need none
and calls no registry and no logger. The lock record has none either:
its rings and totals have one writer, the probe's thread; a thread that
names or retires its role makes one atomic store, pop or append.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Callable, Optional

from geomesa_tpu import conf

_ids = itertools.count(1)
_tls = threading.local()
_profiler = None  # jax.profiler, imported by the first retained span


def _annotation(name: str, trace_id: int):
    """An ENTERED profiler annotation ``name`` carrying the trace id."""
    global _profiler
    if _profiler is None:
        import jax.profiler

        _profiler = jax.profiler
    ann = _profiler.TraceAnnotation(name, trace=trace_id)
    ann.__enter__()
    return ann


class Span:
    """One timed phase. ``finish()`` stamps the duration and appends the
    span to its trace — no lock: concurrent appends DO happen (flush
    pool workers finish stage spans of the same trace in parallel) and
    rely on ``list.append`` being atomic under the GIL. Only the append
    is concurrent; no span is ever mutated after finish, and readers
    (retention, export) run after the root ends. A free-threaded
    runtime would need a per-trace lock here.

    ``segments`` and ``cpu_s`` (``attrs``) exist only on spans the
    ``with`` forms opened: both ends of those, and every
    :meth:`event` between them, are taken on one thread."""

    __slots__ = (
        "trace", "span_id", "parent_id", "name", "attrs", "t0", "dur_s",
        "tid", "_cpu0", "_marks", "_anns",
    )

    def __init__(self, trace: "Trace", name: str, parent_id: Optional[int],
                 attrs: Optional[dict] = None, t0: Optional[float] = None):
        self.trace = trace
        self.span_id = next(_ids)
        self.parent_id = parent_id
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter() if t0 is None else t0
        self.dur_s = 0.0
        self.tid = threading.get_ident()
        self._cpu0 = None   # thread_time at open (retained, cpu=True)
        self._marks = None  # [(segment, perf_counter)]
        self._anns = None   # open profiler annotations, innermost last

    def _open(self, cpu: bool = False) -> "Span":
        """What a ``with`` form adds on entry to a span of a RETAINED
        trace: the profiler annotation and, asked for, the thread's CPU
        clock."""
        if self.trace.retain:
            self._anns = [_annotation("geomesa:" + self.name, self.trace.trace_id)]
            if cpu:
                self._cpu0 = time.thread_time()
        return self

    def event(self, name: str) -> bool:
        """Close the running segment and start segment ``name``, which
        runs to the next ``event`` or the span's end: one clock read and
        a list append, no object. ``finish`` folds them into
        ``attrs["segments"] = {name: wall_s}`` (a name marked twice
        sums). Call it on the thread that finishes the span."""
        mark = (name, time.perf_counter())
        if self._marks is None:
            self._marks = [mark]
        else:
            self._marks.append(mark)
        anns = self._anns
        if anns is not None:
            if len(anns) > 1:
                anns.pop().__exit__(None, None, None)
            anns.append(_annotation(
                f"geomesa:{self.name}.{name}", self.trace.trace_id
            ))
        return True

    def add(self, name: str, n) -> None:
        """Add ``n`` to the numeric attribute ``name`` (block and slot
        counts that several dispatches of one span accumulate)."""
        a = self.attrs
        if a is None:
            self.attrs = {name: n}
        else:
            a[name] = a.get(name, 0) + n

    def annotate(self, **attrs) -> "Span":
        """Attach attributes after the fact (hit counts, strategies)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        return self

    def finish(self, end: Optional[float] = None) -> None:
        now = time.perf_counter() if end is None else end
        self.dur_s = now - self.t0
        if self._marks is not None or self._anns is not None:
            self._fold(now)
        self.trace.spans.append(self)

    def _fold(self, now: float) -> None:
        extra: dict = {}
        if self._cpu0 is not None:
            extra["cpu_s"] = max(time.thread_time() - self._cpu0, 0.0)
        anns, self._anns = self._anns, None
        while anns:
            anns.pop().__exit__(None, None, None)
        marks = self._marks
        if marks:
            segs: dict = {}
            for k, (name, t) in enumerate(marks):
                t1 = marks[k + 1][1] if k + 1 < len(marks) else now
                segs[name] = segs.get(name, 0.0) + (t1 - t)
            extra["segments"] = segs
        if not extra:
            return
        if self.attrs is None:
            self.attrs = extra
        else:
            self.attrs.update(extra)


class Trace:
    """One operation's span tree: the root span plus every finished
    child, flat with parent ids (tree shape reconstructs from ids)."""

    __slots__ = (
        "trace_id", "name", "spans", "root", "t_wall", "retain", "capture",
        "fingerprint",
    )

    def __init__(self, name: str, retain: bool, capture: bool = True):
        self.trace_id = next(_ids)
        self.name = name
        self.spans: list[Span] = []
        self.t_wall = time.time()
        self.retain = retain
        self.capture = capture  # may the slow-query log take it
        # slow-log identity (set by the query path once planned): the
        # plan fingerprint the capture carries, or a zero-argument callable
        # that gives it: end() calls it if the slow-query log takes the
        # trace and drops it otherwise (a filter's text is rendered only
        # for the log: a tube's 256 slices are 40 KB of it)
        self.fingerprint: "dict | Callable[[], dict] | None" = None
        self.root = Span(self, name, None)

    @property
    def wall_s(self) -> float:
        return self.root.dur_s

    def phases(self) -> list[Span]:
        """Top-level phases: the root's direct children, in start order."""
        rid = self.root.span_id
        return sorted(
            (s for s in self.spans if s.parent_id == rid),
            key=lambda s: s.t0,
        )

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "wall_ms": round(self.wall_s * 1e3, 3),
            "t_wall": self.t_wall,
            "spans": [
                {
                    "span_id": s.span_id,
                    "parent_id": s.parent_id,
                    "name": s.name,
                    "tid": s.tid,
                    "start_ms": round((s.t0 - self.root.t0) * 1e3, 3),
                    "dur_ms": round(s.dur_s * 1e3, 3),
                    **({"attrs": s.attrs} if s.attrs else {}),
                }
                for s in sorted(self.spans, key=lambda s: s.t0)
            ],
        }


class _NullSpan:
    """The shared disarmed context: every tracing entry point on an
    untraced thread returns THIS singleton — no allocation, no state."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        return None

    def annotate(self, **attrs):
        return self

    def event(self, name) -> bool:
        return False

    def add(self, name, n) -> None:
        return None


NULL_SPAN = _NullSpan()


class _SpanCtx:
    """Context manager activating a child span on this thread."""

    __slots__ = ("span", "_cpu", "_prev")

    def __init__(self, span: Span, cpu: bool = False):
        self.span = span
        self._cpu = cpu
        self._prev = None

    def __enter__(self) -> Span:
        self._prev = getattr(_tls, "span", None)
        _tls.span = self.span
        return self.span._open(self._cpu)

    def __exit__(self, *exc) -> None:
        self.span.finish()
        _tls.span = self._prev


class _Activation:
    """Cross-thread hop: re-activate an existing span on this thread
    without finishing it on exit (the span belongs to another scope)."""

    __slots__ = ("_span", "_prev")

    def __init__(self, span: Optional[Span]):
        self._span = span
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_tls, "span", None)
        if self._span is not None:
            _tls.span = self._span
        return self._span

    def __exit__(self, *exc) -> None:
        if self._span is not None:
            _tls.span = self._prev


class TraceBuffer(deque):
    """A bounded ring, newest last: a ``deque`` with a ``maxlen``, so an
    append past the cap drops the oldest in the same atomic step. Holds
    the finished traces (touched only under ``Tracer._lock``) and the
    process's stall record (appended to with no lock at all)."""

    def __init__(self, cap: int):
        super().__init__(maxlen=max(int(cap), 1))

    @property
    def cap(self) -> int:
        return self.maxlen

    def items(self) -> list:
        while True:
            try:
                return list(self)
            except RuntimeError:  # appended to meanwhile (the stall ring has no lock)
                continue


# -- runtime stalls ---------------------------------------------------------

#: records the stall ring keeps (a set-up of the largest benchmark cell
#: leaves a few hundred: three a compiled program, one a collection of a
#: millisecond or more)
STALL_RING = 8192
#: a collection shorter than this goes to the totals only: generation 0
#: runs hundreds of times a second
GC_RING_S = 1e-3
#: the short collections are summed in slices of this many seconds, the
#: newest ``_SLICES`` of them, so that they too can be cut to a window
_SLICE_S = 0.25
_SLICES = 4096
#: a compile under one of these roots stalled a request
REQUEST_ROOTS = frozenset(("query", "query_many", "count", "density", "write"))

_GC_NAMES = ("gen0", "gen1", "gen2")
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
# the persistent cache is asked (a miss until it answers), then hits
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}


class _StallRecord:
    """The state both hooks write. No lock: the ring's append is one
    atomic step; CPython runs one collection at a time, callbacks
    included, so the collector's totals have one writer; the compiler's
    totals are kept a thread (each thread adds to a dict of its own,
    registered by one atomic ``list.append``) and summed by the reader.
    :func:`clear_stalls` swaps in a fresh instance."""

    __slots__ = ("ring", "seq", "gc", "gc_short", "gc_last_end", "gc_t0",
                 "gc_ann", "parts")

    def __init__(self):
        self.ring = TraceBuffer(STALL_RING)
        self.seq = itertools.count()
        # a generation: [collections, seconds, longest]
        self.gc = [[0, 0.0, 0.0] for _ in _GC_NAMES]
        # {slice number: [n0, s0, n1, s1, n2, s2]}, pauses under GC_RING_S
        self.gc_short: dict = {}
        self.gc_last_end = 0.0  # when the ring's newest pause ended
        self.gc_t0 = None       # the running collection's start
        self.gc_ann = None      # ... and its profiler annotation
        # a thread that compiled: {"compiled": n, "programs": {fun_name:
        # a _program()}}
        self.parts: list = []


_stalls = _StallRecord()
_gc_hooked = False
_compiler_hooked = False


def _program() -> dict:
    """A program name's totals: seconds by phase, backend phases, and
    those of them the persistent cache answered."""
    return {"trace": 0.0, "lower": 0.0, "backend": 0.0, "calls": 0, "hits": 0}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks``: the pause between ``start`` and ``stop`` into
    the totals, into the ring from ``GC_RING_S`` up, and onto this
    thread's active span. Runs under whatever lock this thread holds:
    takes none, calls no registry and no logger."""
    rec = _stalls
    gen = info["generation"]
    if phase == "start":
        rec.gc_t0 = time.perf_counter()
        if _profiler is not None and gen > 0:
            ann = _profiler.TraceAnnotation("geomesa:gc." + _GC_NAMES[gen])
            ann.__enter__()
            rec.gc_ann = ann
        return
    ann, rec.gc_ann = rec.gc_ann, None
    if ann is not None:
        ann.__exit__(None, None, None)
    now = time.perf_counter()
    t0, rec.gc_t0 = rec.gc_t0, None
    if t0 is None:  # hooked, or cleared, while this collection ran
        return
    dur = now - t0
    tot = rec.gc[gen]
    tot[0] += 1
    tot[1] += dur
    if dur > tot[2]:
        tot[2] = dur
    cur = getattr(_tls, "span", None)
    if cur is not None:
        cur.add("gc_s", dur)
        cur.add("gc_n", 1)
    if dur < GC_RING_S:
        short = rec.gc_short
        k = int(now / _SLICE_S)
        row = short.get(k)
        if row is None:
            row = short[k] = [0, 0.0, 0, 0.0, 0, 0.0]
            if len(short) > _SLICES:
                del short[next(iter(short))]
        row[2 * gen] += 1
        row[2 * gen + 1] += dur
        return
    rec.ring.append({
        "seq": next(rec.seq), "kind": "gc", "name": _GC_NAMES[gen], "t0": t0,
        "dur_s": dur, "tid": threading.get_ident(),
        "trace_id": None if cur is None else cur.trace.trace_id,
        "collected": info.get("collected", 0),
    })
    rec.gc_last_end = now


def _hook_collector() -> None:
    global _gc_hooked
    if not _gc_hooked:
        _gc_hooked = True
        gc.callbacks.append(_on_gc)


def _on_cache(event: str, **kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is not None:
        _tls.cache_seen = (kind, time.perf_counter())


def _on_compile_start(event: str, value, **kw) -> None:
    """A phase began on this thread (JAX records its start time as a
    scalar): one level deeper."""
    if event in _PHASES:
        _tls.phases = getattr(_tls, "phases", 0) + 1


def _on_compile(event: str, secs: float, **kw) -> None:
    """``jax.monitoring``: one of a program's three phases ended on this
    thread (tracing to a jaxpr, lowering it to MLIR, which for a Pallas
    kernel is Mosaic's, and the backend's compile or its fetch from the
    persistent cache). Silent on a jit cache hit: nothing compiles.
    Every ``jnp`` call made while a function is traced, and some made
    while one is lowered, is a trace of its own, thousands a kernel: a
    trace that ends inside another phase is that phase's time and is
    left out, so a program is three records whatever it calls inside."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    depth = _tls.phases = max(getattr(_tls, "phases", 1) - 1, 0)
    if depth and phase == "trace":
        return
    now = time.perf_counter()
    rec = _stalls
    name = str(kw.get("fun_name", "?"))
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]  # lowering and the backend name the module, tracing the function
    held = getattr(_tls, "compiles", None)
    if held is None or held[0] is not rec:
        held = _tls.compiles = (rec, {"compiled": 0, "programs": {}})
        rec.parts.append(held[1])
    part = held[1]
    row = part["programs"].get(name)
    if row is None:
        row = part["programs"][name] = _program()
    row[phase] += secs
    cur = getattr(_tls, "span", None)
    out = {"seq": next(rec.seq), "kind": "compile", "name": name, "phase": phase,
           "t0": now - secs, "dur_s": secs, "tid": threading.get_ident(),
           "trace_id": None if cur is None else cur.trace.trace_id}
    if cur is not None:
        cur.add("compile_s", secs)
    if phase == "backend":
        row["calls"] += 1
        seen = getattr(_tls, "cache_seen", None)
        if seen is not None and seen[1] >= now - secs:
            out["cache"] = seen[0]
            row["hits"] += seen[0] == "hit"
        if cur is not None:
            cur.add("compile_n", 1)
            if cur.trace.name in REQUEST_ROOTS:
                out["root"] = cur.trace.name
                part["compiled"] += 1
    rec.ring.append(out)


def hook_compiler() -> None:
    """Put the compiler's listeners on ``jax.monitoring``, once a
    process. The first ``DataStore`` calls it; importing ``obs`` never
    does."""
    global _compiler_hooked
    if _compiler_hooked:
        return
    _compiler_hooked = True
    import jax.monitoring as mon

    mon.register_scalar_listener(_on_compile_start)
    mon.register_event_duration_secs_listener(_on_compile)
    mon.register_event_listener(_on_cache)


def stalls() -> list:
    """The stall ring, newest last: ``{seq, kind: "gc" | "compile", name
    (``gen0/1/2`` or JAX's ``fun_name``), t0 (``time.perf_counter``, the
    spans' clock), dur_s, tid, trace_id or None}``; a collection adds
    ``collected``; a compile ``phase`` (``trace``, ``lower``,
    ``backend``) and, on the backend phase, ``cache`` (``hit`` /
    ``miss``, where the persistent cache was asked) and ``root`` (the
    request it stalled)."""
    return [dict(r) for r in _stalls.ring.items()]


def stall_totals(t_lo: Optional[float] = None, t_hi: Optional[float] = None) -> dict:
    """``{"gc": {gen: {"n", "s", "max_s"}}, "compile": {fun_name:
    {"trace", "lower", "backend" (seconds), "calls", "hits"}},
    "compiled", "dropped"}``. With no bound: the process's totals since
    the hooks went in. With one: what ENDED inside ``[t_lo, t_hi)`` on
    ``time.perf_counter``, summed from the ring and, for the collections
    too short for it, from their slices (``max_s`` is then the ring's; a
    window is right to a slice's width and while ``dropped``, the records
    the ring has forgotten, is 0)."""
    rec = _stalls
    out_gc = {g: {"n": 0, "s": 0.0, "max_s": 0.0} for g in _GC_NAMES}
    programs: dict = {}
    compiled = 0
    if t_lo is None and t_hi is None:
        for g, (n, s, mx) in zip(_GC_NAMES, rec.gc):
            out_gc[g] = {"n": n, "s": s, "max_s": mx}
        for part in list(rec.parts):
            compiled += part["compiled"]
            for name, row in list(part["programs"].items()):
                p = programs.setdefault(name, _program())
                for key, v in row.items():
                    p[key] += v
    else:
        lo = float("-inf") if t_lo is None else t_lo
        hi = float("inf") if t_hi is None else t_hi
        for r in rec.ring.items():
            if not lo <= r["t0"] + r["dur_s"] < hi:
                continue
            if r["kind"] == "gc":
                g = out_gc[r["name"]]
                g["n"] += 1
                g["s"] += r["dur_s"]
                g["max_s"] = max(g["max_s"], r["dur_s"])
            else:
                p = programs.setdefault(r["name"], _program())
                p[r["phase"]] += r["dur_s"]
                if r["phase"] == "backend":
                    p["calls"] += 1
                    p["hits"] += r.get("cache") == "hit"
                    compiled += "root" in r
        for k, row in list(rec.gc_short.items()):
            if lo <= k * _SLICE_S < hi:
                for i, g in enumerate(_GC_NAMES):
                    out_gc[g]["n"] += row[2 * i]
                    out_gc[g]["s"] += row[2 * i + 1]
    try:
        dropped = rec.ring[0]["seq"]  # records are numbered from 0
    except IndexError:
        dropped = 0
    return {"gc": out_gc, "compile": programs, "compiled": compiled, "dropped": dropped}


def clear_stalls() -> None:
    """Forget every stall (tests; ``Tracer.reset`` does not)."""
    global _stalls
    _stalls = _StallRecord()


def _gc_overlap(t_lo: float, t_hi: float) -> float:
    """Seconds of the ring's collections, of any thread, that overlap
    ``[t_lo, t_hi]``: a walk from the newest record that stops at the
    first one that ended before ``t_lo`` (records enter the ring as they
    end). One comparison where no pause ended since ``t_lo``."""
    rec = _stalls
    if rec.gc_last_end < t_lo:
        return 0.0
    while True:
        total = 0.0
        try:
            for r in reversed(rec.ring):
                end = r["t0"] + r["dur_s"]
                if end < t_lo:
                    break
                if r["kind"] == "gc":
                    total += max(min(end, t_hi) - max(r["t0"], t_lo), 0.0)
            return total
        except RuntimeError:  # appended to meanwhile: walk again
            continue


def _stall_events(pid: int) -> list[dict]:
    """The ring as Chrome trace events, each on its thread's lane."""
    to_wall = time.time() - time.perf_counter()
    out = []
    for r in _stalls.ring.items():
        name = r["name"] if r["kind"] == "gc" else f"{r['phase']} {r['name']}"
        out.append({
            "name": f"{r['kind']}:{name}", "ph": "X", "pid": pid, "tid": r["tid"],
            "ts": round((r["t0"] + to_wall) * 1e6, 1),
            "dur": round(r["dur_s"] * 1e6, 1),
            "args": {k: v for k, v in r.items() if k not in ("t0", "dur_s", "tid")},
        })
    return out


# -- the interpreter lock ----------------------------------------------------

#: what the probe sleeps between two samples: a hundred acquisitions a second
PROBE_PERIOD_S = 0.010
#: samples the probe's ring keeps: 164 s of them (a 30 s window is 3,000)
PROBE_RING = 16384
#: the CPU ledger is read every this many wake-ups, four times a second:
#: twenty threads' clocks at 6 us a read are 0.05% of the lock at that rate
LEDGER_EVERY = 25
#: ledger samples kept: eight minutes
LEDGER_RING = 2048


class _LockRecord:
    """What the probe's thread writes, and nobody else: the sample ring
    and its totals, the ledger ring, and ``retired`` (the seconds of the
    threads that ended, by role). ``threads`` holds one entry a thread
    that named its role, ``{native id: [role, CPU clock id, base, last]}``
    (``base``: the clock's reading that counts as 0, None until the probe
    first sees a thread that named itself late; ``last``: the newest
    reading less the base), stored by the thread itself in one step, and
    ``ended`` what a thread leaves when it retires, ``(native id, role,
    seconds)``: the probe drains it. No lock. :func:`clear_lock` swaps in
    a fresh instance, which the running probe notices and ends on."""

    __slots__ = ("probe", "n", "sum_s", "max_s", "exact", "cpu", "threads",
                 "ended", "retired", "gate", "thread")

    def __init__(self, threads: Optional[dict] = None):
        self.probe = TraceBuffer(PROBE_RING)  # (t, wait_s)
        self.n = 0
        self.sum_s = 0.0
        self.max_s = 0.0
        self.exact = None  # True: the native nap's stamp; False: sleep's overshoot
        self.cpu = TraceBuffer(LEDGER_RING)  # (t, {role: cpu_s}, process_s, {role: threads})
        self.threads: dict = {} if threads is None else threads
        self.ended: deque = deque()
        self.retired: dict = {}
        self.gate = itertools.count()  # whoever draws 0 starts the thread
        self.thread = None


_lock = _LockRecord()


def name_role(role: str, late: bool = False) -> None:
    """This thread's role in the CPU ledger, named by the thread itself
    as it starts: ``handler``, ``dispatcher``, ``flush``, ``wal``,
    ``replica``, ``ops``, ``probe``; ``caller`` is what :meth:`Tracer.begin`
    names a thread that opens a root without a role while a probe runs
    (``late``: its CPU so far is not the program's, so the ledger counts
    it from the probe's first sight of it). One dict store: the probe
    reads the clock from outside, so nothing is read here or on a
    request's path."""
    _tls.role = role
    _lock.threads[threading.get_native_id()] = [
        role, time.pthread_getcpuclockid(threading.get_ident()),
        None if late else 0.0, 0.0,
    ]


def retire_role() -> None:
    """A thread that ends folds its last reading into its role's retired
    seconds first (a closed connection's handler): one clock read at the
    thread's end, and only once a probe reads the ledger. A thread that
    ends without it keeps the seconds the probe last read."""
    rec = _lock
    key = threading.get_native_id()
    ent = rec.threads.get(key)
    if ent is None:
        return
    if rec.thread is not None:
        cpu = time.thread_time()
        base = cpu if ent[2] is None else ent[2]
        rec.ended.append((key, ent[0], cpu - base))  # before the pop: see _read_ledger
    rec.threads.pop(key, None)
    _tls.role = None


def as_role(role: str, fn: Callable) -> Callable:
    """``fn`` as a thread's target that names ``role`` first and retires
    it last."""
    def run(*args, **kwargs):
        name_role(role)
        try:
            return fn(*args, **kwargs)
        finally:
            retire_role()

    return run


def _read_ledger(rec: _LockRecord, now: float) -> None:
    """One ledger sample: every registered thread's CPU clock, read from
    the probe's thread, summed by role over the retired seconds. The
    order makes a retiring thread count once: the live threads are listed
    first, the retirements drained second (a thread appends before it
    pops, so one that is in neither was never listed), and a listed
    thread that retired meanwhile is skipped."""
    while True:
        try:
            live = list(rec.threads.items())
            break
        except RuntimeError:  # a thread named itself meanwhile
            continue
    retired, gone = rec.retired, set()
    while rec.ended:
        key, role, cpu = rec.ended.popleft()
        gone.add(key)
        retired[role] = retired.get(role, 0.0) + cpu
    by_role, n_threads = dict(retired), {}
    for key, ent in live:
        if key in gone:
            continue
        role = ent[0]
        try:
            cpu = time.clock_gettime(ent[1])
        except OSError:  # ended without retiring: its last reading stands
            if rec.threads.pop(key, None) is not None:
                retired[role] = retired.get(role, 0.0) + ent[3]
        else:
            if ent[2] is None:
                ent[2] = cpu
            ent[3] = cpu - ent[2]
            n_threads[role] = n_threads.get(role, 0) + 1
        by_role[role] = by_role.get(role, 0.0) + ent[3]
    rec.cpu.append((now, by_role, time.process_time(), n_threads))


def _probe_loop(rec: _LockRecord) -> None:
    """The probe's thread. Opens no span and writes no profiler event
    (``idle_named_pct`` counts every ``geomesa:`` one)."""
    from geomesa_tpu import native

    nap, perf, ring, k = native.nap, time.perf_counter, rec.probe, 0
    _read_ledger(rec, perf())
    while _lock is rec:
        t0 = perf()
        wait = nap(PROBE_PERIOD_S)
        now = perf()
        rec.exact = wait is not None
        if wait is None:  # no native tier: the overshoot of ``time.sleep``
            time.sleep(PROBE_PERIOD_S)
            now = perf()
            wait = max(now - t0 - PROBE_PERIOD_S, 0.0)
        ring.append((now, wait))
        rec.n += 1
        rec.sum_s += wait
        if wait > rec.max_s:
            rec.max_s = wait
        k += 1
        if k % LEDGER_EVERY == 0:
            _read_ledger(rec, now)


def arm_lock_probe() -> None:
    """Start ``geomesa-lockprobe``, once a record: the first retained
    root and ``DataStore.serve_ops`` call it; nothing else does, so a
    process that keeps no trace and serves no ops plane has no such
    thread."""
    rec = _lock
    if rec.thread is None and next(rec.gate) == 0:
        rec.thread = th = threading.Thread(
            target=as_role("probe", _probe_loop), args=(rec,),
            name="geomesa-lockprobe", daemon=True,
        )
        th.start()


def _quantile(ordered: list, q: float) -> float:
    return ordered[min(int(q * len(ordered)), len(ordered) - 1)] if ordered else 0.0


def lock_probe(t_lo: Optional[float] = None, t_hi: Optional[float] = None,
               newest: int = 0) -> dict:
    """What a thread of this process waited to hold the interpreter lock
    again after a native call that released it, as the probe sampled it:
    ``{"n", "sum_s", "p50_s", "p95_s", "max_s", "exact"}``. With no bound:
    the totals since the probe started (the percentiles are the ring's).
    With one: the samples taken inside ``[t_lo, t_hi)`` on
    ``time.perf_counter``, from the ring (the newest ``PROBE_RING``).
    ``exact``: True where a sample is the native nap's own stamp against
    ``perf_counter``, False where it is the overshoot of ``time.sleep``
    (no native tier; timer slack included), None before the first.
    ``newest`` > 0 adds ``samples``: that many of the cut's newest,
    ``{"t", "wait_s"}``, oldest first."""
    rec = _lock
    lo = float("-inf") if t_lo is None else t_lo
    hi = float("inf") if t_hi is None else t_hi
    cut = [s for s in rec.probe.items() if lo <= s[0] < hi]
    waits = sorted(w for _, w in cut)
    if t_lo is None and t_hi is None:
        out = {"n": rec.n, "sum_s": rec.sum_s, "max_s": rec.max_s}
    else:
        out = {"n": len(waits), "sum_s": sum(waits), "max_s": waits[-1] if waits else 0.0}
    out.update(p50_s=_quantile(waits, 0.5), p95_s=_quantile(waits, 0.95), exact=rec.exact)
    if newest > 0:
        out["samples"] = [{"t": t, "wait_s": w} for t, w in cut[-newest:]]
    return out


def _ledger_at(ring: list, t: float):
    """({role: cpu_s}, process_s) at ``t``, interpolated between the two
    samples round it (the first or the last outside them). A role that
    the earlier sample lacks began with the later one's reading."""
    if t <= ring[0][0]:
        return ring[0][1], ring[0][2]
    if t >= ring[-1][0]:
        return ring[-1][1], ring[-1][2]
    lo, hi = 0, len(ring) - 1
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if ring[mid][0] <= t:
            lo = mid
        else:
            hi = mid
    (ta, a, pa, _), (tb, b, pb, _) = ring[lo], ring[hi]
    f = (t - ta) / (tb - ta)
    return (
        {r: a.get(r, vb) + f * (vb - a.get(r, vb)) for r, vb in b.items()},
        pa + f * (pb - pa),
    )


def lock_cpu(t_lo: Optional[float] = None, t_hi: Optional[float] = None) -> dict:
    """The CPU ledger by thread role: ``{"cpu_s": {role: seconds},
    "threads": {role: live threads}, "process_s", "samples"}``. With no
    bound: the newest sample (seconds since each thread began, ended
    threads included; ``process_s`` is ``time.process_time``). With
    bounds: the CPU seconds BETWEEN them on ``time.perf_counter``,
    interpolated between the ledger samples on either side of each
    (a quarter of a second apart), ``threads`` as the newest sample at or
    before ``t_hi`` has them, ``samples`` the ledger samples inside.
    Exact over a window whatever the thread clock's tick: a 10 ms tick is
    0.03% of 30 s. Empty (``samples`` 0) before the probe's first
    reading."""
    ring = _lock.cpu.items()
    if not ring:
        return {"cpu_s": {}, "threads": {}, "process_s": 0.0, "samples": 0}
    if t_lo is None and t_hi is None:
        _, by_role, process_s, threads = ring[-1]
        return {"cpu_s": dict(by_role), "threads": dict(threads),
                "process_s": process_s, "samples": len(ring)}
    lo = ring[0][0] if t_lo is None else t_lo
    hi = ring[-1][0] if t_hi is None else t_hi
    (a, pa), (b, pb) = _ledger_at(ring, lo), _ledger_at(ring, hi)
    inside = [r for r in ring if lo <= r[0] <= hi]
    at_hi = [r for r in ring if r[0] <= hi]
    return {
        "cpu_s": {r: vb - a.get(r, vb) for r, vb in b.items()},
        "threads": dict((at_hi[-1] if at_hi else ring[0])[3]),
        "process_s": pb - pa, "samples": len(inside),
    }


def clear_lock() -> None:
    """Forget every probe sample and ledger reading and end the probe's
    thread, which the next arming starts anew (tests; ``Tracer.reset``
    does not). The threads' role names stay: they are facts, not
    readings."""
    global _lock
    old, _lock = _lock, _LockRecord(_lock.threads)
    if old.thread is not None:
        old.thread.join(2.0)


class Tracer:
    """The process tracing runtime: sampling, retention, export.

    One installed instance (:func:`tracer` / :func:`install`) serves
    every store in the process — the serving scheduler, flush workers
    and WAL all record into the same buffer, which is what makes a
    cross-tier trace one tree."""

    def __init__(self, metrics=None):
        from geomesa_tpu.lockwitness import witness

        self._lock = witness(threading.Lock(), "Tracer._lock")
        self.buffer = TraceBuffer(conf.OBS_TRACE_BUFFER.get())  # guarded-by: _lock
        self.slow: list[dict] = []   # guarded-by: _lock
        # roots seen per root NAME: each kind of root is sampled 1/N of
        # its own, so kinds that alternate (a request's ``http`` and
        # ``query``) cannot alias one of them out of the buffer
        self._n_roots: dict = {}     # guarded-by: _lock
        self.metrics = metrics

    # -- arming / roots ---------------------------------------------------
    @property
    def armed(self) -> bool:
        return conf.OBS_TRACE_SAMPLE.get() > 0 or conf.OBS_SLOW_MS.get() > 0

    def begin(self, name: str, capture: bool = True,
              **attrs) -> Optional[Trace]:
        """Open a root trace (sampling decided here), or None when
        disarmed. Does NOT activate it — pair with :meth:`activate`
        (the serving scheduler begins in the caller thread and
        activates per hop); :meth:`trace` composes both.

        ``capture=False``: a root the slow-QUERY log never takes (the
        transport's ``http``, the dispatcher's ``batch``: the query
        roots inside them are what it captures) — built only when
        sampled, so the always-on slow log pays nothing for it.

        Sampling gates the whole tree, not just retention: with the
        slow log off, a sampled-out root returns None and its operation
        records NO spans — 1/N sampling costs ~1/N of full-tracing
        overhead. With the slow log armed every root builds its tree
        (the capture decision needs it), sampling only decides buffer
        retention."""
        sample = conf.OBS_TRACE_SAMPLE.get()
        slow_ms = conf.OBS_SLOW_MS.get()
        if sample <= 0 and slow_ms <= 0:
            return None
        if not _gc_hooked:
            _hook_collector()
        retain = False
        if sample > 0:
            with self._lock:
                n = self._n_roots[name] = self._n_roots.get(name, 0) + 1
                retain = n % sample == 0
        if not retain and (slow_ms <= 0 or not capture):
            return None  # never retained, never slow-captured: free
        tr = Trace(name, retain, capture)
        if attrs:
            tr.root.annotate(**attrs)
        if retain and _lock.thread is None:
            arm_lock_probe()
        if _lock.thread is not None and getattr(_tls, "role", None) is None:
            name_role("caller", late=True)  # somebody reads the ledger
        cur = getattr(_tls, "span", None)
        if cur is not None:
            # begun inside another operation on this thread (a request's
            # ``query`` inside its ``http``): one identifier on both
            # trees, ``http_trace`` here and ``query_trace`` there
            outer = cur.trace
            tr.root.annotate(**{outer.name + "_trace": outer.trace_id})
            outer.root.annotate(**{name + "_trace": tr.trace_id})
        return tr

    def end(self, trace: Optional[Trace], fingerprint: Optional[dict] = None) -> None:
        """Finish a root: stamp the wall, retain per sampling, capture
        into the slow ring when over ``geomesa.obs.slow.ms``. Metrics
        (retention counters) record after the lock is released."""
        if trace is None:
            return
        root = trace.root
        root.finish()
        # a collection stops every thread: what this root waited for the
        # collector, whichever thread it ran on
        waited = _gc_overlap(root.t0, root.t0 + root.dur_s)
        if waited > 0.0:
            root.add("gc_wait_s", waited)
        slow_ms = conf.OBS_SLOW_MS.get()
        is_slow = (
            trace.capture and slow_ms > 0 and trace.wall_s * 1e3 >= slow_ms
        )
        retained = trace.retain
        if callable(trace.fingerprint):
            trace.fingerprint = trace.fingerprint() if is_slow else None
        if not (retained or is_slow):
            return
        entry = None
        if is_slow:
            entry = {
                "captured_at": trace.t_wall,
                "wall_ms": round(trace.wall_s * 1e3, 3),
                "fingerprint": fingerprint or trace.fingerprint or {},
                "trace": trace.to_dict(),
            }
        with self._lock:
            if retained:
                self.buffer.append(trace)
            if entry is not None:
                self.slow.append(entry)
                cap = max(int(conf.OBS_SLOW_MAX.get()), 1)
                if len(self.slow) > cap:
                    del self.slow[: len(self.slow) - cap]
        # retention counters land on the configured registry, or the
        # process-global fallback like every other unconfigured
        # component — recorded AFTER the tracer lock releases (rank 76
        # -> 80, the declared order)
        from geomesa_tpu.metrics import resolve

        m = resolve(self.metrics)
        if retained:
            m.counter("geomesa.obs.traces")
        if is_slow:
            m.counter("geomesa.obs.slow_queries")

    def trace(self, name: str, capture: bool = True, **attrs):
        """``begin`` + activate + ``end`` as one context manager,
        yielding the Trace (or None when disarmed). Opened and closed
        on one thread, so its root, retained, is a profiler annotation
        like any ``with`` span."""
        return _RootCtx(self, name, capture, attrs)

    # -- propagation ------------------------------------------------------
    def current(self) -> Optional[Span]:
        return getattr(_tls, "span", None)

    def span(self, name: str, cpu: bool = False, **attrs):
        """A child span under this thread's active span — the hot-path
        entry: one thread-local probe and the shared null context when
        untraced. ``cpu=True``: a phase that never sleeps by design; on
        a retained trace it also carries ``cpu_s``, its thread's CPU
        time."""
        cur = getattr(_tls, "span", None)
        if cur is None:
            return NULL_SPAN
        return _SpanCtx(Span(cur.trace, name, cur.span_id, attrs or None), cpu)

    def activate(self, span: Optional[Span]):
        """Adopt an existing span as this thread's active context (the
        scheduler dispatcher / flush-worker hop); no-op on None."""
        return _Activation(span)

    def add_span(self, parent: Optional[Span], name: str, t0: float,
                 end: float, **attrs) -> Optional[Span]:
        """Record a phase measured elsewhere (queue wait between
        threads): explicit start/end, finished immediately."""
        if parent is None:
            return None
        s = Span(parent.trace, name, parent.span_id, attrs or None, t0=t0)
        s.finish(end=end)
        return s

    # -- surfaces ---------------------------------------------------------
    def traces(self) -> list[Trace]:
        with self._lock:
            return self.buffer.items()

    def slow_queries(self, type_name: "str | None" = None) -> list[dict]:
        """The slow-query ring, newest last: each entry carries the
        wall, the plan fingerprint and the full span tree.
        ``type_name`` filters to captures whose fingerprint names that
        schema (ops-plane ``/debug/slow?type=``)."""
        with self._lock:
            out = [dict(e) for e in self.slow]
        if type_name is not None:
            out = [
                e for e in out
                if e.get("fingerprint", {}).get("type") == type_name
            ]
        return out

    def reset(self) -> None:
        with self._lock:
            self.buffer = TraceBuffer(conf.OBS_TRACE_BUFFER.get())
            self.slow = []
            self._n_roots = {}

    def chrome_payload(self) -> dict:
        """Every retained trace (buffer + slow ring, deduped by trace
        id) and every stall of the process's ring as a Chrome
        trace-event payload — the ``/debug/trace`` body, and what
        :meth:`dump` writes."""
        with self._lock:
            traces = self.buffer.items()
            slow = [e["trace"] for e in self.slow]
        events = _stall_events(os.getpid())
        for tr in traces:
            events.extend(_chrome_events(tr.to_dict()))
        seen = {tr.trace_id for tr in traces}
        for td in slow:
            if td["trace_id"] not in seen:
                events.extend(_chrome_events(td))
        return {"traceEvents": events}

    def dump(self, path: str) -> str:
        """Write every retained trace (buffer + slow ring) as Chrome
        trace-event JSON — openable in chrome://tracing or Perfetto —
        and return the path."""
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.chrome_payload(), fh, indent=1)
        return path


class _RootCtx:
    __slots__ = ("_tracer", "_name", "_capture", "_attrs", "_trace", "_act")

    def __init__(self, tracer: Tracer, name: str, capture: bool, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._capture = capture
        self._attrs = attrs
        self._trace = None
        self._act = None

    def __enter__(self) -> Optional[Trace]:
        self._trace = self._tracer.begin(
            self._name, self._capture, **self._attrs
        )
        if self._trace is not None:
            # retained: the root reads its thread's CPU clock, so a
            # request's whole CPU on its own thread is a pooled reading
            self._act = _Activation(self._trace.root._open(cpu=True))
            self._act.__enter__()
        return self._trace

    def __exit__(self, *exc) -> None:
        if self._act is not None:
            self._act.__exit__(*exc)
        self._tracer.end(self._trace)


def _chrome_events(td: dict) -> list[dict]:
    """Chrome trace-event ``ph:"X"`` complete events for one trace
    dict: one process, a lane per real thread (``Span.tid``), ``ts`` in
    microseconds from the trace's wall-clock anchor — so the traces of
    concurrent requests line up on one timeline, each span on the
    thread that ran it. ``args`` carries the trace id beside the
    span's attributes."""
    out = []
    pid = os.getpid()
    base_us = td["t_wall"] * 1e6
    for s in td["spans"]:
        out.append({
            "name": s["name"],
            "ph": "X",
            "pid": pid,
            "tid": s["tid"],
            "ts": round(base_us + s["start_ms"] * 1e3, 1),
            "dur": round(s["dur_ms"] * 1e3, 1),
            "args": {"trace_id": td["trace_id"], **s.get("attrs", {})},
        })
    return out


def phase_breakdown(trace: Optional[Trace]) -> list[str]:
    """Human-readable top-level phase lines for explain trails:
    ``trace: <phase> <dur>ms`` per phase plus the covered fraction, and
    one line more where the runtime stalled the operation: what the root
    waited for the collector (``gc_wait_s``), what its spans spent
    compiling (``compile_s``) and, where it has any, the times it let the
    interpreter lock go (``handoffs``)."""
    if trace is None or trace.wall_s <= 0:
        return []
    lines = []
    covered = 0.0
    for s in trace.phases():
        covered += s.dur_s
        lines.append(f"trace: {s.name} {s.dur_s * 1e3:.3f}ms")
    lines.append(
        f"trace: wall {trace.wall_s * 1e3:.3f}ms, phases cover "
        f"{100.0 * covered / trace.wall_s:.1f}%"
    )
    waited = (trace.root.attrs or {}).get("gc_wait_s", 0.0)
    spans = {trace.root, *trace.spans}  # the root is among them once it has finished
    compiling = sum((s.attrs or {}).get("compile_s", 0.0) for s in spans)
    handoffs = sum((s.attrs or {}).get("handoffs", 0) for s in spans)
    if waited or compiling or handoffs:
        lines.append(
            f"trace: stalled gc {waited * 1e3:.3f}ms, "
            f"compile {compiling * 1e3:.3f}ms"
            + (f", handoffs {handoffs}" if handoffs else "")
        )
    return lines


# the installed process tracer; install() swaps it (tests arm the lock
# witness first, then install a fresh instance so its lock is wrapped)
TRACER = Tracer()


def tracer() -> Tracer:
    """The installed process :class:`Tracer`."""
    return TRACER


def install(t: Tracer) -> Tracer:
    """Replace the installed tracer (tests; custom retention) and
    return it."""
    global TRACER
    TRACER = t
    return t


def span(name: str, cpu: bool = False, **attrs):
    """Module-level child-span helper — ``obs.span("scan")`` from any
    hot path; the disarmed cost is one thread-local probe."""
    return TRACER.span(name, cpu, **attrs)


def event(name: str) -> bool:
    """Start segment ``name`` of this thread's active span
    (:meth:`Span.event`) — for code that runs inside a caller's span
    without being handed it (the table under the planner's ``scan``).
    False, after one thread-local probe, when nothing is traced."""
    cur = getattr(_tls, "span", None)
    return cur is not None and cur.event(name)


def add(name: str, n) -> None:
    """:meth:`Span.add` on this thread's active span, if any."""
    cur = getattr(_tls, "span", None)
    if cur is not None:
        cur.add(name, n)
