"""IndexTable: one index's sorted, device-resident columnar table.

The reference materializes each index as a sorted KV table (Accumulo/HBase
tablets; write path Z3IndexKeySpace.toIndexKey + IndexWriter, /root/
reference/geomesa-index-api/src/main/scala/org/locationtech/geomesa/index/
z3/Z3IndexKeySpace.scala:63-95). Here the same logical layout is a
struct-of-arrays table sorted lexicographically by (bin, z):

- host side: the sort keys (bins i32, zs u64), per-bin segment offsets, and
  the permutation back to the backing FeatureCollection — used for
  range -> row-span -> block pruning (the analogue of seeking scan ranges
  in a tablet server). The sort itself is the native radix argsort
  (geomesa_tpu.native.sort_bins_z) — the LSM "flush" hot path;
- device side: the predicate columns, laid out [n_blocks, SUB, 128]
  (BLOCK = SUB*128 rows) so candidate blocks DMA straight into VMEM for
  the Pallas bitmask kernel (geomesa_tpu.scan.block_kernels). Pad rows
  carry never-matching sentinels.

Query execution (round-3 redesign, see PERF.md): ONE device call + ONE
batched pull per query. The host turns covering z-ranges into row spans
(searchsorted) and block ids; rows in *contained* ranges (reference
ZN.zranges contained semantics, ZN.scala:110-242 — classified here against
shrunk inner ordinals so containment is exact at f64) are taken from the
spans directly with no device work and no refinement; remaining blocks go
through the kernel, which returns wide + inner bit planes. Host refinement
then touches only `wide & ~inner` boundary rows.
"""

from __future__ import annotations

import weakref

import numpy as np

from geomesa_tpu.index.api import IndexKeySpace, ScanConfig, WriteKeys, expand_runs
from geomesa_tpu.metrics import global_registry
from geomesa_tpu.obs.trace import add as _oadd
from geomesa_tpu.obs.trace import event as _oevent
from geomesa_tpu.planning.errors import check_deadline
from geomesa_tpu.scan import block_kernels as bk

# the tables know no store: they count into the process-global registry
_METRICS = global_registry()

_SENTINELS = {
    "x": np.float32(np.inf),
    "y": np.float32(np.inf),
    "gxmin": np.float32(np.inf),
    "gymin": np.float32(np.inf),
    "gxmax": np.float32(-np.inf),
    "gymax": np.float32(-np.inf),
    "tbin": np.int32(-1),
    "toff": np.int32(0),
    "tw": np.int32(-1),  # packed-time: bin -1 never matches
}

# Canonical fused-dispatch shape (scan_submit_many): every multi-member
# chunk pads its slot list to EXACTLY the table's ``fused_slots`` and its
# param stacks to FUSED_CHUNK_Q, so there is ONE fused kernel variant per
# (projected columns, predicate flags) — compiled at warmup, zero
# query-time recompiles (the same doctrine as the single-query M-bucket
# ladder). ``fused_slots`` is FUSED_CHUNK_SLOTS clamped down to the
# table's own block-count bucket: the kernel's scan cost is proportional
# to slots whether they are real or pads, so a 123-block table padding to
# 2048 slots would scan 16x its own size per dispatch. The fixed size also
# bounds device memory: plane bytes — and, on the XLA fallback, the column
# gathers — scale with the chunk's slot count, not the whole batch.
# 2048 slots = 4.2M rows per dispatch at the default tile; greedy packing
# keeps pad waste small, and members broader than half a chunk take the
# single-query ladder instead. A table growing past its block-count
# bucket compiles the next fused shape on first use — the same
# growth-triggered compile the single-query ladder already has (new
# buckets past warmup's table size), softened by the persistent compile
# cache; re-run warmup() after major growth to take it off the hot path.
FUSED_CHUNK_SLOTS = 2048
FUSED_CHUNK_Q = 128


def _block_rows(tile: "int | None") -> int:
    """Rows per device scan block for a ``tile`` request (the ONE rounding
    rule, shared by IndexTable.__init__ and the fold-plan eligibility
    check so they can never drift)."""
    return bk.BLOCK if tile is None else max(4096, -(-int(tile) // 4096) * 4096)


def _device_fold_enabled() -> bool:
    """Whether folded_table may build device columns through the
    device-side fold plan (geomesa.stream.fold.device). 'on' forces it;
    'auto' (the default) uses it only on a TPU backend, where the
    O(touched)-vs-O(table) LINK transfer is the cost that matters — on
    the CPU backend every "transfer" is a memcpy, while the plan's
    eager device ops re-specialize per slice shape, so the host
    gather + upload path is strictly faster there."""
    import jax

    from geomesa_tpu.conf import STREAM_FOLD_DEVICE

    mode = str(STREAM_FOLD_DEVICE.get()).lower()
    if mode in ("on", "1", "true"):
        return True
    return mode == "auto" and jax.default_backend() == "tpu"


class SortedKeys:
    """Host-side sorted key structure shared by the single-device and
    distributed tables: the (bin, z) lexicographic sort, the permutation
    back to feature ordinals, and searchsorted range -> row-span pruning
    (the analogue of seeking scan ranges in a tablet server)."""

    def __init__(
        self,
        keyspace: IndexKeySpace,
        keys: WriteKeys,
        tile: int,
        sorted_state: "np.ndarray | None" = None,
    ):
        self.keyspace = keyspace
        self.tile = tile
        n = len(keys.bins)
        self.n = n

        if sorted_state is not None:
            # the caller already knows the sort order (merge compaction:
            # storage.table.merged_table) — skip the radix sort entirely
            perm = sorted_state
            self.rows_sorted = 0
        elif keys.sub is not None:
            # secondary sort words (string attribute indexes): full
            # lexicographic (bin, z, sub[0], ..., sub[W-1]) order so
            # z-tie runs stay value-sorted and candidate_spans can narrow
            # boundary runs (np.lexsort: LAST key is most significant)
            sub_keys = tuple(
                keys.sub[:, j] for j in range(keys.sub.shape[1] - 1, -1, -1)
            )
            perm = np.lexsort(sub_keys + (keys.zs, keys.bins))
            self.rows_sorted = n
        else:
            from geomesa_tpu import native

            perm = native.sort_bins_z(keys.bins, keys.zs)
            if perm is None:
                perm = np.lexsort((keys.zs, keys.bins))
            self.rows_sorted = n
        self.perm = perm  # table row -> feature ordinal (u32 or i64)
        self.bins = _take(keys.bins, perm)
        self.zs = _take(keys.zs, perm)
        self.subkeys = keys.sub[perm] if keys.sub is not None else None  # [n, W]

        # per-bin segments for searchsorted pruning. self.bins is sorted
        # (it IS the primary sort key), so the segment boundaries come
        # from one linear diff pass — np.unique's O(n log n) sort here
        # was a measurable slice of every table build (the round-11 fold
        # profile: ~60 ms per 3M-row build, x2 indexes x slices)
        if n:
            starts = np.concatenate([
                [0], np.flatnonzero(self.bins[1:] != self.bins[:-1]) + 1
            ])
            self.ubins = self.bins[starts]
        else:
            starts = np.zeros(0, np.int64)
            self.ubins = self.bins[:0]
        self.bin_starts = np.append(starts, n).astype(np.int64)

    def _narrow_lo(self, a: int, ae: int, words: np.ndarray) -> int:
        """First row >= the bound within the primary tie-run [a, ae):
        descend word by word — rows below the word are dropped, the
        word-tie run recurses, and final-level ties stay included."""
        for j in range(self.subkeys.shape[1]):
            if ae <= a:
                return a
            col = self.subkeys[a:ae, j]
            w = words[j] if j < len(words) else 0
            left = a + int(np.searchsorted(col, w, side="left"))
            right = a + int(np.searchsorted(col, w, side="right"))
            if right <= left:
                return left  # no exact ties at this word: done
            a, ae = left, right
        return a

    def _narrow_hi(self, hs: int, z: int, words: np.ndarray) -> int:
        """One past the last row <= the bound within the primary tie-run
        [hs, z): rows below the word are kept whole, the word-tie run
        recurses, rows above are dropped."""
        U64 = np.uint64(0xFFFFFFFFFFFFFFFF)
        for j in range(self.subkeys.shape[1]):
            if z <= hs:
                return z
            col = self.subkeys[hs:z, j]
            w = words[j] if j < len(words) else U64
            left = hs + int(np.searchsorted(col, w, side="left"))
            right = hs + int(np.searchsorted(col, w, side="right"))
            if right <= left:
                return left  # everything below the word is included
            hs, z = left, right
        return z

    def pad_cols(self, keys: WriteKeys, n_pad: int) -> dict:
        """Sorted device columns padded to n_pad rows with never-matching
        sentinels."""
        cols = {}
        for name, col in keys.device_cols.items():
            out = np.full(n_pad, _SENTINELS[name], dtype=col.dtype)
            out[: self.n] = _take(col, self.perm)
            cols[name] = out
        return cols

    # -- pruning ---------------------------------------------------------
    def candidate_spans(self, config: ScanConfig) -> "RowSpans":
        """Merged, sorted row spans [lo, hi) covering ALL scan ranges
        (contained + overlapping) — the cost estimator's input."""
        return self.scan_spans(config)[0].union

    def candidate_spans_split(self, config: ScanConfig):
        """(overlap_spans, contained_spans): row spans [lo, hi) of the
        non-contained vs contained scan ranges. Contained ranges' rows are
        certain hits (no device predicate, no refinement) when
        ``config.contained_exact`` — otherwise they are folded into the
        overlap set."""
        spans = self.scan_spans(config)[0]
        return spans.overlap, spans.contained

    def scan_spans(self, config: ScanConfig) -> "tuple[ScanSpans, bool]":
        """``config``'s candidate spans over THIS table, and whether they
        were found rather than computed. They are computed once a
        (config, table) and held in the config's one slot, so the
        planner's ``cost()`` and the dispatch of the same query (and a
        warm repeat filter, through the planner's config memo) share one
        computation. The slot is valid by identity only: row positions
        mean nothing in another table, so a config costed against a table
        that a write has since swapped recomputes here. Two threads
        filling the slot at once both compute the same value (benign)."""
        slot = config._spans
        if slot is not None and slot[0]() is self:
            _METRICS.counter("geomesa.scan.spans.reused")
            return slot[1], True
        spans = self._compute_spans([config])[0]
        config._spans = (weakref.ref(self), spans)
        _METRICS.counter("geomesa.scan.spans.computed")
        return spans, False

    def scan_spans_many(self, configs: list) -> "list[ScanSpans]":
        """:meth:`scan_spans` of several configs: those whose slot holds
        this table's spans find them, the others are searched in ONE pass
        (:meth:`_compute_spans`) and their slots filled as ``scan_spans``
        fills them, so the dispatch that follows finds them."""
        out: list = [None] * len(configs)
        todo = []
        for k, config in enumerate(configs):
            slot = config._spans
            if slot is not None and slot[0]() is self:
                out[k] = slot[1]
            else:
                todo.append(k)
        if len(todo) < len(configs):
            _METRICS.counter("geomesa.scan.spans.reused", len(configs) - len(todo))
        if todo:
            ref = weakref.ref(self)
            computed = self._compute_spans([configs[k] for k in todo])
            for k, spans in zip(todo, computed):
                configs[k]._spans = (ref, spans)
                out[k] = spans
            _METRICS.counter("geomesa.scan.spans.computed", len(todo))
        return out

    def candidate_rows_many(self, configs: list) -> np.ndarray:
        """Rows covered by each config's candidate spans (the cost
        estimator's number, i64 a config), through :meth:`scan_spans_many`."""
        return np.array(
            [s.union.n_rows() for s in self.scan_spans_many(configs)], dtype=np.int64
        )

    def _compute_spans(self, configs: list) -> "list[ScanSpans]":
        """The (overlap, contained) merged row spans of each config's
        ranges, and of several configs their union: the ranges of ALL
        configs sorted by bin, two searchsorted calls a bin, then masks and
        one merge a class with the config's number as the major key — no
        Python object a range, no search a config."""
        if self.subkeys is not None and len(configs) > 1:
            # tie-run narrowing walks a config's own word columns
            return [self._compute_spans([c])[0] for c in configs]
        counts = [len(c.range_bins) for c in configs]
        if not sum(counts):
            return [ScanSpans(NO_SPANS, NO_SPANS) for _ in configs]
        rbins = _joined([c.range_bins for c in configs])
        rlo = _joined([c.range_lo for c in configs])
        rhi = _joined([c.range_hi for c in configs])
        # contained flags count only where the config vouches for them
        flagged = any(c.contained_exact and n for c, n in zip(configs, counts))
        cont = None
        if flagged:
            cont = _joined([
                c.range_contained if c.contained_exact else np.zeros(n, bool)
                for c, n in zip(configs, counts)
            ])
        member = None
        if len(configs) > 1:
            member = np.repeat(np.arange(len(configs), dtype=np.int64), counts)
        narrow = self.subkeys is not None and configs[0].range_lo2 is not None
        lo2 = hi2 = None
        if narrow:
            lo2, hi2 = configs[0].range_lo2, configs[0].range_hi2
        if member is not None and (rbins[1:] < rbins[:-1]).any():
            # a bin's ranges of every config together: searched once (one
            # config's come grouped in runs, and a bin met in two runs is
            # searched twice and merges like any other ranges)
            order = np.argsort(rbins, kind="stable")
            rbins, rlo, rhi, member = rbins[order], rlo[order], rhi[order], member[order]
            cont = None if cont is None else cont[order]
        n = len(rbins)
        cuts = (np.flatnonzero(rbins[1:] != rbins[:-1]) + 1).tolist()
        los, his = [], []
        for a, z in zip([0, *cuts], [*cuts, n]):
            b = rbins[a]
            i = int(np.searchsorted(self.ubins, b))
            if i >= len(self.ubins) or self.ubins[i] != b:
                # a bin the table lacks: empty spans
                los.append(np.zeros(z - a, np.int64))
                his.append(los[-1])
                continue
            s, e = int(self.bin_starts[i]), int(self.bin_starts[i + 1])
            seg = self.zs[s:e]
            lo = np.searchsorted(seg, rlo[a:z], side="left") + s
            hi = np.searchsorted(seg, rhi[a:z], side="right") + s
            if narrow:
                # narrow each range's boundary TIE-RUNS by the secondary
                # sort words: rows sharing the lo (hi) primary code are
                # value-sorted by the word columns, so long-string bounds
                # prune exactly past the 8-byte prefix (VERDICT r4 weak
                # #4; ties at every word stay INCLUDED — superset, host
                # refinement is exact)
                lo_end = np.searchsorted(seg, rlo[a:z], side="right") + s
                hi_start = np.searchsorted(seg, rhi[a:z], side="left") + s
                for k in range(z - a):
                    lo[k] = self._narrow_lo(int(lo[k]), int(lo_end[k]), lo2[a + k])
                    hi[k] = self._narrow_hi(int(hi_start[k]), int(hi[k]), hi2[a + k])
            los.append(lo)
            his.append(hi)
        lo, hi = _joined(los), _joined(his)
        live = hi > lo
        stride = self.n + 1
        if member is not None:
            # config k's spans live in [k * stride, k * stride + n]: spans
            # of two configs never touch, so one merge serves them all
            lo += member * stride
            hi += member * stride
        if cont is None:
            over, inside = _merge_spans(lo[live], hi[live]), NO_SPANS
        else:
            outer, inner = live & ~cont, live & cont
            over = _merge_spans(lo[outer], hi[outer])
            inside = _merge_spans(lo[inner], hi[inner])
        if member is None:
            return [ScanSpans(over, inside)]
        # the unions too in one merge, of the two classes' merged spans
        k = len(configs)
        unions = [None] * k
        if len(inside) and len(over):
            unions = _split_spans(_merge_spans(
                np.concatenate([over.lo, inside.lo]), np.concatenate([over.hi, inside.hi])
            ), k, stride)
        return [
            ScanSpans(o, c, o if not len(c) else (c if not len(o) else u))
            for o, c, u in zip(
                _split_spans(over, k, stride), _split_spans(inside, k, stride), unions
            )
        ]


def _await_device(arrays) -> bool:
    """Under a caller's span (its ``scan`` or ``agg``), cut the pull in
    two: segment ``wait`` until the device has the result (the kernel and
    whatever queued before it), then ``pull``, which the caller's
    ``device_get`` fills (the rest of the copy started at dispatch).
    Untraced, nothing: ``device_get`` waits for both, as it always did.
    Both let the interpreter lock go: two ``handoffs`` on the span."""
    traced = _oevent("wait")
    if traced:
        import jax

        jax.block_until_ready(arrays)
        _oevent("pull")
        _oadd("handoffs", 2)
    return traced


def _take(col: np.ndarray, perm: np.ndarray) -> np.ndarray:
    from geomesa_tpu import native

    if perm.dtype == np.uint32:
        out = native.take(col, perm)
        if out is not None:
            return out
    return col[perm]


class RowSpans:
    """Row spans [lo[k], hi[k]) of a sorted table as two parallel int64
    arrays: ascending, non-empty, and merged (no two touch or overlap),
    as :func:`_merge_spans` makes them."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: np.ndarray, hi: np.ndarray):
        self.lo = lo
        self.hi = hi

    def __len__(self) -> int:
        return len(self.lo)

    def n_rows(self) -> int:
        """Rows covered (the cost estimator's number)."""
        return int((self.hi - self.lo).sum())


NO_SPANS = RowSpans(np.zeros(0, np.int64), np.zeros(0, np.int64))


class ScanSpans:
    """One scan config's candidate spans over one sorted table: the
    ``overlap`` class (kernel + refinement), the ``contained`` class
    (certain rows, no device work) and, made on first use, their
    ``union`` (cost, aggregations, span-exact clipping)."""

    __slots__ = ("overlap", "contained", "_union")

    def __init__(self, overlap: RowSpans, contained: RowSpans,
                 union: "RowSpans | None" = None):
        self.overlap = overlap
        self.contained = contained
        self._union = union

    @property
    def union(self) -> RowSpans:
        u = self._union
        if u is None:
            o, c = self.overlap, self.contained
            if not len(c):
                u = o
            elif not len(o):
                u = c
            else:
                u = _merge_spans(
                    np.concatenate([o.lo, c.lo]), np.concatenate([o.hi, c.hi])
                )
            self._union = u
        return u


def _joined(parts: list) -> np.ndarray:
    return parts[0] if len(parts) == 1 else np.concatenate(parts)


def _split_spans(spans: RowSpans, k: int, stride: int) -> "list[RowSpans]":
    """The spans of configs 0..k-1 out of one merge in which config j's
    rows were shifted by ``j * stride`` (:meth:`SortedKeys._compute_spans`),
    shifted back."""
    if not len(spans):
        return [NO_SPANS] * k
    cuts = np.searchsorted(spans.lo, np.arange(k + 1, dtype=np.int64) * stride)
    shift = np.repeat(np.arange(k, dtype=np.int64) * stride, np.diff(cuts))
    lo, hi = spans.lo - shift, spans.hi - shift
    cuts = cuts.tolist()
    return [
        RowSpans(lo[a:z], hi[a:z]) if z > a else NO_SPANS
        for a, z in zip(cuts[:-1], cuts[1:])
    ]


def _merge_spans(lo: np.ndarray, hi: np.ndarray) -> RowSpans:
    """Union of non-empty [lo, hi) spans in any order: one sort by ``lo``,
    the running maximum of ``hi``, and a boundary wherever a span starts
    past everything before it (spans that touch merge)."""
    n = len(lo)
    if n == 0:
        return NO_SPANS
    lo = lo.astype(np.int64, copy=False)
    hi = hi.astype(np.int64, copy=False)
    if n > 1:
        if (lo[1:] < lo[:-1]).any():  # the ranges of one bin come ascending
            order = np.argsort(lo, kind="stable")
            lo, hi = lo[order], hi[order]
        reach = np.maximum.accumulate(hi)
        first = np.flatnonzero(lo[1:] > reach[:-1]) + 1
        lo = lo[np.concatenate([[0], first])]
        hi = reach[np.concatenate([first - 1, [n - 1]])]
    return RowSpans(lo, hi)


def _span_rows(spans: RowSpans) -> np.ndarray:
    """Every row of the spans, ascending."""
    if not len(spans):
        return np.zeros(0, np.int64)
    return expand_runs(spans.lo, spans.hi - spans.lo)


def _merge_sorted_rows(cont_rows: np.ndarray, kr: np.ndarray, kc: np.ndarray):
    """Merge two ascending row runs — contained rows (all certain) and
    kernel rows with their certainty — in O(n) via the positional two-run
    merge (an argsort over the concatenation costs n log n and dominated
    large-query latency; see PERF.md)."""
    nm, nd = len(cont_rows), len(kr)
    if nd == 0:
        return cont_rows, np.ones(nm, bool)
    if nm == 0:
        return kr, kc
    pos = np.searchsorted(cont_rows, kr)
    kr_dest = pos + np.arange(nd, dtype=np.int64)
    cont_dest = np.arange(nm, dtype=np.int64) + np.searchsorted(
        pos, np.arange(nm, dtype=np.int64), side="right"
    )
    rows = np.empty(nm + nd, np.int64)
    certain = np.empty(nm + nd, bool)
    rows[cont_dest] = cont_rows
    certain[cont_dest] = True
    rows[kr_dest] = kr
    certain[kr_dest] = kc
    return rows, certain


def _spans_intersect(lo: np.ndarray, hi: np.ndarray, spans: RowSpans) -> np.ndarray:
    """Boolean mask: which [lo[k], hi[k]) intersect any span."""
    if not len(spans):
        return np.zeros(len(lo), dtype=bool)
    # the first span that ends past lo is the only one that can reach it
    idx = np.searchsorted(spans.hi, lo, side="right")
    return (idx < len(spans)) & (spans.lo[np.minimum(idx, len(spans) - 1)] < hi)


def _rows_in_spans(rows: np.ndarray, spans: RowSpans) -> np.ndarray:
    """Boolean mask: which sorted ``rows`` fall inside any [lo, hi) span."""
    if not len(spans) or len(rows) == 0:
        return np.zeros(len(rows), dtype=bool)
    idx = np.searchsorted(spans.lo, rows, side="right") - 1
    return (idx >= 0) & (rows < spans.hi[np.maximum(idx, 0)])


class IndexTable(SortedKeys):
    """Sorted columnar table for one (feature type, index) pair.

    This class is the WHOLE scan engine: subclasses (the distributed table,
    parallel.dtable) override only the device hooks — ``_round_blocks`` /
    ``_place_cols`` for layout and ``_device_scan`` / ``_device_pops`` /
    ``_device_density_submit`` / ``_device_bounds`` for execution — so the
    single-chip and multi-chip paths share one pruning + exactness-tier +
    decode pipeline (the reference runs the same coprocessor push-down on
    every region server, geomesa-hbase-rpc/.../GeoMesaCoprocessor.scala:
    28-79; rounds 2-3 had diverging engines, VERDICT r3 #1).
    """

    def __init__(
        self,
        keyspace: IndexKeySpace,
        keys: WriteKeys,
        tile: int | None = None,
        device=None,
        sorted_state: "np.ndarray | None" = None,
        reuse: "tuple[IndexTable, int] | None" = None,
        fold_plan: "tuple | None" = None,
    ):
        # device scan granularity: BLOCK rows (Pallas layout constraint:
        # SUB multiple of 32 sublanes); `tile` requests are rounded up
        block = _block_rows(tile)
        super().__init__(keyspace, keys, block, sorted_state=sorted_state)
        self.block = block
        self.sub = block // bk.LANES

        import geomesa_tpu

        geomesa_tpu.enable_compile_cache()
        n_blocks = self._round_blocks(max(1, -(-self.n // block)))
        self.n_blocks = n_blocks
        self.n_pad = n_blocks * block
        self.col_names = tuple(sorted(keys.device_cols))
        self.extent = "gxmin" in keys.device_cols
        # projection accounting for the most recent kernel call
        self.last_scan_cols: tuple = ()
        self.last_scan_bytes = 0
        # ``reuse``: (old table, first changed sorted row) — merge
        # compaction keeps every device block before the first insertion
        # point and uploads only the changed suffix
        self._reuse = reuse
        if (
            fold_plan is not None
            and type(self)._place_cols is IndexTable._place_cols
        ):
            # device-side fold plan (round 11, docs/streaming.md
            # "Incremental fold"): the folded columns are computed ON
            # DEVICE from the old table's resident blocks plus an
            # O(touched) upload, instead of re-gathering and re-uploading
            # the O(table) sorted suffix over the link
            self._fold_cols_device(fold_plan, device)
        elif type(self)._place_cols is IndexTable._place_cols:
            # bounded-memory build: sort-gather each column in
            # block-aligned spans and upload it before touching the next —
            # host peak is ONE padded column, never a second full copy of
            # the column set (the 1B compaction OOM; docs/ingest.md)
            self._stream_cols(keys, device)
        else:
            # subclasses (the distributed table) own their layout via the
            # whole-dict hook; they get the classic padded column set
            self._place_cols(self.pad_cols(keys, self.n_pad), device)

    # -- layout hooks ----------------------------------------------------
    def _round_blocks(self, n_blocks: int) -> int:
        """Block-count rounding hook (the distributed table rounds up to a
        multiple of the mesh size)."""
        return n_blocks

    @property
    def fused_slots(self) -> int:
        """Slot count of THIS table's canonical fused-dispatch shape:
        FUSED_CHUNK_SLOTS clamped down to the table's own block-count
        bucket (see the constants' doctrine note) — still one static
        shape per (columns, flags), but a small table never scans a
        multiple of its own size in pad slots. For the distributed table
        this is the PER-DEVICE slot bucket."""
        return min(FUSED_CHUNK_SLOTS, bk.bucket_of(self.n_blocks))

    @property
    def fused_pack_capacity(self) -> int:
        """Candidate-block capacity the chunk packer fills per fused
        chunk. Equal to ``fused_slots`` on a single-device table; the
        distributed table multiplies by the mesh size (its candidates
        split round-robin across devices, each padded to ``fused_slots``
        local slots)."""
        return self.fused_slots

    def _fused_supported(self) -> bool:
        """Whether scan_submit_many may dispatch fused chunks on this
        table: true for the base engine, and for subclasses that override
        the device seam ONLY IF they also provide their own
        ``_submit_fused_chunk`` (DistributedIndexTable's shard_map fused
        dispatch) — otherwise the fused kernel would silently bypass the
        subclass's device hooks."""
        return (
            type(self)._device_scan_submit is IndexTable._device_scan_submit
            or type(self)._submit_fused_chunk is not IndexTable._submit_fused_chunk
        )

    def _reuse_prefix(self, col_names) -> tuple:
        """(old table, first reusable block count) from ``self._reuse``,
        or (None, 0) when nothing can be reused."""
        if self._reuse is not None:
            cand, first_row = self._reuse
            if cand.block == self.block and set(cand.col_names) == set(col_names):
                return cand, min(
                    first_row // self.block, cand.n_blocks, self.n_blocks
                )
        return None, 0

    def _place_cols(self, cols: dict, device) -> None:
        """Put the padded columns on device in the [n_blocks, SUB, 128]
        scan layout. With ``self._reuse`` set, device blocks before the
        first changed row are taken from the old table (prefix rows are
        byte-identical) and only the suffix is uploaded."""
        import jax
        import jax.numpy as jnp

        old, first_block = self._reuse_prefix(set(cols))
        self.rows_uploaded = (self.n_blocks - first_block) * self.block
        self.cols3 = {}
        for k, v in cols.items():
            v3 = v.reshape(self.n_blocks, self.sub, bk.LANES)
            if old is not None and first_block > 0:
                suffix = jax.device_put(v3[first_block:], device) if device else jax.device_put(v3[first_block:])
                self.cols3[k] = jnp.concatenate([old.cols3[k][:first_block], suffix])
            else:
                self.cols3[k] = jax.device_put(v3, device) if device else jax.device_put(v3)

    def _stream_cols(self, keys: WriteKeys, device) -> None:
        """Bounded-memory `_place_cols`: build and upload the sorted
        padded columns ONE AT A TIME, gathering each through block-aligned
        spans of ``geomesa.tpu.compact.span.rows`` rows, and release the
        host copy before the next column starts. The classic path
        materialized every sorted column simultaneously — at 1B rows that
        is a second full copy of the column set next to the unsorted
        source, which OOM'd a 125 GB host (ISSUE 4; docs/ingest.md).
        Keeps the merge-compaction suffix reuse: with ``self._reuse`` set,
        only rows past the first changed block are gathered/uploaded."""
        import jax
        import jax.numpy as jnp

        from geomesa_tpu.conf import COMPACT_SPAN_ROWS

        old, first_block = self._reuse_prefix(set(keys.device_cols))
        self.rows_uploaded = (self.n_blocks - first_block) * self.block
        lo = first_block * self.block  # first sorted row to (re)build
        span = max(self.block, (COMPACT_SPAN_ROWS.get() // self.block) * self.block)
        self.cols3 = {}
        for k in self.col_names:
            col = keys.device_cols[k]
            out = np.empty(self.n_pad - lo, dtype=col.dtype)
            for s in range(lo, self.n, span):
                e = min(s + span, self.n)
                out[s - lo : e - lo] = _take(col, self.perm[s:e])
            out[self.n - lo :] = _SENTINELS[k]  # pad rows never match
            v3 = out.reshape(self.n_blocks - first_block, self.sub, bk.LANES)
            suffix = jax.device_put(v3, device) if device else jax.device_put(v3)
            if old is not None and first_block > 0:
                self.cols3[k] = jnp.concatenate(
                    [old.cols3[k][:first_block], suffix]
                )
            else:
                self.cols3[k] = suffix
            del out, v3, suffix

    def _fold_cols_device(self, plan, device) -> None:
        """Fold-plan device build (round 11): the new sorted columns are a
        pure permutation of the OLD table's device-resident rows plus the
        delta's — so instead of host-gathering and uploading the changed
        O(table) suffix (``_stream_cols``), ship only the fold's
        *description* (removed sorted positions, insert destinations, the
        delta's sorted rows — all O(touched)) and let the device compute
        each new row's source:

        - a non-insert destination ``i`` holds survivor rank
          ``r = i - #inserts<=i``; its OLD sorted position solves
          ``p = r + #removed<=p`` via one searchsorted over
          ``removed - arange`` (survivors-before-each-removal, a
          non-decreasing key);
        - an insert destination takes its value from the uploaded sorted
          delta rows;
        - pad rows past ``self.n`` take the never-matching sentinels.

        One gather per column over HBM — fold-time cost, never on the
        query path (the "no gathers" doctrine in scan/block_kernels.py
        guards kernels, not maintenance). Bit-identical to the host
        rebuild: every value is a copy of an old-table or delta value
        (tests/test_streaming_tier.py pins cols3 equality both ways).
        ``rows_uploaded`` records the rows that actually crossed the
        link — the fold's O(touched) claim."""
        import jax
        import jax.numpy as jnp

        old, removed, delta_dest, delta_sorted_cols = plan
        nr, nd = len(removed), len(delta_dest)
        # i32 position math: the fold plan is gated to < 2**31 padded rows
        # (the u32-perm regime; the 1B single-chip layout is well inside)
        i = jnp.arange(self.n_pad, dtype=jnp.int32)
        if nd:
            dd = jnp.asarray(np.asarray(delta_dest, np.int32))
            k_ins = jnp.searchsorted(dd, i, side="right").astype(jnp.int32)
            is_ins = (k_ins > 0) & (dd[jnp.clip(k_ins - 1, 0, nd - 1)] == i)
            ins_idx = jnp.clip(k_ins - 1, 0, nd - 1)
        else:
            k_ins = jnp.zeros(self.n_pad, jnp.int32)
            is_ins = None
            ins_idx = None
        r = i - k_ins
        if nr:
            rem_adj = jnp.asarray(
                np.asarray(removed, np.int64) - np.arange(nr, dtype=np.int64)
            ).astype(jnp.int32)
            src = r + jnp.searchsorted(rem_adj, r, side="right").astype(jnp.int32)
        else:
            src = r
        src = jnp.clip(src, 0, max(old.n_pad - 1, 0))
        valid = i < self.n
        self.rows_uploaded = nd  # only the delta rows cross the link
        self.cols3 = {}
        for k in self.col_names:
            old_flat = old.cols3[k].reshape(-1)
            vals = jnp.take(old_flat, src)
            if is_ins is not None:
                dcol = np.asarray(delta_sorted_cols[k])
                dvals = jax.device_put(dcol, device) if device else jnp.asarray(dcol)
                vals = jnp.where(is_ins, jnp.take(dvals, ins_idx), vals)
            vals = jnp.where(valid, vals, _SENTINELS[k].astype(vals.dtype))
            self.cols3[k] = vals.reshape(self.n_blocks, self.sub, bk.LANES)

    # -- scanning --------------------------------------------------------
    def candidate_blocks(self, spans: RowSpans) -> np.ndarray:
        """Ascending ids of the scan blocks the spans touch."""
        if not len(spans):
            return np.zeros(0, np.int64)
        first = spans.lo // self.block
        last = (spans.hi - 1) // self.block
        ids = expand_runs(first, last - first + 1)
        # ascending spans: a block two spans share shows twice in a row
        keep = np.empty(len(ids), bool)
        keep[0] = True
        np.not_equal(ids[1:], ids[:-1], out=keep[1:])
        return ids[keep]

    def _dispatch_spans(self, config: ScanConfig) -> "ScanSpans":
        """:meth:`scan_spans` at a dispatch site (one that marks
        ``prune``): the caller's ``dispatch`` span counts in
        ``spans_reused`` how many of its configs found their spans."""
        spans, reused = self.scan_spans(config)
        _oadd("spans_reused", int(reused))
        return spans

    def scan(self, config: ScanConfig, deadline=None) -> tuple[np.ndarray, np.ndarray]:
        """One-call device scan. Returns (ordinals, certain):

        - ``ordinals``: feature ordinals of all candidate hits, ascending in
          table order (wide predicate — a superset of true hits only where
          ``certain`` is False);
        - ``certain``: per-row True when the row is a guaranteed f64-exact
          hit of the index's spatial/temporal constraint (inner predicate or
          contained range) — the planner refines only the rest.

        Both arrays are fresh at every submit and the caller's own: the
        indexed join orders a lone member's ``ordinals`` in place and hands
        them back as its answer (sql/join.py ``_assemble``).

        ``deadline``: optional ``time.monotonic()`` cutoff; the scan checks
        it at stage boundaries and raises QueryTimeout when overdue
        (reference ThreadManagement scan timeouts).
        """
        return self.scan_submit(config, deadline=deadline)()

    def scan_submit(self, config: ScanConfig, deadline=None):
        """Pipelined form of :meth:`scan`: dispatch the device work NOW,
        return a zero-arg ``finish()`` producing (ordinals, certain).

        jax dispatch is asynchronous — submitting several queries' kernels
        before pulling any result overlaps their device work and hides the
        per-pull link latency behind computation (DataStore.query_many).

        Under a caller's ``dispatch`` span it marks the segments
        ``prune`` (spans, candidate blocks, padding) and ``enqueue`` (the
        jitted call), and counts ``blocks`` (candidates), ``slots``
        (what the kernel's bucket pads them to) and ``spans_reused`` (1
        when the spans were the ones ``cost()`` left in the config's slot).
        """
        if config.disjoint or self.n == 0:
            return lambda: (np.zeros(0, np.int64), np.zeros(0, bool))
        check_deadline(deadline, "range pruning")
        _oevent("prune")
        spans = self._dispatch_spans(config)
        has_pred = config.boxes is not None or config.windows is not None

        if not has_pred:
            # pure range scan (attribute index primary): spans are row-exact
            rows = _span_rows(spans.union)
            out = (self.perm[rows].astype(np.int64), np.ones(len(rows), bool))
            return lambda: out

        blocks = self.candidate_blocks(spans.overlap)
        if len(blocks) == 0:
            return self._contained_only(spans)

        check_deadline(deadline, "device scan dispatch")
        return self._make_finish(
            self._device_scan_submit(blocks, config), config, spans, deadline
        )

    def _contained_only(self, spans: ScanSpans):
        """finish() of a scan with no block for the kernel: the contained
        spans' rows, all certain."""
        rows = _span_rows(spans.contained)
        out = (self.perm[rows].astype(np.int64), np.ones(len(rows), bool))
        return lambda: out

    def _make_finish(self, finish_device, config, spans, deadline):
        """finish() closure over a dispatched device scan: decode +
        _post_decode. Shared by scan_submit and scan_submit_many's
        single-member groups so the two can never drift."""

        def finish() -> tuple[np.ndarray, np.ndarray]:
            rows, certain = finish_device()
            check_deadline(deadline, "bitmask decode")
            return self._post_decode(rows, certain, config, spans)

        return finish

    def _post_decode(self, rows, certain, config, spans: ScanSpans):
        """Decoded kernel rows -> (feature ordinals, certain): span
        clipping, contained-span union (all certain; native two-pointer
        dedup when available), permutation to feature ordinals. Shared by
        the per-query and fused scan paths. Under a span a clipped scan
        counts ``clip_in`` and ``clip_kept``."""
        if config.clip_rows:
            keep = _rows_in_spans(rows, spans.union)
            # on the caller's ``scan`` span: the rows the kernel's blocks
            # hit, and those inside the value's row spans
            _oadd("clip_in", len(rows))
            rows, certain = rows[keep], certain[keep]
            _oadd("clip_kept", len(rows))
        contained = spans.contained
        if len(contained):
            from geomesa_tpu import native

            merged = native.merge_rows_spans(contained.lo, contained.hi, rows, certain)
            if merged is not None:
                rows, certain = merged
            else:
                dup = _rows_in_spans(rows, contained)
                rows, certain = _merge_sorted_rows(
                    _span_rows(contained), rows[~dup], certain[~dup]
                )
        return self.perm[rows].astype(np.int64), certain

    def scan_submit_many(self, configs: list, deadline=None):
        """Fused form of :meth:`scan_submit` for MANY queries (round 5):
        groups eligible configs by kernel variant and dispatches ONE fused
        kernel per chunk (`bk.block_scan_multi`, every chunk padded to the
        canonical FUSED_CHUNK_SLOTS x FUSED_CHUNK_Q shape) instead of one
        dispatch per query — slot i of the fused grid scans block bids[i]
        with query qids[i]'s params. Returns one
        ``finish() -> (ordinals, certain)`` PER config, in input order;
        a chunk's planes pull once (on its first member's finish) but each
        member decodes lazily, so callers that discard some results (kNN's
        speculative wide windows) never pay their decode.

        Per-query dispatch overhead (~2 ms submit + serialized kernel
        launches) dominated many-small-query workloads: the indexed
        spatial join's 256 per-polygon scans spent nearly all their time
        there, not in host refinement. Round 6 widened
        eligibility to EVERY kernel-backed config: polygon-INTERSECTS
        members fuse through the chunk's [Q, E, 128] edge stack (the
        device PIP tier, selected per slot), extent/XZ members fuse on
        their wide-only plane, and the distributed table dispatches the
        whole chunk under shard_map. Only pure range scans (row-exact, no
        kernel) and empty/disjoint configs fall back to
        :meth:`scan_submit` per query, still dispatched before any pull.

        This is the TPU shape of the reference's server-side batch scans
        (geomesa-utils/.../utils/AbstractBatchScan.scala threads one
        range per pooled scanner; geomesa-hbase/.../HBaseQueryPlan.scala:
        43-54 fans ranges over CachedThreadPool): instead of threads
        hiding per-range latency, one kernel grid scans every (query,
        block) slot and the host decodes per-query segments.
        """
        if not self._fused_supported():
            # subclass re-routes the device seam without providing its own
            # fused chunk dispatch: the fused kernel would bypass the seam
            # — keep per-query dispatches, still pipelined
            return [self.scan_submit(c, deadline=deadline) for c in configs]

        n_q = len(configs)
        _oevent("prune")
        finishes: list = [None] * n_q
        # groups: variant key -> [(j, config, bids_padded?, ...)]
        groups: dict[tuple, list] = {}
        for j, config in enumerate(configs):
            if config.disjoint or self.n == 0:
                out = (np.zeros(0, np.int64), np.zeros(0, bool))
                finishes[j] = lambda out=out: out
                continue
            check_deadline(deadline, "range pruning")
            has_pred = config.boxes is not None or config.windows is not None
            if not has_pred:
                # pure range scans (attribute-index primaries) keep the
                # per-query path: spans are row-exact, no kernel runs.
                # PIP-edge polygon configs FUSE (round 6): their chunks
                # carry a [Q, E, 128] edge stack and a per-slot selector,
                # grouped per E bucket so polygon batches share dispatches
                # without taxing box chunks with edge work
                finishes[j] = self.scan_submit(config, deadline=deadline)
                continue
            spans = self._dispatch_spans(config)
            blocks = self.candidate_blocks(spans.overlap)
            if len(blocks) == 0:
                finishes[j] = self._contained_only(spans)
                continue
            blocks = self._full_or(blocks)
            names = self._scan_cols(config)
            # the E and R buckets are part of the variant key: box
            # queries group at E = R = 0 (their slots keep the round-5
            # zero-edge kernel cost and the Pallas path), polygons group
            # per fused bucket — a 256-edge member must not inflate
            # every box slot to 256-edge PIP work, nor demote the chunk
            # past PALLAS_MAX_EDGES/RINTS to the XLA variant, just to
            # share one dispatch
            e_bucket = (
                0 if self.extent
                else bk.fused_e_bucket(bk.n_edges_of(config.poly))
            )
            r_bucket = (
                0 if self.extent
                else bk.fused_r_bucket(bk.n_rints_of(config.rast))
            )
            key = (
                names, config.boxes is not None, config.windows is not None,
                e_bucket, r_bucket,
            )
            groups.setdefault(key, []).append((j, config, blocks, spans))

        slots = self.fused_pack_capacity
        for (names, has_boxes, has_windows, _e, _r), group_members in groups.items():
            # pack members into fixed-shape chunks (fused_pack_capacity /
            # FUSED_CHUNK_Q — see the constants' doctrine note). Broad
            # members (> half a chunk, e.g. _full_or expansions) dispatch
            # alone on the single-query bucket ladder; the rest pack
            # greedily in input order.
            chunks: list[list] = []
            cur: list = []
            cur_blocks = 0
            for m in group_members:
                nb = len(m[2])
                if nb > slots // 2:
                    chunks.append([m])
                    continue
                if cur and (
                    cur_blocks + nb > slots
                    or len(cur) == FUSED_CHUNK_Q
                ):
                    chunks.append(cur)
                    cur, cur_blocks = [], 0
                cur.append(m)
                cur_blocks += nb
            if cur:
                chunks.append(cur)
            for members in chunks:
                self._submit_fused_chunk(
                    members, names, has_boxes, has_windows, finishes, deadline
                )

        return finishes

    def _fused_route_single(self, members, finishes, deadline) -> bool:
        """Route single-member / near-empty chunks to the plain
        single-query kernel (the fixed fused shape would waste most of
        its scan work on pads); returns True when routed. Shared by the
        single-device and distributed fused dispatches."""
        if len(members) == 1 or (
            # near-empty AND few members: past a handful of queries the
            # per-dispatch overhead (~2 ms each) outweighs scanning the
            # canonical shape's pad slots (~ms), so larger chunks always
            # fuse even when sparse
            len(members) <= 8
            and sum(len(m[2]) for m in members) < self.fused_pack_capacity // 8
        ):
            for j, config, blocks, spans in members:
                _oevent("prune")  # back from the last member's enqueue
                finishes[j] = self._make_finish(
                    self._device_scan_submit(blocks, config), config, spans, deadline
                )
            return True
        return False

    def _fused_param_stacks(self, members):
        """(boxes, wins) [FUSED_CHUNK_Q, 8, 128] per-query param stacks
        for one fused chunk — shared by the single-device and distributed
        dispatches so the packing can never drift."""
        boxes = np.zeros((FUSED_CHUNK_Q, 8, bk.LANES), np.float32)
        wins = np.zeros((FUSED_CHUNK_Q, 8, bk.LANES), np.int32)
        for q, m in enumerate(members):
            boxes[q], wins[q] = self._params(m[1])
        return boxes, wins

    @staticmethod
    def _fused_pull(wide, inner, members: int = 0):
        """Start the async device->host copies for a fused chunk's planes
        NOW (see _device_scan_submit on why) and return a memoized
        ``group_pull() -> (wide_h, inner_h)``: the chunk pulls ONCE, on
        its first member's finish, and members decode lazily. Shared by
        the single-device and distributed dispatches. The member whose
        finish makes the pull carries its ``wait`` and ``pull`` segments
        and ``group`` = ``members``; the others show none."""
        import jax

        for plane in (wide, inner):
            if plane is not None and hasattr(plane, "copy_to_host_async"):
                plane.copy_to_host_async()
        pulled: dict = {}

        def group_pull():
            if "planes" not in pulled:
                if _await_device((wide, inner)):
                    _oadd("group", members)
                wide_h, inner_h = jax.device_get((wide, inner))
                pulled["planes"] = (
                    np.asarray(wide_h),
                    None if inner_h is None else np.asarray(inner_h),
                )
            return pulled["planes"]

        return group_pull

    def _chunk_edge_stack(self, members):
        """(chunk_E, edges [FUSED_CHUNK_Q, chunk_E, 128] | None, pip [Q]
        bool) for one fused chunk: the per-query PIP edge stack, sized to
        the chunk's largest member polygon and zero-padded per query
        (pack_edges pad rows never cross and are never near). Extent
        tables ignore polygon edges in BOTH scan paths (bbox-intersects
        is the device test), so their chunks always ride E = 0."""
        pip = np.zeros(len(members), bool)
        if self.extent:
            return 0, None, pip
        chunk_e = bk.fused_e_bucket(
            max(bk.n_edges_of(m[1].poly) for m in members)
        )
        if chunk_e == 0:
            return 0, None, pip
        edges = np.zeros((FUSED_CHUNK_Q, chunk_e, bk.LANES), np.float32)
        for q, m in enumerate(members):
            poly = m[1].poly
            if poly is not None:
                edges[q, : poly.shape[0]] = poly
                pip[q] = True
        return chunk_e, edges, pip

    def _chunk_raster_stack(self, members):
        """(chunk_R, rasts [FUSED_CHUNK_Q, 1 + chunk_R, 128] | None,
        rast [Q] bool) for one fused chunk: the per-query raster-interval
        stack (RasterApprox.pack_block header + intervals), sized to the
        chunk's largest member raster and zero-padded per query (pad
        interval rows never match; an all-zero header classifies every
        row out-of-grid, and such slots never select the polygon leg).
        Extent tables ride R = 0 like they ride E = 0."""
        has = np.zeros(len(members), bool)
        if self.extent:
            return 0, None, has
        chunk_r = bk.fused_r_bucket(
            max(bk.n_rints_of(m[1].rast) for m in members)
        )
        if chunk_r == 0:
            return 0, None, has
        rasts = np.zeros((FUSED_CHUNK_Q, 1 + chunk_r, bk.LANES), np.float32)
        for q, m in enumerate(members):
            rast = m[1].rast
            if rast is not None:
                rasts[q, : rast.shape[0]] = rast
                has[q] = True
        return chunk_r, rasts, has

    def _submit_fused_chunk(
        self, members, names, has_boxes, has_windows, finishes, deadline
    ):
        """Dispatch one fused chunk (scan_submit_many): single-member or
        near-empty chunks take the plain single-query kernel; real
        batches share one block_scan_multi call — box AND polygon-PIP
        members together, selected per slot — and decode per-member slot
        segments."""
        slots = self.fused_slots
        if self._fused_route_single(members, finishes, deadline):
            return
        check_deadline(deadline, "device scan dispatch")
        _oevent("prune")  # back from the last chunk's enqueue
        boxes, wins = self._fused_param_stacks(members)
        chunk_e, edges, pip = self._chunk_edge_stack(members)
        chunk_r, rasts, has_rast = self._chunk_raster_stack(members)
        poly_slot = pip | has_rast
        bid_parts: list[np.ndarray] = []
        qid_parts: list[np.ndarray] = []
        segs: list[tuple[int, int]] = []  # slot segment per member
        pos = 0
        for q, (j, config, blocks, _) in enumerate(members):
            bid_parts.append(blocks.astype(np.int32))
            qid_parts.append(np.full(len(blocks), q, np.int32))
            segs.append((pos, pos + len(blocks)))
            pos += len(blocks)
        bids, n_real = bk.pad_bids(
            np.concatenate(bid_parts), self.n_blocks, bucket=slots
        )
        self._record_scan(names, len(bids))
        _oadd("blocks", n_real)
        _oadd("slots", len(bids))
        _oadd("groups", 1)
        qids = np.zeros(len(bids), np.int32)
        qids[:n_real] = np.concatenate(qid_parts)
        spip = None
        if chunk_e or chunk_r:
            spip = poly_slot[qids].astype(np.int32)
            spip[n_real:] = 0  # pad slots keep the (cheaper) box leg
        _oevent("enqueue")
        wide, inner = bk.block_scan_multi(
            self._cols_args(names), bids, qids, boxes, wins,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=self.extent, edges=edges, spip=spip, n_edges=chunk_e,
            rasts=rasts, n_rints=chunk_r,
        )
        group_pull = self._fused_pull(wide, inner, len(members))

        def member_finish(k):
            j, config, blocks, spans = members[k]
            s, e = segs[k]
            wide_h, inner_h = group_pull()
            _oevent("bits")
            check_deadline(deadline, "bitmask decode")
            rows, certain = bk.decode_bits_pair(
                np.ascontiguousarray(wide_h[s:e]),
                None if inner_h is None else np.ascontiguousarray(inner_h[s:e]),
                blocks, e - s,
            )
            return self._post_decode(rows, certain, config, spans)

        for k, (j, *_rest) in enumerate(members):
            finishes[j] = lambda k=k, f=member_finish: f(k)

    # -- device hooks ----------------------------------------------------
    def _params(self, config: ScanConfig):
        """(boxes, windows) packed [8, 128] kernel param blocks (wide +
        inner planes). Packed-time tables (the 1B layout) convert window
        offsets to device ticks first — floor-wide / shrink-inner, so
        tick-boundary rows refine on host like f32 box edges."""
        boxes = bk.pack_boxes(config.boxes, config.boxes_inner)
        shift = getattr(self.keyspace, "packed_time", None)
        if shift is not None and config.windows is not None:
            from geomesa_tpu.index.z3 import windows_to_ticks

            wide = bk.merge_window_slots(
                windows_to_ticks(config.windows, shift, inner=False),
                overflow="widen",
            )
            wi = config.windows_inner
            if wi is not None:
                wi = np.asarray(windows_to_ticks(wi, shift, inner=True))
                wi = wi[wi[:, 1] <= wi[:, 2]] if len(wi) else wi
            inner = (
                bk.merge_window_slots(wi, overflow="drop")
                if wi is not None and len(wi) else None
            )
            return boxes, bk.pack_windows(wide, inner)
        wins = bk.pack_windows(
            bk.merge_window_slots_wide(config), bk.merge_window_slots_inner(config)
        )
        return boxes, wins

    def _full_or(self, blocks: np.ndarray) -> np.ndarray:
        """Past the largest static M bucket, scan every block — one static
        shape per table instead of an unbounded bucket ladder. The caller's
        span counts ``full``: how often that shape was taken."""
        full = len(blocks) > bk.M_BUCKETS[-1]
        _oadd("full", int(full))
        if full:
            return np.arange(self.n_blocks, dtype=np.int64)
        return blocks

    # -- column projection (reference ColumnGroups, index/conf/
    # ColumnGroups.scala: scans fetch only the column families the query
    # needs; here a scan variant's BlockSpecs DMA only the projected
    # device columns — a time-only query ships no x/y blocks) ------------
    def _coord_cols(self) -> set:
        want = {"gxmin", "gymin", "gxmax", "gymax"} if self.extent else {"x", "y"}
        return want & set(self.col_names)

    def _scan_cols(self, config: ScanConfig) -> tuple:
        """Device columns this scan's predicate actually reads."""
        names: set = set()
        if config.boxes is not None:
            names |= self._coord_cols()
        if config.windows is not None:
            names |= {"tbin", "toff", "tw"} & set(self.col_names)
        if not names:
            # no predicate: one validity column (sentinel test in _masks)
            for v in ("x", "gxmin", "tw", "tbin"):
                if v in self.col_names:
                    names = {v}
                    break
        return tuple(sorted(names))

    def _agg_cols(self, config: ScanConfig) -> tuple:
        """Aggregations additionally read the representative coordinates."""
        return tuple(sorted(set(self._scan_cols(config)) | self._coord_cols()))

    def _kernel_kwargs(self, config: ScanConfig, names: tuple | None = None) -> dict:
        return dict(
            col_names=names if names is not None else self._scan_cols(config),
            has_boxes=config.boxes is not None,
            has_windows=config.windows is not None,
            extent=self.extent,
        )

    def _scan_kernel_kwargs(self, config: ScanConfig, names: tuple) -> dict:
        """Kernel kwargs for the SCAN path only: adds the device PIP and
        raster-interval tiers (aggregation kernels keep the box test —
        their wide-plane math cannot carry the near-band / boundary-cell
        uncertainty, so poly configs take the host aggregation path via
        mask_decides_filter)."""
        kw = self._kernel_kwargs(config, names)
        if config.poly is not None and not self.extent:
            kw["edges"] = config.poly
            kw["n_edges"] = bk.n_edges_of(config.poly)
        if config.rast is not None and not self.extent:
            kw["rast"] = config.rast
            kw["n_rints"] = bk.n_rints_of(config.rast)
        return kw

    def _cols_args(self, names: tuple) -> tuple:
        return tuple(self.cols3[k] for k in names)

    def _record_scan(self, names: tuple, n_blocks: int) -> None:
        """Projection accounting: what the last kernel call DMA'd."""
        self.last_scan_cols = names
        self.last_scan_bytes = sum(
            int(self.cols3[k].dtype.itemsize) for k in names
        ) * n_blocks * self.block

    def _device_scan(self, blocks: np.ndarray, config: ScanConfig):
        """Kernel call over candidate blocks -> (rows, certain)."""
        return self._device_scan_submit(blocks, config)()

    def _device_scan_submit(self, blocks: np.ndarray, config: ScanConfig):
        """Dispatch the scan kernel now; return finish() -> (rows, certain).
        The device-hook seam the distributed table overrides."""
        import jax

        blocks = self._full_or(blocks)
        bids, n_real = bk.pad_bids(blocks, self.n_blocks)
        boxes, wins = self._params(config)
        names = self._scan_cols(config)
        self._record_scan(names, len(bids))
        _oadd("blocks", n_real)
        _oadd("slots", len(bids))
        _oevent("enqueue")
        wide, inner = bk.block_scan(
            self._cols_args(names), bids, boxes, wins,
            **self._scan_kernel_kwargs(config, names),
        )
        # start the device->host copy as soon as the kernel finishes:
        # in-flight transfers overlap, where a blocking device_get pays
        # a full serialized roundtrip per query (PERF.md §4e) — this is
        # what makes query_many's pipelining actually pipeline
        for plane in (wide, inner):
            if plane is not None and hasattr(plane, "copy_to_host_async"):
                plane.copy_to_host_async()

        def finish():
            _await_device((wide, inner))
            # inner is None on extent box scans (skip_inner_plane): pull
            # and decode the wide plane only — half the per-query bytes
            wide_h, inner_h = jax.device_get((wide, inner))
            _oevent("bits")
            inner_h = None if inner_h is None else np.asarray(inner_h)
            return bk.decode_bits_pair(np.asarray(wide_h), inner_h, bids, n_real)

        return finish

    def _device_pops(self, blocks: np.ndarray, config: ScanConfig):
        """Per-candidate-block wide-hit counts -> (pops [n] i64, global
        block ids [n] i64). Pulls M ints, never bit planes."""
        import jax

        from geomesa_tpu.scan import aggregations

        blocks = self._full_or(blocks)
        bids, n_real = bk.pad_bids(blocks, self.n_blocks)
        boxes, wins = self._params(config)
        names = self._scan_cols(config)
        self._record_scan(names, len(bids))
        pops = aggregations.block_pops(
            self._cols_args(names), bids, boxes, wins,
            **self._kernel_kwargs(config, names),
        )
        pops = np.asarray(jax.device_get(pops))[:n_real].astype(np.int64)
        return pops, bids[:n_real].astype(np.int64)

    def _device_density_submit(self, blocks, config, grid_bounds, width, height):
        """Dispatch the density kernel now (host copy started async);
        return finish() -> [height, width] grid."""
        import jax

        from geomesa_tpu.scan import aggregations

        blocks = self._full_or(blocks)
        bids, n_real = bk.pad_bids(blocks, self.n_blocks, pad=-1)
        boxes, wins = self._params(config)
        names = self._agg_cols(config)
        self._record_scan(names, len(bids))
        _oadd("blocks", n_real)
        _oadd("slots", len(bids))
        _oevent("enqueue")
        grid, paths = aggregations.block_density(
            self._cols_args(names), bids, boxes, wins, grid_bounds,
            width=width, height=height, counts=True,
            **self._kernel_kwargs(config, names),
        )
        if hasattr(grid, "copy_to_host_async"):
            grid.copy_to_host_async()

        def finish():
            # the kernel's slot counts are the span's: no span, no pull
            if not _await_device(grid) or paths is None:
                return np.asarray(jax.device_get(grid))
            out, by_path = jax.device_get((grid, paths))
            for name, n in zip(("skipped", "windowed", "whole"), by_path):
                _oadd(name, int(n))
            return np.asarray(out)

        return finish

    def _device_bounds(self, blocks, config):
        """(count, envelope | None) over wide-predicate hits."""
        import jax

        from geomesa_tpu.scan import aggregations

        blocks = self._full_or(blocks)
        bids, n_real = bk.pad_bids(blocks, self.n_blocks, pad=-1)
        boxes, wins = self._params(config)
        names = self._agg_cols(config)
        self._record_scan(names, len(bids))
        stats = aggregations.block_bounds(
            self._cols_args(names), bids, boxes, wins,
            **self._kernel_kwargs(config, names),
        )
        return aggregations.reduce_bounds(jax.device_get(stats), n_real)

    # -- counting --------------------------------------------------------
    def count(self, config: ScanConfig) -> int:
        """Wide-predicate hit count (superset semantics where the config is
        imprecise; exact counting goes through scan + refinement).

        Avoids materializing row ids: contained spans count by length,
        other candidate blocks count by device-side popcount of their wide
        bit plane; only blocks *straddling* a contained span (which would
        double-count its rows) are decoded."""
        if config.disjoint or self.n == 0:
            return 0
        spans = self.scan_spans(config)[0]
        overlap, contained = spans.overlap, spans.contained
        cont_total = contained.n_rows()
        has_pred = config.boxes is not None or config.windows is not None
        if not has_pred:
            return cont_total + overlap.n_rows()
        if config.clip_rows:  # span-exact clipping needs the rows
            rows, _ = self.scan(config)
            return len(rows)
        blocks = self.candidate_blocks(overlap)
        if len(blocks) == 0:
            return cont_total
        pops, gbids = self._device_pops(blocks, config)
        if not len(contained):
            return int(pops.sum())
        straddle = _spans_intersect(
            gbids * self.block, (gbids + 1) * self.block, contained
        )
        total = int(pops[~straddle].sum()) + cont_total
        if straddle.any():
            rows, _ = self._device_scan(gbids[straddle], config)
            total += int((~_rows_in_spans(rows, contained)).sum())
        return total

    # -- aggregation push-down -------------------------------------------
    def _agg_blocks(self, config: ScanConfig) -> np.ndarray:
        """Candidate blocks over ALL scan ranges (contained rows pass the
        wide predicate, so aggregations just run the kernel over them)."""
        return self.candidate_blocks(self.candidate_spans(config))

    def bounds_stats(self, config: ScanConfig):
        """(count, (xmin, ymin, xmax, ymax)) of matching rows on device (the
        StatsScan Count/MinMax(geom) fast path; loose f32 semantics)."""
        if config.disjoint or self.n == 0:
            return 0, None
        blocks = self._agg_blocks(config)
        if len(blocks) == 0:
            return 0, None
        return self._device_bounds(blocks, config)

    def density(self, config: ScanConfig, bounds, width: int, height: int) -> np.ndarray:
        """[height, width] density grid over ``bounds`` computed on device
        (the DensityScan push-down tier; see geomesa_tpu.scan.aggregations)."""
        return self.density_submit(config, bounds, width, height)()

    def density_submit(self, config: ScanConfig, bounds, width: int, height: int):
        """Pipelined form of :meth:`density`: dispatch the grid kernel now,
        return finish() -> grid. A batch of map tiles submits every tile's
        kernel before pulling any grid (DataStore.density_many)."""
        if config.disjoint or self.n == 0:
            return lambda: np.zeros((height, width), dtype=np.float32)
        _oevent("prune")
        blocks = self.candidate_blocks(self._dispatch_spans(config).union)
        if len(blocks) == 0:
            return lambda: np.zeros((height, width), dtype=np.float32)
        gb = np.asarray(bounds, dtype=np.float32).reshape(4)
        return self._device_density_submit(blocks, config, gb, width, height)

    # -- warmup ----------------------------------------------------------
    def warmup(self) -> int:
        """Pre-compile the scan-kernel variants this table can hit, so the
        first real query never pays the (potentially tens-of-seconds) XLA
        compile. Variants are keyed by (M bucket, projected columns,
        predicate flags); this drives the shared device hook
        (``_device_scan_submit`` — so the distributed table warms its
        shard_map variants too) once per ladder bucket up to the table
        size, for the table's natural flag combinations — plus the one
        canonical fused multi-query shape per flag combo
        (scan_submit_many's fixed FUSED_CHUNK_SLOTS/FUSED_CHUNK_Q chunk).
        Returns the number of kernel calls issued."""
        if self.n == 0:
            return 0
        # every ladder bucket at or below n_blocks, PLUS the bucket that
        # n_blocks itself pads into (a query touching between the largest
        # whole bucket and n_blocks compiles that one), plus the full-scan
        # shape past the ladder
        sizes = sorted({
            *(m for m in bk.M_BUCKETS if m <= self.n_blocks),
            min(bk.bucket_of(self.n_blocks), max(self.n_blocks, bk.M_BUCKETS[0])),
        })
        if self.n_blocks > bk.M_BUCKETS[-1]:
            sizes.append(bk.M_BUCKETS[-1] + 1)  # triggers the full-scan shape
        has_windows = bool({"tbin", "tw"} & set(self.col_names))
        # (False, False) is the attribute-only / no-predicate variant
        # (validity-column projection) — real queries hit it too
        flag_combos = [(True, False), (False, False)]
        if has_windows:
            flag_combos = [(True, True), (True, False), (False, True), (False, False)]
        def make_cfg(has_boxes: bool, has_w: bool) -> ScanConfig:
            return ScanConfig(
                index="warmup",
                range_bins=np.zeros(1, np.int32),
                range_lo=np.zeros(1, np.uint64),
                range_hi=np.zeros(1, np.uint64),
                boxes=np.array([[0.0, 0.0, 1e-6, 1e-6]], np.float32)
                if has_boxes else None,
                windows=np.array([[0, 0, 0]], np.int32) if has_w else None,
            )

        calls = 0
        for m in sizes:
            blocks = np.arange(min(m, self.n_blocks), dtype=np.int64)
            for has_boxes, has_w in flag_combos:
                self._device_scan_submit(blocks, make_cfg(has_boxes, has_w))()
                calls += 1
        # the canonical fused multi-query variants (scan_submit_many):
        # fixed (fused_slots, FUSED_CHUNK_Q) shape means ONE compile per
        # (predicate-flag combo, E bucket, R bucket) covers every future
        # batch. E = R = 0 is the box-only chunk; point tables
        # additionally warm the PIP-fused E ladder and the
        # raster-interval R ladder (polygon members always carry a bbox,
        # so only has_boxes combos can hit them). Mixed E x R shapes
        # (the non-default device-residue mode) compile on first use.
        if self._fused_supported():
            pip_ok = not self.extent and {"x", "y"} <= set(self.col_names)
            for has_boxes, has_w in flag_combos:
                if not (has_boxes or has_w):
                    continue  # fused path requires a predicate
                e_ladder = [(0, 0)] + (
                    [(e, 0) for e in bk.FUSED_E_BUCKETS]
                    + [(0, r) for r in bk.FUSED_R_BUCKETS]
                    if (pip_ok and has_boxes) else []
                )
                for n_e, n_r in e_ladder:
                    cfg = make_cfg(has_boxes, has_w)
                    if n_e:
                        cfg.poly = np.zeros((n_e, bk.LANES), np.float32)
                    if n_r:
                        cfg.rast = np.zeros((1 + n_r, bk.LANES), np.float32)
                        cfg.rast[1:, 0] = 1.0  # pad intervals never match
                    names = self._scan_cols(cfg)
                    # half a chunk of round-robin blocks per member:
                    # enough real slots to clear the small-batch routing
                    # threshold (and to touch every mesh device), same
                    # compile key as any future fused dispatch
                    blk = (
                        np.arange(max(self.fused_pack_capacity // 4, 1))
                        % self.n_blocks
                    ).astype(np.int64)
                    fused_fins: list = [None, None]
                    none = ScanSpans(NO_SPANS, NO_SPANS)
                    self._submit_fused_chunk(
                        [(0, cfg, blk, none), (1, cfg, blk, none)],
                        names, has_boxes, has_w, fused_fins, None,
                    )
                    for f in fused_fins:
                        f()
                    calls += 1
        return calls

    @property
    def nbytes_device(self) -> int:
        return sum(int(v.nbytes) for v in self.cols3.values())


def folded_table(
    old: IndexTable,
    merged_keys: WriteKeys,
    keep_ordinal: "np.ndarray | None",
    ordinal_map: "np.ndarray | None",
    delta_keys: WriteKeys,
    delta_perm: "np.ndarray | None" = None,
    tile: int | None = None,
) -> IndexTable:
    """Incremental replace-merge: fold a delete + insert batch into a
    sorted table WITHOUT the whole-table radix sort (the streaming
    hot->cold merge; docs/streaming.md). :func:`merged_table` handles
    pure appends; an upsert flush also *removes* the replaced rows'
    keys, which round 8 and earlier paid for with a full recompaction
    (``_main_rows = 0`` -> re-sort + re-upload the entire table per
    flush). Here:

    - survivors keep their relative sorted order (dropping rows from a
      sorted sequence preserves sortedness), so no survivor re-sorts;
    - the delta radix-sorts alone (or arrives pre-sorted from the
      stream flusher's shard-sort stage as ``delta_perm``) and two-run
      merges into the survivor order with ``side='right'`` ties — new
      rows land AFTER equal-key survivors, exactly where the stable
      whole-table sort of ``concat(survivors, delta)`` puts them, so
      the result is bit-identical to a full recompaction (the
      differential matrix in tests/test_streaming_tier.py pins
      ``perm``/``bins``/``zs`` and every device column);
    - device blocks before the first touched sorted row are reused
      as-is (the ``reuse`` seam ``_stream_cols`` already honors), so
      the re-uploaded bytes scale with the flush's key locality, not N.

    ``merged_keys`` must be ``concat(masked old keys, delta_keys)`` in
    ordinal order; ``keep_ordinal`` is the survivor mask over OLD
    feature ordinals (None = nothing deleted) and ``ordinal_map`` maps
    old ordinals to post-delete ordinals (None when nothing deleted).
    Tables with a secondary sort word rebuild outright, like
    :func:`merged_table`.
    """
    nd = len(delta_keys.zs)
    if old.n == 0 or merged_keys.sub is not None:
        return IndexTable(old.keyspace, merged_keys, tile=tile)

    from geomesa_tpu import native

    if keep_ordinal is None:
        keep_sorted = None
        nm = old.n
        sbins, szs = old.bins, old.zs
        sperm = np.asarray(old.perm, dtype=np.int64)
        first_del = old.n
    else:
        # survivor mask in SORTED order: a sorted row survives when its
        # feature ordinal does
        keep_sorted = keep_ordinal[np.asarray(old.perm, dtype=np.int64)]
        nm = int(keep_sorted.sum())
        if nm == 0:
            return IndexTable(old.keyspace, merged_keys, tile=tile)
        sbins = old.bins[keep_sorted]
        szs = old.zs[keep_sorted]
        sperm = ordinal_map[np.asarray(old.perm, dtype=np.int64)[keep_sorted]]
        first_del = int(np.argmax(~keep_sorted)) if not keep_sorted.all() else old.n

    if nd == 0:
        perm = sperm
        first_change = first_del
    else:
        if delta_perm is not None and len(delta_perm) == nd:
            dperm = np.asarray(delta_perm, dtype=np.int64)
        else:
            dperm = native.sort_bins_z(delta_keys.bins, delta_keys.zs)
            if dperm is None:
                dperm = np.lexsort((delta_keys.zs, delta_keys.bins))
            dperm = np.asarray(dperm, dtype=np.int64)
        db = delta_keys.bins[dperm]
        dz = delta_keys.zs[dperm]

        # per-bin survivor segments for the insertion searchsorted
        subins, sstarts = np.unique(sbins, return_index=True)
        sstarts = np.append(sstarts, nm).astype(np.int64)
        pos = np.empty(nd, np.int64)
        for b in np.unique(db):
            i = int(np.searchsorted(subins, b))
            if i < len(subins) and subins[i] == b:
                s, e = int(sstarts[i]), int(sstarts[i + 1])
            else:
                s = e = int(sstarts[i]) if i < len(sstarts) else nm
            sel = db == b
            # side='right': delta rows land AFTER equal-key survivors —
            # the stable concat-sort tie order (survivors hold lower
            # ordinals in merged_keys)
            pos[sel] = np.searchsorted(szs[s:e], dz[sel], side="right") + s

        main_dest = np.arange(nm, dtype=np.int64) + np.searchsorted(
            pos, np.arange(nm, dtype=np.int64), side="right"
        )
        delta_dest = pos + np.arange(nd, dtype=np.int64)
        perm = np.empty(nm + nd, dtype=np.int64)
        perm[main_dest] = sperm
        perm[delta_dest] = nm + dperm
        first_change = min(first_del, int(pos.min()))
    if len(perm) < 2**32:
        perm = perm.astype(np.uint32)  # keep the native take() fast path

    fold_plan = None
    if _device_fold_enabled() and getattr(old, "cols3", None) is not None:
        removed = (
            np.flatnonzero(~keep_sorted) if keep_sorted is not None
            else np.zeros(0, np.int64)
        )
        if (
            old.block == _block_rows(tile)
            and set(old.col_names) == set(merged_keys.device_cols)
            and max(old.n_pad, nm + nd) < 2**31  # i32 position math
        ):
            delta_sorted_cols = (
                {k: v[dperm] for k, v in delta_keys.device_cols.items()}
                if nd else {}
            )
            dest = delta_dest if nd else np.zeros(0, np.int64)
            fold_plan = (old, removed, dest, delta_sorted_cols)

    table = IndexTable(
        old.keyspace, merged_keys, tile=tile,
        sorted_state=perm, reuse=(old, first_change), fold_plan=fold_plan,
    )
    table.rows_sorted = nd
    return table


def merged_table(
    old: IndexTable, merged_keys: WriteKeys, delta_keys: WriteKeys, tile: int | None = None
) -> IndexTable:
    """Merge-based minor compaction (the TimePartition analogue, reference
    index/conf/partition/TimePartition.scala): because the table is sorted
    by (bin, z), time partitions are CONTIGUOUS SEGMENTS of the sorted
    order — so folding a delta in needs no global re-sort, only a radix
    sort of the delta itself plus a positional merge, and every device
    block before the first insertion point is reused as-is. For the
    streaming steady state (recent-time appends land in the last bins) the
    re-sorted + re-uploaded data is proportional to the delta's time
    locality, not to N (VERDICT r3 #4: round-3 compaction concatenated and
    radix-re-sorted the entire table on every minor compaction).

    ``merged_keys`` must be ``concat(old keys, delta_keys)`` in ordinal
    order: delta feature ordinals follow the old table's.
    """
    nm, nd = old.n, len(delta_keys.zs)
    if nm == 0 or nd == 0 or merged_keys.sub is not None:
        # tables with a secondary sort word (string attribute indexes)
        # rebuild outright: the positional merge below compares (bin, z)
        # only, which would interleave z-tie runs out of sub order and
        # break the boundary-run narrowing in candidate_spans
        return IndexTable(old.keyspace, merged_keys, tile=tile)

    from geomesa_tpu import native

    dperm = native.sort_bins_z(delta_keys.bins, delta_keys.zs)
    if dperm is None:
        dperm = np.lexsort((delta_keys.zs, delta_keys.bins))
    db = delta_keys.bins[dperm]
    dz = delta_keys.zs[dperm]

    # insertion position in the old sorted order for every delta row,
    # resolved per bin segment (lexicographic (bin, z) searchsorted)
    pos = np.empty(nd, np.int64)
    for b in np.unique(db):
        i = int(np.searchsorted(old.ubins, b))
        if i < len(old.ubins) and old.ubins[i] == b:
            s, e = int(old.bin_starts[i]), int(old.bin_starts[i + 1])
        else:
            # bin absent from the old table: insert at the segment boundary
            s = e = int(old.bin_starts[i]) if i < len(old.bin_starts) else nm
        sel = db == b
        pos[sel] = np.searchsorted(old.zs[s:e], dz[sel], side="left") + s

    # classic stable two-run merge by destination index
    main_dest = np.arange(nm, dtype=np.int64) + np.searchsorted(
        pos, np.arange(nm, dtype=np.int64), side="right"
    )
    delta_dest = pos + np.arange(nd, dtype=np.int64)
    perm = np.empty(nm + nd, dtype=np.int64)
    perm[main_dest] = np.asarray(old.perm, dtype=np.int64)
    perm[delta_dest] = nm + np.asarray(dperm, dtype=np.int64)
    if nm + nd < 2**32:
        perm = perm.astype(np.uint32)  # keep the native take() fast path

    table = IndexTable(
        old.keyspace, merged_keys, tile=tile,
        sorted_state=perm, reuse=(old, int(pos.min())),
    )
    table.rows_sorted = nd
    return table
