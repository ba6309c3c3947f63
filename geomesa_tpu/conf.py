"""Typed system properties: the GeoMesaSystemProperties analogue.

Reference: /root/reference/geomesa-utils-parent/geomesa-utils/src/main/
scala/org/locationtech/geomesa/utils/conf/GeoMesaSystemProperties.scala —
typed ``SystemProperty`` objects with defaults, resolved from JVM system
properties (e.g. ``geomesa.scan.ranges.target`` in index/conf/
QueryProperties.scala, read at Z3IndexKeySpace.scala:170). Here each
property resolves, in order: programmatic override (``prop.set``) ->
environment variable -> default. The other two config tiers are per-query
QueryHints (planning/hints.py) and per-schema SFT user_data (sft.py),
mirroring the reference's three-tier layout.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

REGISTRY: dict[str, "SystemProperty"] = {}


def _parse_bool(s) -> bool:
    if isinstance(s, bool):
        return s  # programmatic prop.set(True/False)
    return str(s).strip().lower() in ("1", "true", "yes", "on")


@dataclass
class SystemProperty:
    """One typed, overridable configuration knob."""

    name: str  # dotted name, e.g. "geomesa.scan.ranges.target"
    default: object
    parser: Callable = int
    doc: str = ""
    _override: Optional[object] = field(default=None, repr=False)

    def __post_init__(self):
        REGISTRY[self.name] = self

    @property
    def env_key(self) -> str:
        return self.name.upper().replace(".", "_")

    def get(self):
        import os

        if self._override is not None:
            return self._override
        raw = os.environ.get(self.env_key)
        if raw is not None:
            try:
                return self.parser(raw)
            except (TypeError, ValueError):
                return self.default
        return self.default

    def set(self, value) -> None:
        """Programmatic override (takes precedence over the environment);
        ``clear()`` restores resolution."""
        self._override = None if value is None else self.parser(value)

    def clear(self) -> None:
        self._override = None


# -- the knobs (reference QueryProperties / index defaults) ---------------

SCAN_RANGES_TARGET = SystemProperty(
    "geomesa.scan.ranges.target", 2000, int,
    "max covering z-ranges per query (reference QueryProperties.ScanRangesTarget)",
)
COMPACT_MIN_ROWS = SystemProperty(
    "geomesa.tpu.compact.min.rows", 262_144, int,
    "delta rows before a minor compaction merges into the device table",
)
COMPACT_SPAN_ROWS = SystemProperty(
    "geomesa.tpu.compact.span.rows", 4_194_304, int,
    "bounded-buffer rows per gather span when a compaction streams sorted "
    "columns to the device (block-aligned; caps host scratch instead of "
    "materializing the whole sorted column set)",
)
DENSITY_VMEM_BUDGET = SystemProperty(
    "geomesa.tpu.density.vmem.budget", 10 << 20, int,
    "VMEM byte budget for the Pallas density histogram kernel",
)
QUERY_TIMEOUT = SystemProperty(
    "geomesa.query.timeout", None, float,
    "default per-query wall-clock budget in seconds (None = unbounded)",
)
GUARD_TEMPORAL_MAX = SystemProperty(
    "geomesa.guard.temporal.max.duration", 7 * 86_400_000, int,
    "ms cap on a query's temporal span for TemporalQueryGuard."
    "from_properties() (reference TemporalQueryGuard's property of the "
    "same name; default one week)",
)
PALLAS_MODE = SystemProperty(
    "geomesa.tpu.pallas", None, str,
    "force the kernel backend: '1' = Pallas (interpret off-TPU), '0' = XLA",
)

# -- query/aggregation cache tier (geomesa_tpu.cache; docs/caching.md) ----

CACHE_MAX_BYTES = SystemProperty(
    "geomesa.cache.result.max.bytes", 256 << 20, int,
    "LRU byte budget for cached query results (0 disables the result cache)",
)
CACHE_TTL = SystemProperty(
    "geomesa.cache.ttl", None, float,
    "seconds a cached entry stays servable (None = until invalidated)",
)
CACHE_TTL_JITTER = SystemProperty(
    "geomesa.cache.ttl.jitter", 0.0, float,
    "deterministic per-key TTL spread as a fraction of the TTL (0..1): a "
    "burst of same-TTL entries admitted together expires staggered "
    "instead of stampeding the store in lockstep (0 = exact TTLs)",
)
CACHE_MIN_COST = SystemProperty(
    "geomesa.cache.min.cost", 0.0, float,
    "cost-aware admission: cache only results whose measured scan took at "
    "least this many seconds (0 = admit everything)",
)
CACHE_TILE_BITS = SystemProperty(
    "geomesa.cache.tile.bits", 6, int,
    "tile-aggregate cache resolution: the world splits into 2^bits x "
    "2^bits SFC-aligned tiles whose partial aggregates are memoized",
)
CACHE_TILE_MAX = SystemProperty(
    "geomesa.cache.tile.max.entries", 65_536, int,
    "max resident tile aggregates before LRU eviction (0 disables the "
    "tile cache)",
)
CACHE_TILES_PER_QUERY = SystemProperty(
    "geomesa.cache.tile.max.per.query", 1024, int,
    "bbox queries spanning more interior tiles than this skip tile "
    "composition (the per-tile bookkeeping would beat the scan)",
)


# -- pipelined multi-core ingest (geomesa_tpu.ingest; docs/ingest.md) -----

INGEST_WORKERS = SystemProperty(
    "geomesa.ingest.workers", 0, int,
    "worker count for the pipelined ingest's parse/key/sort stages "
    "(0 = one per host core)",
)
INGEST_QUEUE_DEPTH = SystemProperty(
    "geomesa.ingest.queue.depth", 4, int,
    "bounded admission window: chunks a producer may stage ahead of the "
    "ordered writer before put() blocks; overflow waits are counted by "
    "the geomesa.ingest.queue_full metric",
)
INGEST_CHUNK_ROWS = SystemProperty(
    "geomesa.ingest.chunk.rows", 1 << 20, int,
    "fixed-size sort shard rows: each chunk's (bin, z) keys radix-sort in "
    "shards of this many rows, in parallel, merged spanwise at finalize",
)
INGEST_MERGE_MIN_BINS = SystemProperty(
    "geomesa.ingest.merge.min.bins", 2, int,
    "distinct sort bins below which the ingest finalize falls back to the "
    "whole-table LSD radix sort (the PERF.md 4f negative result: spanwise "
    "merging has nothing to parallelize over few bins)",
)


# -- multi-host pod tier (geomesa_tpu.pod; docs/distributed.md) -----------

POD_HOSTS = SystemProperty(
    "geomesa.pod.hosts", 0, int,
    "host-group size H for the pod tier (0 = one host per jax process "
    "under the distributed driver, else one simulated host per local "
    "device slice)",
)
POD_DEVICES_PER_HOST = SystemProperty(
    "geomesa.pod.devices.per.host", 0, int,
    "devices each host contributes to its shard mesh (0 = divide the "
    "visible devices evenly over the hosts)",
)
POD_DRIVER = SystemProperty(
    "geomesa.pod.driver", "auto", str,
    "host-group driver: 'distributed' (real jax.distributed processes), "
    "'sim' (in-process per-host device slices), or 'auto' (distributed "
    "when launched under a multi-process jax runtime, else sim)",
)


# -- raster-interval polygon approximations + adaptive spatial joins
# (geomesa_tpu.filter.raster, sql/join.py; docs/joins.md) ------------------

RASTER_ENABLED = SystemProperty(
    "geomesa.raster.enabled", True, _parse_bool,
    "precompute raster-interval approximations (arXiv 2307.01716) for "
    "polygon queries: full/out cells resolve by integer interval checks, "
    "exact PIP runs only on the boundary residue",
)
RASTER_MAX_CELLS = SystemProperty(
    "geomesa.raster.max.cells", 16384, int,
    "cell budget of one polygon's Z2-aligned raster grid (the level is "
    "the finest whose bbox window fits this many cells)",
)
RASTER_MIN_EDGES = SystemProperty(
    "geomesa.raster.min.edges", 8, int,
    "polygons with fewer edges than this skip rasterization (the exact "
    "device PIP tier is already cheap at tiny edge counts)",
)
RASTER_KERNEL_INTERVALS = SystemProperty(
    "geomesa.raster.kernel.intervals", 16, int,
    "cap on the per-query interval count shipped to the scan kernel "
    "(coalesced conservatively past it): the raster-derived z-ranges "
    "already prune at full resolution host-side, so a coarse in-kernel "
    "stack trades a slightly wider residue for a much cheaper kernel leg",
)
RASTER_RESIDUE = SystemProperty(
    "geomesa.raster.residue", "host", str,
    "where the boundary-cell residue runs its exact even-odd PIP: 'host' "
    "(f64, threaded native ray cast — the fast default) or 'device' (the "
    "kernel's f32 _pip_unrolled/_pip_loop tier, masks bit-identical to "
    "the pre-raster path)",
)
JOIN_ADAPTIVE = SystemProperty(
    "geomesa.join.adaptive", True, _parse_bool,
    "pick the spatial-join strategy per partition from measured "
    "selectivity (arXiv 1802.09488) instead of one fixed plan",
)
JOIN_SAMPLE = SystemProperty(
    "geomesa.join.sample", 512, int,
    "candidate rows sampled per join partition to measure boundary-cell "
    "selectivity before picking a strategy",
)
JOIN_BROAD_FRACTION = SystemProperty(
    "geomesa.join.broad.fraction", 0.25, float,
    "indexed-join polygons whose candidate spans cover more than this "
    "fraction of the table skip the fused-scan probe and classify the "
    "whole point set against their raster on host",
)
JOIN_IN_SELECTIVITY = SystemProperty(
    "geomesa.join.in.selectivity", 0.5, float,
    "attribute-join IN push-down is skipped (host membership mask "
    "instead) when the sampled fraction of matching secondary rows "
    "exceeds this — the scan would return most rows anyway",
)


# -- production streaming tier (geomesa_tpu.streaming; docs/streaming.md) -

STREAM_WORKERS = SystemProperty(
    "geomesa.stream.workers", 0, int,
    "worker count for the stream flusher's parse/key/shard-sort stages "
    "(0 = one per host core); the pool stays warm across flushes",
)
STREAM_CHUNK_ROWS = SystemProperty(
    "geomesa.stream.chunk.rows", 65_536, int,
    "rows per flush micro-chunk: the hot snapshot stages through the "
    "warm workers in chunks of this many rows (also the shard size of "
    "the per-chunk radix sorts)",
)
STREAM_QUEUE_DEPTH = SystemProperty(
    "geomesa.stream.queue.depth", 4, int,
    "bounded admission window: flush micro-chunks queued in the worker "
    "pool at once before staging blocks (bounds the parse stage's "
    "double-buffering; fully-staged chunks are held until the atomic "
    "publish); overflow waits are counted by the "
    "geomesa.stream.queue_full metric",
)
STREAM_FOLD_ROWS = SystemProperty(
    "geomesa.stream.fold.rows", 131_072, int,
    "pending UPDATE rows before a micro-batch flush folds them into the "
    "cold tables (the amortized hot->cold merge): below it, updated ids "
    "stay resident in the hot overlay — reads remain exact through the "
    "hot-wins-by-id merge — so the steady-state flush pays O(batch) for "
    "appends instead of O(table) per flush; a full persist "
    "(persist_hot/checkpoint) always folds everything",
)
STREAM_FOLD_SLICE_ROWS = SystemProperty(
    "geomesa.stream.fold.slice.rows", 65_536, int,
    "update-fold slice size: a fold batch larger than this splits into "
    "bounded key-contiguous slices, each published atomically on its own "
    "(readers see exact intermediate states; the scheduler's admission "
    "window drains between slices), so the fold stops being one "
    "O(table) stop-the-world pause; 0 folds monolithically",
)
STREAM_FOLD_YIELD_MS = SystemProperty(
    "geomesa.stream.fold.yield.ms", 15.0, float,
    "cap on the between-slice scheduler yield: after each published fold "
    "slice the folding thread waits up to this long for the cold store's "
    "QueryScheduler admission queue to drain (live dashboard queries "
    "interleave instead of queueing behind the whole fold); an idle "
    "queue returns immediately",
)
STREAM_FOLD_PRESTAGE = SystemProperty(
    "geomesa.stream.fold.prestage", True, _parse_bool,
    "parse/key/shard-sort pending update rows through the warm flush "
    "workers AT MICRO-FLUSH TIME (as the updates arrive), so the "
    "eventual fold window pays only merge+publish; rows re-updated "
    "after staging re-stage at fold time. False defers all staging to "
    "the fold (the round-9 behavior)",
)
STREAM_FOLD_DEVICE = SystemProperty(
    "geomesa.stream.fold.device", "auto", str,
    "device-side fold plan: 'auto'/'on' rebuilds a folded index table's "
    "device columns ON DEVICE from the old table plus an O(touched) "
    "upload (removed positions, insert positions, the slice's sorted "
    "rows) instead of re-gathering and re-uploading the O(table) "
    "suffix over the link; 'off' keeps the host gather + suffix upload "
    "(the round-9 path, and the fallback whenever the plan is "
    "ineligible)",
)
STREAM_WAL_SYNC = SystemProperty(
    "geomesa.stream.wal.sync", "always", str,
    "streaming WAL fsync policy (docs/durability.md): 'always' = every "
    "acknowledged write is fsync'd first (group-committed, zero "
    "acknowledged-row loss on kill -9), 'interval' = fsync at most every "
    "geomesa.stream.wal.sync.interval.ms (bounded loss window), 'off' = "
    "never fsync (redo-from-checkpoint workloads / bench baseline)",
)
STREAM_WAL_SYNC_INTERVAL_MS = SystemProperty(
    "geomesa.stream.wal.sync.interval.ms", 50.0, float,
    "fsync cadence under geomesa.stream.wal.sync=interval: a hard kill "
    "loses at most the writes acknowledged since the last sync",
)
STREAM_WAL_SEGMENT_BYTES = SystemProperty(
    "geomesa.stream.wal.segment.bytes", 64 << 20, int,
    "streaming WAL segment size: the active log rotates past this many "
    "bytes; sealed segments retire only once a checkpoint watermark "
    "covers them (LambdaStore.checkpoint — the durable cold publish)",
)
STREAM_WAL_REPLAY_BATCH = SystemProperty(
    "geomesa.stream.wal.replay.batch.rows", 262_144, int,
    "recovery-side replay batching: contiguous WAL upsert records "
    "coalesce into one bulk hot-tier apply of up to this many rows "
    "(single lock hold, vectorized grid-index insert) instead of one "
    "apply per record — recovery is single-threaded, so the live tier's "
    "reader-interleaving lock chunking buys nothing there; 0 replays "
    "record-at-a-time (the round-10 behavior)",
)
STREAM_INCREMENTAL = SystemProperty(
    "geomesa.stream.incremental", True, _parse_bool,
    "fold flushes into the cold tables incrementally "
    "(DataStore.fold_upsert: no whole-table re-sort, scoped cache "
    "invalidation); False = the legacy delete-and-rewrite upsert flush "
    "(the pre-round-9 path, kept as the bench baseline and the escape "
    "hatch for custom adapters without the fold_table seam)",
)


# -- replication: WAL shipping, read replicas, failover
# (geomesa_tpu.streaming.replica; docs/replication.md) ---------------------

REPLICA_SHIP_CHUNK_BYTES = SystemProperty(
    "geomesa.replica.ship.chunk.bytes", 256 << 10, int,
    "SegmentShipper transfer granularity: WAL segment bytes stream to "
    "followers in frames of at most this many payload bytes (each "
    "length-prefixed + checksummed), so one huge sealed segment never "
    "monopolizes the transport between staleness marks",
)
REPLICA_SHIP_INTERVAL_MS = SystemProperty(
    "geomesa.replica.ship.interval.ms", 25.0, float,
    "SegmentShipper pump cadence: every tick ships newly durable WAL "
    "bytes to each follower and broadcasts a staleness mark (the "
    "leader's applied horizon + wall clock) — the floor of follower "
    "staleness under an idle leader",
)
REPLICA_STALENESS_MAX_MS = SystemProperty(
    "geomesa.replica.staleness.max.ms", 5000.0, float,
    "follower health threshold: a ReplicaStore whose measured staleness "
    "watermark exceeds this degrades /health with a replica.staleness "
    "reason (docs/replication.md); 0 disables the check",
)
REPLICA_GIVEUP_S = SystemProperty(
    "geomesa.replica.giveup.s", 10.0, float,
    "SegmentShipper retry budget per pump, in seconds (fault."
    "with_retries max_elapsed_s): past it the shipper stops retrying "
    "that follower for the tick and trips the replica.ship.giveup "
    "/health reason instead of spinning in backoff forever",
)


# -- observability: tracing / slow-query log / SLOs (geomesa_tpu.obs;
# docs/observability.md) ---------------------------------------------------

OBS_TRACE_SAMPLE = SystemProperty(
    "geomesa.obs.trace.sample", 0, int,
    "structured-tracing sample rate: 0 disarms tracing entirely (span "
    "entry is a no-op thread-local check), 1 traces every root "
    "operation, N retains every Nth root's span tree in the trace "
    "buffer (slow queries are captured regardless — see "
    "geomesa.obs.slow.ms)",
)
OBS_TRACE_BUFFER = SystemProperty(
    "geomesa.obs.trace.buffer", 256, int,
    "bounded in-memory trace ring: completed sampled traces retained "
    "for DataStore.dump_trace (oldest evicted first)",
)
OBS_SLOW_MS = SystemProperty(
    "geomesa.obs.slow.ms", 1000.0, float,
    "always-on slow-query log threshold: a root operation slower than "
    "this captures its full span tree + plan fingerprint into the "
    "slow-query ring (DataStore.slow_queries); 0 disables the slow log "
    "(and, with geomesa.obs.trace.sample=0, disarms tracing outright)",
)
OBS_SLOW_MAX = SystemProperty(
    "geomesa.obs.slow.max", 64, int,
    "slow-query ring capacity (oldest captures evicted first)",
)
OBS_SLO_WINDOW_S = SystemProperty(
    "geomesa.obs.slo.window.s", 300.0, float,
    "sliding evaluation window for SLO objectives (DataStore.slo_report)",
)
OBS_SLO_SLICES = SystemProperty(
    "geomesa.obs.slo.slices", 30, int,
    "sub-slices per SLO window: observations rotate through this many "
    "interval sub-histograms, so the window slides with bounded memory "
    "and at most window/slices staleness",
)
OBS_SLO_QUERY_P99_MS = SystemProperty(
    "geomesa.obs.slo.query.p99.ms", 250.0, float,
    "default query-latency objective: geomesa.query.scan p99 over the "
    "sliding window must stay at or under this (SloTracker."
    "default_objectives; 0 drops the objective)",
)
OBS_SLO_FOLD_P99_MS = SystemProperty(
    "geomesa.obs.slo.fold.p99.ms", 150.0, float,
    "default fold-pause objective: geomesa.stream.fold.slice p99 must "
    "stay at or under this (the round-11 pause-kill SLO; 0 drops it)",
)
OBS_SLO_WAL_P99_MS = SystemProperty(
    "geomesa.obs.slo.wal.p99.ms", 50.0, float,
    "default durability objective: geomesa.stream.wal.fsync p99 must "
    "stay at or under this (0 drops it)",
)
OBS_SLO_STANDING_P99_MS = SystemProperty(
    "geomesa.obs.slo.standing.p99.ms", 250.0, float,
    "default standing-query alert objective: geomesa.standing.latency "
    "p99 (batch arrival -> alerts delivered, docs/standing.md) must "
    "stay at or under this (0 drops it)",
)
OBS_SLO_REPLICA_STALENESS_P99_MS = SystemProperty(
    "geomesa.obs.slo.replica.staleness.p99.ms", 2000.0, float,
    "default replication objective: geomesa.replica.staleness.ms p99 "
    "(a follower's measured staleness watermark, docs/replication.md) "
    "must stay at or under this (0 drops it)",
)
OBS_SLO_TILES_P99_MS = SystemProperty(
    "geomesa.obs.slo.tiles.p99.ms", 100.0, float,
    "default tile-serving objective: geomesa.tiles.fetch p99 (one "
    "/tiles request, compose + render included; docs/tiles.md) must "
    "stay at or under this (0 drops it)",
)


# -- the ops plane: /health + /metrics endpoints, telemetry history
# (geomesa_tpu.obs.ops; docs/observability.md "The ops plane") ------------

OBS_OPS_HOST = SystemProperty(
    "geomesa.obs.ops.host", "127.0.0.1", str,
    "bind address of the ops endpoint (DataStore.serve_ops): loopback "
    "by default — exposing /metrics//health beyond the host is an "
    "explicit operator decision",
)
OBS_OPS_SAMPLE_MS = SystemProperty(
    "geomesa.obs.ops.sample.ms", 1000.0, float,
    "TelemetryRecorder sampling cadence: every tick snapshots the "
    "metrics registry's gauges, counters and histogram p50/p99 into "
    "bounded time-series rings (/debug/vars), so operators get history "
    "between scrapes, not just the instantaneous value",
)
OBS_OPS_HISTORY = SystemProperty(
    "geomesa.obs.ops.history", 512, int,
    "points retained per telemetry ring (oldest evicted first): at the "
    "default 1 Hz cadence, ~8.5 minutes of history per series",
)


# -- planner estimate accountability (geomesa_tpu.obs.accuracy;
# docs/observability.md "Estimate accountability") ------------------------

PLAN_ESTIMATE = SystemProperty(
    "geomesa.plan.estimate.enabled", True, _parse_bool,
    "record the stats-sketch row estimate on every plan and compare it "
    "against the rows the executed scan actually produced (the "
    "geomesa.plan.estimate.error histogram + per-index accuracy in "
    "/health); False skips the plan-time sketch probe entirely",
)
PLAN_ESTIMATE_STALE_P90 = SystemProperty(
    "geomesa.plan.estimate.stale.p90", 4.0, float,
    "misestimate threshold: when a (type, index)'s p90 estimate error "
    "factor exceeds this, /health carries a 'stats stale — re-analyze' "
    "reason (and the auto-analyze hook may fire); 0 disables staleness "
    "detection",
)
PLAN_ESTIMATE_MIN_COUNT = SystemProperty(
    "geomesa.plan.estimate.min.count", 64, int,
    "recorded estimate-vs-actual samples a (type, index) window needs "
    "before its p90 can trip the staleness threshold (a handful of "
    "unlucky queries must not flag a whole store stale)",
)
PLAN_ESTIMATE_AUTO_ANALYZE = SystemProperty(
    "geomesa.plan.estimate.auto.analyze", False, _parse_bool,
    "when the staleness threshold trips, re-run DataStore.analyze_stats "
    "for the offending type automatically (once per trip; the accuracy "
    "window resets after). Off by default: a full re-sketch on a large "
    "store is a deliberate maintenance op",
)


# -- standing queries: the inverted subscription index
# (geomesa_tpu.streaming.standing; docs/standing.md) ----------------------

STANDING_GRID_LEVEL = SystemProperty(
    "geomesa.standing.grid.level", 12, int,
    "Z2 routing-grid level of the SubscriptionIndex (2^level cells per "
    "axis): arriving points route to the subscriptions covering their "
    "cell; finer levels shrink candidate sets but grow each "
    "subscription's registered cell count",
)
STANDING_CLASSIFY_CELLS = SystemProperty(
    "geomesa.standing.classify.cells", 16384, int,
    "per-subscription cell budget for FULL/PARTIAL registration-time "
    "classification (the PR 6 raster machinery): geofences whose bbox "
    "window exceeds it register every bbox cell PARTIAL — a superset, "
    "never wrong, just no zero-geometry full-cell matches",
)
STANDING_FUSED_MIN_POINTS = SystemProperty(
    "geomesa.standing.fused.min.points", 64, int,
    "routed candidate rows a boundary geofence needs in one batch "
    "before it joins a fused block_scan_multi dispatch; sparser "
    "candidates take the vectorized host ray cast (<= 0 keeps "
    "everything on the host path)",
)
STANDING_RASTER_CELLS = SystemProperty(
    "geomesa.standing.raster.cells", 1_048_576, int,
    "per-subscription cell budget for the MATCH-TIME raster grid built "
    "for dense (>= 16-edge, non-rectangle) geofences at registration: "
    "each candidate point classifies by one cell lookup — FULL cells "
    "match, OUT cells miss, only the boundary residue pays the exact "
    "ray cast (the PR 6 raster-interval economics, inverted); much "
    "finer than the routing grid, so jagged polygons' residue shrinks "
    "~10x; 0 disables (every boundary pair pays edges)",
)
STANDING_FUSED_GATE = SystemProperty(
    "geomesa.standing.fused.gate", True, _parse_bool,
    "measured-cost gate on the standing matcher's fused kernel path "
    "(the tile cache's adaptive-gate pattern): per-unit EWMAs of the "
    "host ray cast and the fused dispatch — seeded by one bounded "
    "probe chunk — keep each eligible geofence on whichever path "
    "measures cheaper on THIS host (counted by "
    "geomesa.standing.gate.host); false always fuses past "
    "geomesa.standing.fused.min.points (differential tests, kernel "
    "debugging)",
)
STANDING_QUEUE_MAX = SystemProperty(
    "geomesa.standing.queue.max", 65_536, int,
    "bounded alert-queue capacity: past it the OLDEST alerts drop "
    "(counted by geomesa.standing.dropped) — delivery never blocks the "
    "write ack path",
)
STANDING_WINDOW_PANES = SystemProperty(
    "geomesa.standing.window.panes", 512, int,
    "retained panes per continuous-window aggregate: panes older than "
    "the newest this-many drop (counted by "
    "geomesa.standing.window.dropped), bounding window state",
)


# -- lock-witness runtime (geomesa_tpu.lockwitness; docs/concurrency.md) --

LOCK_WITNESS = SystemProperty(
    "geomesa.tpu.lock.witness", False, _parse_bool,
    "arm the dynamic lock witness: registry-declared locks constructed "
    "AFTER arming wrap in an order-recording proxy; the observed "
    "acquisition graph must stay acyclic and inside the static model's "
    "predicted edges (tests/test_lock_witness.py; resolves from "
    "GEOMESA_TPU_LOCK_WITNESS=1 like every knob)",
)
LOCK_WITNESS_ARTIFACT = SystemProperty(
    "geomesa.tpu.lock.witness.artifact", "/tmp/lock_witness.json", str,
    "where lockwitness.dump() writes the observed edge graph / blocking "
    "events so a CI failure is diagnosable from logs alone",
)


# -- concurrent query serving (geomesa_tpu.serving; docs/serving.md) ------

SERVING_WINDOW_MS = SystemProperty(
    "geomesa.serving.window_ms", 2.0, float,
    "micro-batch window CAP in milliseconds: the scheduler's adaptive "
    "window grows toward this under load (more fusion per dispatch) and "
    "shrinks to ~0 when idle (single queries pay ~no added latency)",
)
SERVING_QUEUE_MAX = SystemProperty(
    "geomesa.serving.queue.max", 1024, int,
    "bounded admission queue depth: a full queue blocks (backpressure) or "
    "sheds with the geomesa.serving.shed counter, never buffers unboundedly",
)
SERVING_BATCH_MAX = SystemProperty(
    "geomesa.serving.batch.max", 128, int,
    "max queries drained into one fused micro-batch dispatch",
)


# -- the data plane (geomesa_tpu.serving.http; docs/serving.md) -----------

SERVE_HOST = SystemProperty(
    "geomesa.serve.host", "127.0.0.1", str,
    "bind address for DataStore.serve(port) — loopback by default "
    "(sandbox- and laptop-friendly, same posture as the ops plane)",
)
SERVE_PAGE_ROWS = SystemProperty(
    "geomesa.serve.page.rows", 4096, int,
    "rows per chunked-transfer page on the query endpoints: one big "
    "result streams as bounded pages instead of materializing the whole "
    "payload (one Arrow record batch per page on fmt=arrow)",
)
SERVE_MAX_BODY_BYTES = SystemProperty(
    "geomesa.serve.max.body.bytes", 64 << 20, int,
    "cap on an ingest request body; a larger Content-Length is refused "
    "with HTTP 413 before any bytes are read",
)
SERVE_RETRY_AFTER_MS = SystemProperty(
    "geomesa.serve.retry.after.ms", 50.0, float,
    "Retry-After hint (milliseconds, rendered as ceil seconds) on a 429 "
    "shed or a 503 stale-replica read — the client backoff the admission "
    "layer suggests",
)


# -- live map-tile serving (geomesa_tpu.tiles; docs/tiles.md) -------------

TILES_LEAF_ZOOM = SystemProperty(
    "geomesa.tiles.leaf.zoom", 3, int,
    "the pyramid's finest zoom: leaf tiles aggregate rows once at this "
    "level, every zoom above folds child partials; /tiles serves zooms "
    "[0, leaf.zoom]",
)
TILES_PX = SystemProperty(
    "geomesa.tiles.px", 256, int,
    "tile raster edge in pixels (one served tile is px x px)",
)
TILES_CACHE_MAX_BYTES = SystemProperty(
    "geomesa.tiles.cache.max.bytes", 128 << 20, int,
    "LRU byte budget for composed tile grids (the pyramid's own "
    "ResultCache instance; 0 recomposes every fetch)",
)
TILES_TTL = SystemProperty(
    "geomesa.tiles.ttl", None, float,
    "seconds a composed tile grid stays servable past its compose "
    "(None = until a generation bump invalidates it); spread by "
    "geomesa.cache.ttl.jitter like every cached result",
)
TILES_MAX_AGE_S = SystemProperty(
    "geomesa.tiles.max.age.s", 0.0, float,
    "Cache-Control on /tiles responses: > 0 serves 'public, max-age=N' "
    "(clients may reuse without revalidating for N seconds); 0 serves "
    "'no-cache' so clients revalidate via the generation-derived ETag "
    "(a clean tile costs one 304, no compose or render work)",
)


# -- multi-tenant fairness (geomesa_tpu.serving.tenancy; docs/serving.md) --

TENANT_QUEUE_MAX = SystemProperty(
    "geomesa.tenant.queue.max", 256, int,
    "per-tenant admission quota: one tenant's queued queries past this "
    "shed with 429 while other tenants' queues stay open — the bound "
    "that keeps a flooding tenant from filling the shared queue",
)
TENANT_DEFAULT_WEIGHT = SystemProperty(
    "geomesa.tenant.default.weight", 1.0, float,
    "deficit-round-robin weight for tenants without an explicit "
    "TenantRegistry.configure() entry: each drained micro-batch takes "
    "from backlogged tenants in proportion to weight",
)
TENANT_SLO_P99_MS = SystemProperty(
    "geomesa.tenant.slo.p99.ms", 500.0, float,
    "per-tenant SLO objective: served-query wall p99 threshold for each "
    "tenant's own SloTracker window (0 disables per-tenant objectives)",
)


def describe() -> str:
    """One line per registered property with its current value (CLI env)."""
    out = []
    for name in sorted(REGISTRY):
        p = REGISTRY[name]
        out.append(f"{name} = {p.get()!r}  [{p.env_key}] {p.doc}")
    return "\n".join(out)
