"""Driver benchmark: all five BASELINE.md configs on one TPU chip.

- config 1 (primary; printed first AND repeated as the final line so any
  single-line parser reads it): Z3 point index, BBOX + time-range queries
  over a GDELT-shaped table (default N=500M — 8 GB of device columns,
  ~half of v5e HBM).
- config 2: Z2 point index, BBOX-only queries (OSM-GPS-shaped).
- config 3: XZ2 polygon index, ST_Intersects queries over building-
  footprint-shaped rectangles (default N3=200M — the OSM building layer
  is ~500M footprints; 50M in rounds 3-4 understated the table the
  baseline has to scan).
- config 4: grid-partitioned spatial join, points x admin polygons.
- config 5: kNN process over trajectory-shaped points.

The baseline proxy for every config is a vectorized NumPy full-columnar
CPU scan of the same predicate (the reference's geomesa-fs Parquet/CPU
path is JVM and cannot run here; an in-memory columnar scan is a
*stronger* baseline than a Parquet file scan).

Measured queries are DISJOINT from warmup queries: both draw from the
same shape/selectivity buckets but with different seeds, so the timed
set proves no per-query host state is reused (VERDICT r3 weak #4).
Warmup still compiles every (bucket, flags) kernel variant because
variants are keyed by shape bucket, not query values.

Prints one JSON line per config, config 1 first. Env knobs:
GEOMESA_BENCH_N (config-1 points), GEOMESA_BENCH_N2, GEOMESA_BENCH_N3,
GEOMESA_BENCH_N4, GEOMESA_BENCH_N5, GEOMESA_BENCH_QUERIES,
GEOMESA_BENCH_CONFIGS (e.g. "1" or "1,2,3"; named scenarios "cache",
"serving", "ingest", "fused", "pip_join", "stream", "wal", "knn",
"obs", "ops", "standing", "replica", "serve_http", "tiles", "drift",
"pod").

One process, one platform: ``main()`` refuses to run when JAX's first
device is not a TPU (exit 2, no row printed). There is no supervisor, no
retry and no replay of recorded rows; a device that does not come up is
the result.
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time

import numpy as np

N1 = int(os.environ.get("GEOMESA_BENCH_N", 500_000_000))
N2 = int(os.environ.get("GEOMESA_BENCH_N2", 200_000_000))
N3 = int(os.environ.get("GEOMESA_BENCH_N3", 200_000_000))
N_QUERIES = int(os.environ.get("GEOMESA_BENCH_QUERIES", 40))
CONFIGS = os.environ.get("GEOMESA_BENCH_CONFIGS", "1,2,3,4,5").split(",")
SEED = 42


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def gdelt_points(n, rng):
    """World-wide events clustered around population centers: uniform
    background + gaussian clusters."""
    n_clustered = n // 2
    n_uniform = n - n_clustered
    cx = rng.uniform(-160, 160, 64)
    cy = rng.uniform(-55, 65, 64)
    which = rng.integers(0, 64, n_clustered)
    x = np.concatenate(
        [
            rng.uniform(-180, 180, n_uniform),
            np.clip(cx[which] + rng.normal(0, 3.0, n_clustered), -180, 180),
        ]
    )
    y = np.concatenate(
        [
            rng.uniform(-90, 90, n_uniform),
            np.clip(cy[which] + rng.normal(0, 2.0, n_clustered), -90, 90),
        ]
    )
    return x, y


def box_queries(rng, n_queries):
    """Selectivity mix: city-scale through continent-scale boxes."""
    out = []
    for _ in range(n_queries):
        w = float(rng.choice([1.0, 2.0, 5.0, 10.0, 20.0, 40.0]))
        h = w / 2
        qx = rng.uniform(-175, 175 - w)
        qy = rng.uniform(-85, 85 - h)
        out.append((qx, qy, qx + w, qy + h))
    return out


def time_windows(rng, n_queries, t0, span_ms):
    out = []
    for _ in range(n_queries):
        dur_ms = int(rng.choice([6, 24, 72, 168, 24 * 14]) * 3600_000)
        start = int(t0 + rng.integers(0, span_ms - dur_ms))
        out.append((start, start + dur_ms))
    return out


def run_queries(ds, type_name, queries, label):
    """(latencies s, total hits) — warmup pass then a timed pass over a
    DISJOINT measured set."""
    warmup, measured = queries
    t_warm = time.perf_counter()
    for i, q in enumerate(warmup):
        s = time.perf_counter()
        ds.query(type_name, q)
        if i < 3 or time.perf_counter() - s > 1.0:
            log(f"[{label}] warmup {i}: {time.perf_counter() - s:.2f}s")
    # one small batch compiles the canonical fused multi-query variant
    # (fixed chunk shape), so the timed query_many pass stays compile-free
    s = time.perf_counter()
    ds.query_many(type_name, warmup[:6])
    log(f"[{label}] warmup done in {time.perf_counter() - t_warm:.1f}s "
        f"(fused batch {time.perf_counter() - s:.2f}s)")

    lat, hits = [], 0
    t_all = time.perf_counter()
    for q in measured:
        s = time.perf_counter()
        out = ds.query(type_name, q)
        lat.append(time.perf_counter() - s)
        hits += len(out)
    return np.array(lat), hits, time.perf_counter() - t_all


def result_line(metric, lat, hits, wall, base_mean, extra):
    lat_ms = lat * 1e3
    rec = {
        "metric": metric,
        "value": round(hits / wall, 1),
        "unit": "features/s",
        "vs_baseline": round(base_mean / float(np.mean(lat)), 2),
        "n_queries": len(lat),
        "hits_total": hits,
        "latency_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "latency_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "latency_mean_ms": round(float(np.mean(lat_ms)), 2),
        "cpu_baseline_mean_ms": round(base_mean * 1e3, 2),
    }
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------------------- config 1


def config1_z3():
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    n = N1
    rng = np.random.default_rng(SEED)
    log(f"[z3] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    span_ms = 120 * 86400_000
    t = t0 + rng.integers(0, span_ms, n)

    sft = FeatureType.from_spec("gdelt", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3"
    if n > 600_000_000:
        # the 1B-row north-star configuration: packed-time device layout
        # (12 B/row -> 12 GB at 1e9; the 16 B/row (tbin, toff) layout
        # would blow the v5e's 16 GB HBM). Results identical — tick
        # boundaries refine on host (tests/test_packed_time.py)
        sft.user_data["geomesa.z3.packed-time"] = "true"
    ds = DataStore()
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(sft, np.arange(n), {"dtg": t, "geom": (x, y)})
    t_in = time.perf_counter()
    ds.write("gdelt", fc, check_ids=False)
    ingest_s = time.perf_counter() - t_in
    table = ds.table("gdelt", "z3")
    log(f"[z3] ingest {ingest_s:.1f}s, device {table.nbytes_device / 1e9:.2f} GB")

    def qset(seed):
        r = np.random.default_rng(seed)
        boxes = box_queries(r, N_QUERIES)
        wins = time_windows(r, N_QUERIES, t0, span_ms)
        qs = []
        for (x0, y0, x1, y1), (lo, hi) in zip(boxes, wins):
            qs.append(
                (
                    f"bbox(geom, {x0:.4f}, {y0:.4f}, {x1:.4f}, {y1:.4f}) AND dtg DURING "
                    f"{np.datetime64(lo, 'ms')}Z/{np.datetime64(hi, 'ms')}Z",
                    (x0, y0, x1, y1, lo, hi),
                )
            )
        return qs

    warmup = [q for q, _ in qset(SEED + 1)]
    measured_full = qset(SEED + 2)  # disjoint from warmup, same buckets
    measured = [q for q, _ in measured_full]

    lat, hits, wall = run_queries(ds, "gdelt", (warmup, measured), "z3")

    # pipelined throughput: same measured set through query_many (all
    # device scans dispatch before any pull — hides the per-query link
    # round-trip; per-query latency above is unchanged by this)
    t_pipe = time.perf_counter()
    outs = ds.query_many("gdelt", measured)
    pipe_wall = time.perf_counter() - t_pipe
    pipe_hits = sum(len(o) for o in outs)
    assert pipe_hits == hits, (pipe_hits, hits)

    # CPU columnar baseline on a sample of the measured set
    times = []
    for _, (x0, y0, x1, y1, lo, hi) in measured_full[:6]:
        s = time.perf_counter()
        m = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1) & (t >= lo) & (t < hi)
        np.nonzero(m)[0]
        times.append(time.perf_counter() - s)
        del m
    base_mean = float(np.mean(times))

    rec = result_line(
        "gdelt_z3_bbox_time_features_per_sec_per_chip", lat, hits, wall, base_mean,
        {
            "n_points": n,
            "ingest_rate_per_s": round(n / ingest_s, 1),
            "device_gb": round(table.nbytes_device / 1e9, 3),
            "pipelined_features_per_sec": round(pipe_hits / pipe_wall, 1),
            **LINK_PROFILE,
        },
    )
    del ds, fc, table, x, y, t
    gc.collect()
    return rec


# ------------------------------------------------------------- config 2


def config2_z2():
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    n = N2
    rng = np.random.default_rng(SEED + 10)
    log(f"[z2] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)  # OSM-GPS-shaped: clustered + background

    sft = FeatureType.from_spec("osm", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(sft, np.arange(n), {"geom": (x, y)})
    t_in = time.perf_counter()
    ds.write("osm", fc, check_ids=False)
    ingest_s = time.perf_counter() - t_in
    table = ds.table("osm", "z2")
    log(f"[z2] ingest {ingest_s:.1f}s, device {table.nbytes_device / 1e9:.2f} GB")

    def qset(seed):
        r = np.random.default_rng(seed)
        return [
            (f"bbox(geom, {x0:.4f}, {y0:.4f}, {x1:.4f}, {y1:.4f})", (x0, y0, x1, y1))
            for x0, y0, x1, y1 in box_queries(r, N_QUERIES)
        ]

    warmup = [q for q, _ in qset(SEED + 11)]
    measured_full = qset(SEED + 12)
    measured = [q for q, _ in measured_full]
    lat, hits, wall = run_queries(ds, "osm", (warmup, measured), "z2")

    t_pipe = time.perf_counter()
    outs = ds.query_many("osm", measured)
    pipe_wall = time.perf_counter() - t_pipe
    pipe_hits = sum(len(o) for o in outs)
    assert pipe_hits == hits, (pipe_hits, hits)

    times = []
    for _, (x0, y0, x1, y1) in measured_full[:6]:
        s = time.perf_counter()
        m = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
        np.nonzero(m)[0]
        times.append(time.perf_counter() - s)
        del m
    base_mean = float(np.mean(times))

    rec = result_line(
        "osm_z2_bbox_features_per_sec_per_chip", lat, hits, wall, base_mean,
        {
            "n_points": n,
            "ingest_rate_per_s": round(n / ingest_s, 1),
            "device_gb": round(table.nbytes_device / 1e9, 3),
            "pipelined_features_per_sec": round(pipe_hits / pipe_wall, 1),
        },
    )
    del ds, fc, table, x, y
    gc.collect()
    return rec


# ------------------------------------------------------------- config 3


def config3_xz2():
    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    n = N3
    rng = np.random.default_rng(SEED + 20)
    log(f"[xz2] building {n:,} polygon store ...")
    # building-footprint-shaped rectangles clustered in "cities"
    cx = rng.uniform(-160, 160, 256)
    cy = rng.uniform(-55, 65, 256)
    which = rng.integers(0, 256, n)
    x0 = np.clip(cx[which] + rng.normal(0, 0.5, n), -179.9, 179.8)
    y0 = np.clip(cy[which] + rng.normal(0, 0.4, n), -89.9, 89.8)
    w = rng.uniform(0.0002, 0.002, n)  # ~20-200 m
    h = rng.uniform(0.0002, 0.002, n)
    col = geo.PackedGeometryColumn.from_boxes(x0, y0, x0 + w, y0 + h)

    sft = FeatureType.from_spec("bld", "*geom:Polygon:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "xz2"
    ds = DataStore()
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(sft, np.arange(n), {"geom": col})
    t_in = time.perf_counter()
    ds.write("bld", fc, check_ids=False)
    ingest_s = time.perf_counter() - t_in
    table = ds.table("bld", "xz2")
    log(f"[xz2] ingest {ingest_s:.1f}s, device {table.nbytes_device / 1e9:.2f} GB")

    def qset(seed):
        r = np.random.default_rng(seed)
        qs = []
        for _ in range(N_QUERIES):
            c = r.integers(0, 256)
            qw = float(r.choice([0.02, 0.05, 0.1, 0.5, 2.0]))
            qx = cx[c] + r.uniform(-1, 1)
            qy = cy[c] + r.uniform(-0.8, 0.8)
            poly = (
                f"POLYGON(({qx:.4f} {qy:.4f}, {qx + qw:.4f} {qy:.4f}, "
                f"{qx + qw:.4f} {qy + qw:.4f}, {qx:.4f} {qy + qw:.4f}, "
                f"{qx:.4f} {qy:.4f}))"
            )
            qs.append((f"INTERSECTS(geom, {poly})", (qx, qy, qx + qw, qy + qw)))
        return qs

    warmup = [q for q, _ in qset(SEED + 21)]
    measured_full = qset(SEED + 22)
    measured = [q for q, _ in measured_full]
    lat, hits, wall = run_queries(ds, "bld", (warmup, measured), "xz2")

    t_pipe = time.perf_counter()
    outs = ds.query_many("bld", measured)
    pipe_wall = time.perf_counter() - t_pipe
    pipe_hits = sum(len(o) for o in outs)
    assert pipe_hits == hits, (pipe_hits, hits)

    bx0, by0 = col.bboxes[:, 0], col.bboxes[:, 1]
    bx1, by1 = col.bboxes[:, 2], col.bboxes[:, 3]
    times = []
    for _, (qx0, qy0, qx1, qy1) in measured_full[:6]:
        s = time.perf_counter()
        m = (bx0 <= qx1) & (bx1 >= qx0) & (by0 <= qy1) & (by1 >= qy0)
        np.nonzero(m)[0]
        times.append(time.perf_counter() - s)
        del m
    base_mean = float(np.mean(times))

    rec = result_line(
        "osm_xz2_intersects_features_per_sec_per_chip", lat, hits, wall, base_mean,
        {
            "n_polygons": n,
            "ingest_rate_per_s": round(n / ingest_s, 1),
            "device_gb": round(table.nbytes_device / 1e9, 3),
            "pipelined_features_per_sec": round(pipe_hits / pipe_wall, 1),
        },
    )
    del ds, fc, table, col
    gc.collect()
    return rec


# ------------------------------------------------------ ingest scenario


def _rss_bytes() -> int:
    """Current resident set size of this process (Linux /proc)."""
    with open("/proc/self/statm") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def _malloc_trim() -> None:
    """Release freed-but-retained allocator arenas before a baseline RSS
    capture, so the measured ratios compare live bytes, not glibc
    retention. NOT called while sampling a phase's peak — the peak stays
    conservative (what an OOM killer would actually see)."""
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass


class _RssSampler:
    """Background peak-RSS sampler (the compaction memory-model proof:
    ru_maxrss is a process-lifetime high-water mark, useless for scoping
    one phase)."""

    def __init__(self, interval_s: float = 0.02):
        import threading

        self.interval_s = interval_s
        self.peak = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            self.peak = max(self.peak, _rss_bytes())
            self._stop.wait(self.interval_s)

    def __enter__(self):
        self.peak = _rss_bytes()
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join()
        self.peak = max(self.peak, _rss_bytes())


def _ingest_column_set_bytes(ds, type_name: str) -> int:
    """Host bytes attributable to one type's column set: feature columns
    + ids + every index's key columns + the resident table columns (RAM
    on a CPU backend)."""
    from geomesa_tpu.ingest.pipeline import _chunk_nbytes

    total = 0
    for fc in ds._chunks.get(type_name, []):
        total += _chunk_nbytes(fc, {})
    for (t, name), parts in ds._key_chunks.items():
        if t != type_name:
            continue
        for k in parts:
            total += int(k.bins.nbytes) + int(k.zs.nbytes)
            total += sum(int(v.nbytes) for v in k.device_cols.values())
    for (t, name), table in ds._tables.items():
        if t == type_name:
            total += int(table.nbytes_device)  # RAM on a CPU backend
            # the table's host half: sorted key copies + the permutation
            for arr in (table.perm, table.bins, table.zs):
                total += int(np.asarray(arr).nbytes)
    return total


def _table_fingerprint(ds, type_name: str) -> str:
    """blake2b over every index table's sorted keys, block layout, and
    the stats sketch JSON — the bit-identity check between the sequential
    and pipelined ingest paths."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for (t, name) in sorted(ds._tables):
        if t != type_name:
            continue
        tab = ds._tables[(t, name)]
        h.update(f"{name}:{tab.n}:{tab.block}:{tab.n_blocks}".encode())
        h.update(np.ascontiguousarray(tab.bins).tobytes())
        h.update(np.ascontiguousarray(tab.zs).tobytes())
        for k in tab.col_names:
            h.update(np.asarray(tab.cols3[k]).tobytes())
    stats = ds.stats_for(type_name)
    if stats is not None:
        h.update(json.dumps(stats.to_json(), default=str, sort_keys=True).encode())
    return h.hexdigest()


def config_ingest(out_path: "str | None" = None):
    """Pipelined multi-core ingest scenario (docs/ingest.md): sequential
    ``write()`` loop vs the staged BulkLoader at 1/2/4 workers on a
    GDELT-shaped bulk load, with a bit-identity check between the paths,
    plus a compaction row proving the bounded-memory streamed merge
    (peak RSS vs the column set). CPU-runnable. Env knobs:
    GEOMESA_BENCH_INGEST_N (rows), GEOMESA_BENCH_INGEST_CHUNK (rows per
    ingest chunk), GEOMESA_BENCH_INGEST_WORKERS (comma list),
    GEOMESA_BENCH_INGEST_COMPACT_N (compaction-row table size)."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.ingest import BulkLoader, PipelineConfig
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_INGEST_N", 20_000_000))
    chunk = int(os.environ.get("GEOMESA_BENCH_INGEST_CHUNK", 1_000_000))
    workers_list = [
        int(w) for w in os.environ.get(
            "GEOMESA_BENCH_INGEST_WORKERS", "1,2,4"
        ).split(",")
    ]
    compact_n = int(os.environ.get("GEOMESA_BENCH_INGEST_COMPACT_N", 100_000_000))
    SPEC = "dtg:Date,*geom:Point:srid=4326"
    T0 = 1_704_067_200_000  # 2024-01-01
    SPAN = 80 * 86_400_000

    # -- compaction peak-RSS row (the bounded-memory merge proof) --------
    # run FIRST so the RSS baseline is the bare process (interpreter,
    # jax, XLA) with no leftover arenas from the throughput comparison
    gc.collect()
    _malloc_trim()
    rss_baseline = _rss_bytes()
    log(f"[ingest] compaction row: building {compact_n:,}-row z3 table ...")
    # a GDELT-shaped row: time + point + a payload attribute
    sft = FeatureType.from_spec("cmp", "val:Double," + SPEC)
    sft.user_data["geomesa.indices.enabled"] = "z3"
    ds = DataStore()
    ds.create_schema(sft)
    crng = np.random.default_rng(SEED + 81)
    loader = BulkLoader(ds, "cmp", check_ids=False)
    step = 4_000_000
    for s in range(0, compact_n, step):
        m = min(step, compact_n - s)
        x, y = gdelt_points(m, crng)
        loader.put(FeatureCollection.from_columns(
            sft, np.arange(s, s + m, dtype=np.int64),
            {"val": crng.uniform(0, 1, m),
             "dtg": T0 + crng.integers(0, SPAN, m), "geom": (x, y)},
        ))
    loader.close()
    del loader
    gc.collect()
    delta_rows = max(compact_n // 64, 1)
    x, y = gdelt_points(delta_rows, crng)
    ds.write("cmp", FeatureCollection.from_columns(
        sft, np.arange(compact_n, compact_n + delta_rows, dtype=np.int64),
        {"val": crng.uniform(0, 1, delta_rows),
         "dtg": T0 + crng.integers(0, SPAN, delta_rows), "geom": (x, y)},
    ), check_ids=False)
    gc.collect()
    _malloc_trim()
    column_set = _ingest_column_set_bytes(ds, "cmp")
    with _RssSampler() as rss:
        before = rss.peak
        t0 = time.perf_counter()
        ds.compact("cmp")
        compact_s = time.perf_counter() - t0
    peak_extra = rss.peak - before
    # store-attributable peak (minus the pre-store process baseline) vs
    # the column set: the "no doubling" criterion
    peak_over_cs = (rss.peak - rss_baseline) / max(column_set, 1)
    # TPU-host model: on a real accelerator host the device columns live
    # in HBM, not host RSS — the CPU backend double-counts them (old +
    # freshly-built table both resident at the swap). Subtract them from
    # both sides for the host-memory-model ratio docs/ingest.md states.
    dev = sum(
        int(t.nbytes_device) for (tn, _), t in ds._tables.items() if tn == "cmp"
    )
    host_cs = max(column_set - dev, 1)
    # clamp at 0: at CI-sized tables the streamed build's real extra is
    # below the modeled 2x device subtraction, which would otherwise
    # publish a negative (nonsense) ratio
    host_peak = max((rss.peak - rss_baseline) - 2 * dev, 0)
    peak_over_cs_host = host_peak / host_cs
    # exactness spot-check after the streamed merge
    probe = ds.count("cmp", "bbox(geom, -10, -10, 0, 0)")
    compaction = {
        "n_rows": compact_n,
        "delta_rows": delta_rows,
        "seconds": round(compact_s, 2),
        "column_set_bytes": column_set,
        "rss_baseline_bytes": rss_baseline,
        "rss_before_bytes": before,
        "rss_peak_bytes": rss.peak,
        "peak_extra_bytes": peak_extra,
        "peak_over_column_set": round(peak_over_cs, 3),
        "peak_over_column_set_host_model": round(peak_over_cs_host, 3),
        "probe_hits": int(probe),
    }
    log(
        f"[ingest] compaction: {compact_s:.1f}s, column set "
        f"{column_set / 1e9:.2f} GB, peak RSS {rss.peak / 1e9:.2f} GB "
        f"(store-attributed {peak_over_cs:.2f}x column set, "
        f"+{peak_extra / 1e9:.2f} GB during compact)"
    )
    del ds
    gc.collect()


    log(f"[ingest] generating {n:,} rows in {chunk:,}-row chunks ...")
    rng = np.random.default_rng(SEED + 80)
    raw = []  # shared immutable arrays: both paths ingest identical data
    for s in range(0, n, chunk):
        m = min(chunk, n - s)
        x, y = gdelt_points(m, rng)
        raw.append((
            np.arange(s, s + m, dtype=np.int64),
            T0 + rng.integers(0, SPAN, m),
            x, y,
        ))

    def run_ingest(body) -> tuple:
        """(wall seconds, store, fingerprint) for one full load."""
        sft = FeatureType.from_spec("ing", SPEC)
        sft.user_data["geomesa.indices.enabled"] = "z3,z2"
        ds = DataStore()
        ds.create_schema(sft)
        chunks = [
            FeatureCollection.from_columns(
                sft, ids, {"dtg": t, "geom": (x, y)}
            )
            for ids, t, x, y in raw
        ]
        t0 = time.perf_counter()
        body(ds, chunks)
        wall = time.perf_counter() - t0
        return wall, ds, _table_fingerprint(ds, "ing")

    def seq_body(ds, chunks):
        for fc in chunks:
            ds.write("ing", fc, check_ids=False)
        ds.compact("ing")  # bulk loads end compacted on both paths

    log("[ingest] sequential write() loop ...")
    seq_wall, ds, seq_fp = run_ingest(seq_body)
    del ds
    gc.collect()
    seq_rate = n / seq_wall
    log(f"[ingest] sequential: {seq_wall:.1f}s ({seq_rate:,.0f} rows/s)")

    rows = []
    stage_seconds = {}
    for w in workers_list:
        def pipe_body(ds, chunks, w=w):
            loader = BulkLoader(
                ds, "ing", check_ids=False,
                config=PipelineConfig(workers=w),
            )
            for fc in chunks:
                loader.put(fc)
            res = loader.close()
            stage_seconds[w] = {
                k: round(v, 2) for k, v in res.stage_seconds.items()
            }

        wall, ds, fp = run_ingest(pipe_body)
        del ds
        gc.collect()
        identical = fp == seq_fp
        row = {
            "workers": w,
            "seconds": round(wall, 2),
            "rows_per_s": round(n / wall),
            "speedup": round(seq_wall / wall, 2),
            "identical_tables": identical,
            "stage_seconds": stage_seconds.get(w, {}),
        }
        rows.append(row)
        log(
            f"[ingest] pipelined x{w}: {wall:.1f}s "
            f"({n / wall:,.0f} rows/s, {row['speedup']}x, "
            f"identical={identical}) stages={row['stage_seconds']}"
        )

    import jax

    headline = max(rows, key=lambda r: r["workers"])
    payload = {
        "n_rows": n,
        "chunk_rows": chunk,
        "platform": jax.default_backend(),
        "host_cores": os.cpu_count(),
        "sequential": {
            "seconds": round(seq_wall, 2), "rows_per_s": round(seq_rate),
        },
        "pipelined": rows,
        "compaction": compaction,
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_INGEST.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "ingest_pipelined_speedup",
        "value": headline["speedup"],
        "unit": "x",
        "workers": headline["workers"],
        "rows_per_s": headline["rows_per_s"],
        "sequential_rows_per_s": round(seq_rate),
        "identical_tables": headline["identical_tables"],
        "compaction_peak_over_column_set": compaction["peak_over_column_set"],
        "n_rows": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------------- cache scenario


def config_cache(out_path: "str | None" = None):
    """Query/aggregation cache tier scenario (docs/caching.md): repeat-
    query and shifted-bbox workloads on a cache-enabled store, reporting
    hit rate and warm-cache speedup. Emits BENCH_CACHE.json next to this
    file (or at ``out_path``). Env knobs: GEOMESA_BENCH_CACHE_N (points),
    GEOMESA_BENCH_CACHE_QUERIES (distinct queries per workload)."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.planning.hints import QueryHints
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_CACHE_N", 5_000_000))
    n_q = int(os.environ.get("GEOMESA_BENCH_CACHE_QUERIES", 24))
    rng = np.random.default_rng(SEED + 60)
    log(f"[cache] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)
    sft = FeatureType.from_spec("dash", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg, cache=True)
    ds.create_schema(sft)
    ds.write("dash", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (x, y)}), check_ids=False)

    boxes = box_queries(np.random.default_rng(SEED + 61), n_q)
    queries = [
        f"bbox(geom, {x0:.4f}, {y0:.4f}, {x1:.4f}, {y1:.4f})"
        for x0, y0, x1, y1 in boxes
    ]
    bypass = QueryHints(cache="bypass")

    # -- repeat-query workload (the dashboard refresh) -------------------
    for q in queries:  # compile kernels; no cache interaction
        ds.query("dash", q, hints=bypass)
    def _timed_pass(run):
        """Two passes, per-query min: the noise floor under scheduler
        jitter (a 3x run-to-run swing on identical scans is common on a
        contended host; noise only ever ADDS time)."""
        a = []
        for q in queries:
            s = time.perf_counter()
            run(q)
            a.append(time.perf_counter() - s)
        b = []
        for q in queries:
            s = time.perf_counter()
            run(q)
            b.append(time.perf_counter() - s)
        return np.minimum(a, b)

    cold = _timed_pass(  # honest uncached latency, cache bypassed
        lambda q: ds.query("dash", q, hints=bypass)
    )
    for q in queries:  # populate
        ds.query("dash", q)
    h0, m0 = reg.counters["geomesa.cache.hit"], reg.counters["geomesa.cache.miss"]
    hits_total = 0

    def _warm(q):
        nonlocal hits_total
        hits_total += len(ds.query("dash", q))

    warm = _timed_pass(_warm)  # the repeat passes: served warm
    h1, m1 = reg.counters["geomesa.cache.hit"], reg.counters["geomesa.cache.miss"]
    hit_rate = (h1 - h0) / max((h1 - h0) + (m1 - m0), 1)
    # speedup over the WORKLOAD (total cold / total warm): the dashboard
    # refresh is the whole query set, and totals weight the expensive
    # queries the cache exists for — per-query medians flip on boxes whose
    # uncached scan is already sub-ms
    speedup = float(np.sum(cold)) / max(float(np.sum(warm)), 1e-9)
    repeat = {
        "n_queries": n_q,
        "hit_rate": round(hit_rate, 4),
        "speedup": round(speedup, 2),
        "uncached_total_ms": round(float(np.sum(cold)) * 1e3, 3),
        "warm_total_ms": round(float(np.sum(warm)) * 1e3, 3),
        "uncached_median_ms": round(float(np.median(cold)) * 1e3, 3),
        "warm_median_ms": round(float(np.median(warm)) * 1e3, 3),
        "warm_p99_ms": round(float(np.percentile(np.array(warm) * 1e3, 99)), 3),
    }
    log(f"[cache] repeat-query: hit rate {hit_rate:.2%}, speedup {speedup:.1f}x")

    # -- shifted-bbox workload (the dashboard pan) -----------------------
    # count() composes per-tile aggregates: a pan re-scans only the edge
    # strips, the interior comes from the tile cache
    shift_cold = []
    for (x0, y0, x1, y1), q in zip(boxes, queries):
        s = time.perf_counter()
        n_plain = len(ds.query("dash", q, hints=bypass))
        shift_cold.append(time.perf_counter() - s)
        assert ds.count("dash", q) == n_plain  # fills tiles + exactness
    r0 = reg.counters.get("geomesa.cache.tile.reused", 0)
    f0 = reg.counters.get("geomesa.cache.tile.filled", 0)
    g0 = reg.counters.get("geomesa.cache.tile.gated", 0)
    panned = []  # pan each box by ~10% of its width
    for x0, y0, x1, y1 in boxes:
        dx = (x1 - x0) * 0.1
        panned.append(
            f"bbox(geom, {x0 + dx:.4f}, {y0:.4f}, "
            f"{min(x1 + dx, 180.0):.4f}, {y1:.4f})"
        )
    for q in panned:  # compile + plan-memo warmup, same as the cold loop
        ds.query("dash", q, hints=bypass)
    shift_warm = []
    for q in panned:
        s = time.perf_counter()
        ds.count("dash", q)
        shift_warm.append(time.perf_counter() - s)
    r1 = reg.counters.get("geomesa.cache.tile.reused", 0)
    f1 = reg.counters.get("geomesa.cache.tile.filled", 0)
    g1 = reg.counters.get("geomesa.cache.tile.gated", 0)
    reused_frac = (r1 - r0) / max((r1 - r0) + (f1 - f0), 1)
    shifted = {
        "n_queries": n_q,
        "tiles_reused_frac": round(reused_frac, 4),
        # compositions the adaptive cost gate skipped: on backends where
        # fragmented edge scans price near a full scan, the gate keeps
        # the pan workload at plain-scan parity instead of composing at
        # a loss — 0 reuse + high gated is the gate doing its job
        "gated": g1 - g0,
        "uncached_scan_median_ms": round(float(np.median(shift_cold)) * 1e3, 3),
        "shifted_count_median_ms": round(float(np.median(shift_warm)) * 1e3, 3),
        "speedup": round(
            float(np.median(shift_cold)) / max(float(np.median(shift_warm)), 1e-9), 2
        ),
    }
    log(f"[cache] shifted-bbox: {reused_frac:.2%} tiles reused, "
        f"{shifted['speedup']}x vs plain scan")

    import jax

    payload = {
        "n_points": n,
        "platform": jax.default_backend(),
        "repeat_query": repeat,
        "shifted_bbox": shifted,
        "cache_stats": ds.cache.stats(),
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_CACHE.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "cache_repeat_query_speedup",
        "value": repeat["speedup"],
        "unit": "x",
        "hit_rate": repeat["hit_rate"],
        "tiles_reused_frac": shifted["tiles_reused_frac"],
        "n_points": n,
        "hits_total": hits_total,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------------- drift scenario


def config_drift(out_path: "str | None" = None):
    """Workload-drift self-tuning scenario (docs/tuning.md "The drift
    gate"): one dashboard workload served by a FROZEN store (an
    operator-pinned cache-admission threshold), a SELF-TUNED store (the
    same pin, ``cache_min_cost`` controller armed) and an ORACLE store
    (the threshold an operator who had seen the drift coming would
    pick). Phase 1 is a steady hotspot whose scans cost more than the
    pinned threshold — all three serve repeats warm. Then the hotspot
    MOVES and the new queries' scans are cheaper than the pin: the
    frozen store stops admitting and re-scans every repeat, while the
    armed controller senses the hit collapse and relaxes the floor.
    Reported: the frozen store's own pre/post-drift QPS ratio, the
    oracle/tuned post-drift ratio, the recorded decisions, and the
    disarmed bit-identity flag. Emits BENCH_DRIFT.json (or
    GEOMESA_BENCH_DRIFT_OUT / ``out_path``); scripts/bench_gate.py's
    ``config_drift`` bounds are the teeth. Env knobs:
    GEOMESA_BENCH_DRIFT_N (points), GEOMESA_BENCH_DRIFT_QUERIES,
    GEOMESA_BENCH_DRIFT_REPS (measured passes per phase)."""
    from geomesa_tpu import conf as gconf
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.planning.explain import Explainer
    from geomesa_tpu.planning.hints import QueryHints
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_DRIFT_N", 1_000_000))
    n_q = int(os.environ.get("GEOMESA_BENCH_DRIFT_QUERIES", 12))
    reps = int(os.environ.get("GEOMESA_BENCH_DRIFT_REPS", 6))
    rng = np.random.default_rng(SEED + 90)
    log(f"[drift] building 3x {n:,} point stores ...")
    x = rng.uniform(-180.0, 180.0, n)
    y = rng.uniform(-90.0, 90.0, n)
    ids = np.arange(n)

    def build(min_cost_s, tuned=False):
        # the pin IS the knob: each store runs with its own
        # ``geomesa.cache.min.cost`` setting (the cache snapshots it at
        # build; the armed controller reads it live as the value it is
        # allowed to move). Stores run strictly sequentially — the
        # caller clears the knob after each store's run.
        gconf.CACHE_MIN_COST.set(float(min_cost_s))
        sft = FeatureType.from_spec("dash", "*geom:Point:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "z2"
        reg = MetricsRegistry()
        ds = DataStore(metrics=reg, cache=True)
        ds.create_schema(sft)
        ds.write("dash", FeatureCollection.from_columns(
            sft, ids, {"geom": (x, y)}), check_ids=False)
        mgr = None
        if tuned:
            # controller pulses ride the query path: twice per pass
            mgr = ds.attach_tuning(enabled=True, interval=max(1, n_q // 2))
        return ds, reg, mgr

    def star(cx, cy, r_out, r_in, points=60):
        # a concave 120-vertex star: the PIP refinement over its
        # candidates is a STRUCTURAL cost floor (vertex count x
        # candidate count), not a statistical one — scan-noise on a
        # shared host cannot push it near a plain bbox probe
        th = np.linspace(0.0, 2.0 * np.pi, 2 * points, endpoint=False)
        rr = np.where(np.arange(2 * points) % 2 == 0, r_out, r_in)
        xs, ys = cx + rr * np.cos(th), cy + rr * np.sin(th)
        coords = ", ".join(f"{a:.4f} {b:.4f}" for a, b in zip(xs, ys))
        return f"POLYGON(({coords}, {xs[0]:.4f} {ys[0]:.4f}))"

    # hotspot A: concave-polygon region dashboards — expensive scans
    # (device PIP over tens of k candidates). The drift moves the
    # dashboard to hotspot B: drill-down bboxes in the east whose
    # scans bottom out near the probe floor — far below any admission
    # threshold tuned for A.
    arng = np.random.default_rng(SEED + 91)
    qa = [
        f"INTERSECTS(geom, {star(float(arng.uniform(-130.0, -50.0)), float(arng.uniform(-35.0, 35.0)), 40.0, 18.0)})"
        for _ in range(n_q)
    ]
    brng = np.random.default_rng(SEED + 92)
    qb = []
    for _ in range(n_q):
        x0 = float(brng.uniform(5.0, 173.0))
        y0 = float(brng.uniform(-85.0, 84.0))
        qb.append(
            f"bbox(geom, {x0:.4f}, {y0:.4f}, {x0 + 1.5:.4f}, {y0 + 1.0:.4f})"
        )
    bypass = QueryHints(cache="bypass")

    # calibrate the operator's frozen pin between the two hotspots'
    # measured scan costs (machine-dependent), inside the controller's
    # [0, 50 ms] range
    probe_ds, _, _ = build(0.0)
    gconf.CACHE_MIN_COST.clear()
    for q in qa + qb:  # compile kernels off the clock
        probe_ds.query("dash", q, hints=bypass)

    def _cost(ds, queries):
        out = []
        for q in queries:
            s = time.perf_counter()
            ds.query("dash", q, hints=bypass)
            out.append(time.perf_counter() - s)
        return float(np.median(out))

    t_hi = _cost(probe_ds, qa)
    t_lo = _cost(probe_ds, qb)
    # split the measured costs: B scans must price BELOW the pin (the
    # frozen store stops admitting after the drift) and A scans above
    # it (the pin looked right when it was set). Geometric mean keeps
    # equal RELATIVE margins on both sides of the wide polygon-vs-bbox
    # gap; the controller's range caps the pin at 50 ms either way.
    thr = float(np.sqrt(max(t_lo, 1e-6) * max(t_hi, 1e-6)))
    if 1.25 * t_lo <= 0.8 * t_hi:
        thr = max(1.25 * t_lo, min(thr, 0.8 * t_hi))
    thr = min(thr, 0.05)
    log(f"[drift] scan cost: hotspot A {t_hi * 1e3:.1f}ms, "
        f"B {t_lo * 1e3:.1f}ms -> frozen pin {thr * 1e3:.1f}ms")
    if not (t_lo < thr < t_hi):  # pragma: no cover - host-dependent
        log("[drift] WARNING: could not place the pin between the "
            "hotspots' costs; the scenario premise is weak on this host")
    probe_ds.close()
    del probe_ds
    gc.collect()

    def qps(ds, queries, passes):
        t0 = time.perf_counter()
        for _ in range(passes):
            for q in queries:
                ds.query("dash", q)
        return (passes * len(queries)) / (time.perf_counter() - t0)

    def run(ds):
        for q in qa + qb:  # compile both hotspots off the clock
            ds.query("dash", q, hints=bypass)
        for _ in range(2):  # phase 1 populate
            for q in qa:
                ds.query("dash", q)
        pre = qps(ds, qa, reps)  # steady hotspot, served warm
        # the drift: the hotspot moves. Every store gets the same
        # adaptation window (the tuned one senses the hit collapse in
        # it; the frozen one just re-scans), then the same measurement.
        for _ in range(6):
            for q in qb:
                ds.query("dash", q)
        post = qps(ds, qb, reps)
        return pre, post

    results = {}
    decisions = []
    final_min_cost = None
    for name, min_cost, tuned in (
        ("frozen", thr, False), ("oracle", 0.0, False),
        ("tuned", thr, True),
    ):
        ds, reg, mgr = build(min_cost, tuned=tuned)
        try:
            pre, post = run(ds)
            results[name] = {
                "pin_ms": round(min_cost * 1e3, 3),
                "qps_pre": round(pre, 1),
                "qps_post": round(post, 1),
            }
            log(f"[drift] {name}: pre {pre:.0f} q/s -> post {post:.0f} q/s")
            if mgr is not None:
                rep = mgr.report()
                decisions = [
                    d for d in rep["decisions"]
                    if d.get("controller") == "cache_min_cost"
                ]
                final_min_cost = ds.cache.result.conf.min_cost_s
                results[name]["final_pin_ms"] = round(final_min_cost * 1e3, 3)
                results[name]["pulses"] = rep["pulses"]
            ds.close()
        finally:
            gconf.CACHE_MIN_COST.clear()
        del ds
        gc.collect()

    # the off switch: a DISARMED manager must leave a store
    # bit-identical to one without the tier (plans, explains, results)
    def small_store():
        sft = FeatureType.from_spec("dash", "*geom:Point:srid=4326")
        sft.user_data["geomesa.indices.enabled"] = "z2"
        ds = DataStore(metrics=MetricsRegistry(), cache=True)
        ds.create_schema(sft)
        k = min(n, 50_000)
        ds.write("dash", FeatureCollection.from_columns(
            sft, ids[:k], {"geom": (x[:k], y[:k])}), check_ids=False)
        return ds

    plain, disarmed = small_store(), small_store()
    disarmed.attach_tuning(enabled=False)

    def _strip(e):  # timing lines differ run to run; everything else may not
        return [l for l in e.lines if "ms" not in l]

    identical = True
    for q in (qa + qb)[:8]:
        e1, e2 = Explainer(), Explainer()
        r1 = plain.query("dash", q, explain=e1)
        r2 = disarmed.query("dash", q, explain=e2)
        if (
            not np.array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
            or _strip(e1) != _strip(e2)
        ):
            identical = False
    plain.close()
    disarmed.close()

    frozen_degradation = (
        results["frozen"]["qps_pre"]
        / max(results["frozen"]["qps_post"], 1e-9)
    )
    tuned_over_oracle = (
        results["oracle"]["qps_post"]
        / max(results["tuned"]["qps_post"], 1e-9)
    )
    row = {
        "scenario": "config_drift",
        "n_points": n,
        "n_queries": n_q,
        "reps": reps,
        "pin_ms": round(thr * 1e3, 3),
        "hotspot_scan_ms": {
            "pre": round(t_hi * 1e3, 3), "post": round(t_lo * 1e3, 3),
        },
        "frozen": results["frozen"],
        "oracle": results["oracle"],
        "tuned": results["tuned"],
        "frozen_degradation": round(frozen_degradation, 3),
        "tuned_over_oracle": round(tuned_over_oracle, 3),
        "decisions_recorded": len(decisions),
        "decisions": decisions[:8],
        "disarmed_identical": identical,
        "identical": identical,
    }
    log(f"[drift] frozen degraded {frozen_degradation:.1f}x; tuned holds "
        f"{1 / max(tuned_over_oracle, 1e-9):.2f}x of oracle; "
        f"{len(decisions)} decisions; disarmed identical: {identical}")

    import jax

    payload = {"platform": jax.default_backend(), "rows": [row]}
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_DRIFT_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_DRIFT.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "drift_frozen_degradation",
        "value": round(frozen_degradation, 3),
        "unit": "x",
        "tuned_over_oracle": round(tuned_over_oracle, 3),
        "decisions_recorded": len(decisions),
        "disarmed_identical": identical,
        "n_points": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ----------------------------------------------------- serving scenario


def config_serving(out_path: "str | None" = None):
    """Concurrent query serving scenario (docs/serving.md): QPS and
    p50/p99 latency at 8/32/128 concurrent clients, the micro-batch
    scheduler vs the naive per-thread ``execute()`` baseline, reporting
    the mean fused batch size. Emits BENCH_SERVING.json next to this
    file (or at ``out_path``). CPU-runnable. Env knobs:
    GEOMESA_BENCH_SERVING_N (points), GEOMESA_BENCH_SERVING_CLIENTS
    (comma list), GEOMESA_BENCH_SERVING_Q (target total queries per
    client count)."""
    import threading

    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_SERVING_N", 2_000_000))
    clients_list = [
        int(c) for c in os.environ.get(
            "GEOMESA_BENCH_SERVING_CLIENTS", "8,32,128"
        ).split(",")
    ]
    total_q = int(os.environ.get("GEOMESA_BENCH_SERVING_Q", 384))
    rng = np.random.default_rng(SEED + 70)
    log(f"[serving] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)
    sft = FeatureType.from_spec("srv", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg)
    ds.create_schema(sft)
    ds.write("srv", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (x, y)}), check_ids=False)

    # distinct city-scale boxes (small results: per-query overhead is
    # what serving amortizes), one disjoint slice per client
    qrng = np.random.default_rng(SEED + 71)
    def qbox():
        w = float(qrng.choice([0.5, 1.0, 2.0]))
        qx = qrng.uniform(-175, 175 - w)
        qy = qrng.uniform(-85, 85 - w / 2)
        return f"bbox(geom, {qx:.4f}, {qy:.4f}, {qx + w:.4f}, {qy + w / 2:.4f})"

    pool = [qbox() for _ in range(total_q)]
    for q in pool[:8]:  # compile the single-query variants
        ds.query("srv", q)
    ds.query_many("srv", pool[:8])  # compile the fused chunk variant
    for q in pool:  # warm the scan-config memo for BOTH runs (fairness)
        ds.planner.plan("srv", q)

    def run(clients, body):
        """``clients`` threads, each running ``body`` over its slice;
        returns (per-query latencies, total hits, wall seconds)."""
        per = max(1, total_q // clients)
        lat: list[float] = []
        hits = [0]
        lock = threading.Lock()
        start = threading.Barrier(clients + 1)

        def worker(qs):
            loc, h = [], 0
            start.wait()
            for q in qs:
                s = time.perf_counter()
                h += body(q)
                loc.append(time.perf_counter() - s)
            with lock:
                lat.extend(loc)
                hits[0] += h

        threads = [
            threading.Thread(target=worker, args=(pool[i * per:(i + 1) * per],))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        return np.array(lat), hits[0], time.perf_counter() - t0

    rows = []
    for clients in clients_list:
        nl, nh, nw = run(clients, lambda q: len(ds.query("srv", q)))
        sched = ds.serve()
        b0 = reg.counters.get("geomesa.serving.batches", 0)
        q0 = reg.counters.get("geomesa.serving.batched_queries", 0)
        c0 = reg.counters.get("geomesa.serving.coalesced", 0)
        sl, sh, sw = run(clients, lambda q: len(sched.query("srv", q)))
        sched.close()
        b1 = reg.counters.get("geomesa.serving.batches", 0)
        q1 = reg.counters.get("geomesa.serving.batched_queries", 0)
        c1 = reg.counters.get("geomesa.serving.coalesced", 0)
        assert nh == sh, (nh, sh)  # scheduler results == naive results
        mean_batch = (q1 - q0) / max(b1 - b0, 1)
        row = {
            "clients": clients,
            "queries": len(nl),
            "hits_total": int(nh),
            "naive": {
                "qps": round(len(nl) / nw, 1),
                "p50_ms": round(float(np.percentile(nl * 1e3, 50)), 3),
                "p99_ms": round(float(np.percentile(nl * 1e3, 99)), 3),
            },
            "scheduler": {
                "qps": round(len(sl) / sw, 1),
                "p50_ms": round(float(np.percentile(sl * 1e3, 50)), 3),
                "p99_ms": round(float(np.percentile(sl * 1e3, 99)), 3),
                "mean_fused_batch": round(mean_batch, 2),
                "coalesced": c1 - c0,
            },
        }
        row["speedup"] = round(
            row["scheduler"]["qps"] / max(row["naive"]["qps"], 1e-9), 2
        )
        rows.append(row)
        log(
            f"[serving] {clients} clients: scheduler {row['scheduler']['qps']}"
            f" qps vs naive {row['naive']['qps']} qps "
            f"({row['speedup']}x), mean fused batch {mean_batch:.1f}"
        )

    import jax

    payload = {
        "n_points": n,
        "platform": jax.default_backend(),
        "rows": rows,
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SERVING.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    headline = min(rows, key=lambda r: abs(r["clients"] - 32))
    rec = {
        "metric": "serving_scheduler_qps_32_clients",
        "value": headline["scheduler"]["qps"],
        "unit": "queries/s",
        "vs_baseline": headline["speedup"],
        "naive_qps": headline["naive"]["qps"],
        "mean_fused_batch": headline["scheduler"]["mean_fused_batch"],
        "latency_p50_ms": headline["scheduler"]["p50_ms"],
        "latency_p99_ms": headline["scheduler"]["p99_ms"],
        "n_points": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------ observability scenario


def config_obs(out_path: "str | None" = None):
    """Observability overhead + fidelity scenario (docs/observability.md):
    serving QPS through the scheduler with tracing OFF (both arming
    knobs 0 — the disarmed no-op check), SAMPLED (1/64) and FULL
    (every root), on identical query pools; plus (a) live-histogram
    p99 vs the offline numpy percentile of the same latencies, and
    (b) a captured slow-query trace of a fused batched query whose
    top-level phases must cover the root wall. Emits BENCH_OBS.json
    (or ``out_path``; env GEOMESA_BENCH_OBS_OUT), gated by
    scripts/bench_gate.py. CPU-runnable. Env knobs:
    GEOMESA_BENCH_OBS_N (points), GEOMESA_BENCH_OBS_CLIENTS,
    GEOMESA_BENCH_OBS_Q (total queries per mode)."""
    import threading

    from geomesa_tpu import conf, obs
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import HIST_EDGES, MetricsRegistry
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_OBS_N", 2_000_000))
    clients = int(os.environ.get("GEOMESA_BENCH_OBS_CLIENTS", 4))
    total_q = int(os.environ.get("GEOMESA_BENCH_OBS_Q", 1024))
    out_path = out_path or os.environ.get("GEOMESA_BENCH_OBS_OUT")
    rng = np.random.default_rng(SEED + 90)
    log(f"[obs] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)
    sft = FeatureType.from_spec("srv", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg)
    ds.create_schema(sft)
    ds.write("srv", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (x, y)}), check_ids=False)

    qrng = np.random.default_rng(SEED + 91)

    def qbox():
        w = float(qrng.choice([0.5, 1.0, 2.0]))
        qx = qrng.uniform(-175, 175 - w)
        qy = qrng.uniform(-85, 85 - w / 2)
        return f"bbox(geom, {qx:.4f}, {qy:.4f}, {qx + w:.4f}, {qy + w / 2:.4f})"

    pool = [qbox() for _ in range(total_q)]
    for q in pool[:8]:
        ds.query("srv", q)
    ds.query_many("srv", pool[:8])
    for q in pool:
        ds.planner.plan("srv", q)

    def run_clients(body):
        per = max(1, total_q // clients)
        lat: list[float] = []
        hits = [0]
        lock = threading.Lock()
        start = threading.Barrier(clients + 1)

        def worker(qs):
            loc, h = [], 0
            start.wait()
            for q in qs:
                s = time.perf_counter()
                h += body(q)
                loc.append(time.perf_counter() - s)
            with lock:
                lat.extend(loc)
                hits[0] += h

        threads = [
            threading.Thread(target=worker, args=(pool[i * per:(i + 1) * per],))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        return np.array(lat), hits[0], time.perf_counter() - t0

    def arm(sample, slow_ms):
        conf.OBS_TRACE_SAMPLE.set(sample)
        conf.OBS_SLOW_MS.set(slow_ms)
        obs.install(obs.Tracer())

    modes = {"off": (0, 0.0), "sampled": (64, 0.0), "full": (1, 0.0)}
    results: dict = {}
    hits_by_mode: dict = {}
    try:
        # untimed warm pass: compiles every fused batch-size variant the
        # concurrent load will hit, so mode ordering cannot bias the
        # overhead ratios
        arm(0, 0.0)
        sched = ds.serve()
        run_clients(lambda q: len(sched.query("srv", q)))
        sched.close()
        # median-of-5 per mode, modes INTERLEAVED round-robin so slow
        # machine drift (thermal, page cache) hits every mode equally
        # instead of biasing whichever ran last; the median (not the
        # best) is the robust center the overhead ratios divide
        runs: dict = {m: [] for m in modes}
        for _rep in range(5):
            for mode, (sample, slow_ms) in modes.items():
                arm(sample, slow_ms)
                sched = ds.serve()
                lat, hits, wall = run_clients(
                    lambda q: len(sched.query("srv", q))
                )
                sched.close()
                runs[mode].append({
                    "qps": round(len(lat) / wall, 1),
                    "p50_ms": round(float(np.percentile(lat * 1e3, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat * 1e3, 99)), 3),
                    "traces_retained": len(obs.tracer().traces()),
                })
                hits_by_mode[mode] = hits
        for mode in modes:
            ordered = sorted(runs[mode], key=lambda r: r["qps"])
            results[mode] = dict(ordered[len(ordered) // 2])
            results[mode]["qps_runs"] = [r["qps"] for r in runs[mode]]
            log(
                f"[obs] {mode}: {results[mode]['qps']} qps median of "
                f"{results[mode]['qps_runs']}"
            )

        # -- live histogram p99 vs offline percentile (same latencies) --
        arm(0, 0.0)
        hreg = MetricsRegistry()
        ds.metrics = hreg
        offline: list[float] = []
        for q in pool:
            plan = ds.planner.plan("srv", q)
            t0 = time.perf_counter()
            ds.planner.execute(plan)
            offline.append(time.perf_counter() - t0)
        hist_p99 = hreg.histogram_quantile("geomesa.query.scan", 0.99)
        off_p99 = float(np.percentile(offline, 99))
        from bisect import bisect_left

        bucket_delta = abs(
            bisect_left(HIST_EDGES, hist_p99) - bisect_left(HIST_EDGES, off_p99)
        )
        ds.metrics = reg

        # -- slow-query capture of a fused batched query ----------------
        arm(0, 0.0001)  # always-slow threshold: every root captures
        sched = ds.serve()
        burst = pool[:32]
        futs = [sched.submit("srv", q) for q in burst]
        for f in futs:
            f.result(60)
        sched.close()
        slow = obs.tracer().slow_queries()
        serving = [
            e for e in slow
            if any(
                s["name"] == "dispatch" for s in e["trace"]["spans"]
            )
        ]
        entry = serving[-1]
        top = [
            s for s in entry["trace"]["spans"]
            if s["parent_id"] is not None and any(
                r["span_id"] == s["parent_id"] and r["parent_id"] is None
                for r in entry["trace"]["spans"]
            )
        ]
        phase_names = {s["name"] for s in top}
        cover = sum(s["dur_ms"] for s in top) / max(entry["wall_ms"], 1e-9)
        slow_trace = {
            "n_phases": len(phase_names),
            "phases": sorted(phase_names),
            "wall_ms": entry["wall_ms"],
            "phase_cover": round(min(cover, 1.0), 4),
            "fingerprint_strategy": entry["fingerprint"].get("strategy"),
        }
        log(
            f"[obs] slow trace: {slow_trace['n_phases']} phases, "
            f"cover {slow_trace['phase_cover']:.3f}"
        )
    finally:
        conf.OBS_TRACE_SAMPLE.clear()
        conf.OBS_SLOW_MS.clear()
        obs.install(obs.Tracer())

    identical = hits_by_mode["off"] == hits_by_mode["sampled"] == hits_by_mode["full"]
    row = {
        "scenario": "serving_obs",
        "clients": clients,
        "queries": total_q,
        "hits_total": int(hits_by_mode["off"]),
        "identical": bool(identical),
        "off": results["off"],
        "sampled": results["sampled"],
        "full": results["full"],
        "sampled_over_off": round(
            results["sampled"]["qps"] / max(results["off"]["qps"], 1e-9), 4
        ),
        "full_over_off": round(
            results["full"]["qps"] / max(results["off"]["qps"], 1e-9), 4
        ),
        "hist_p99": {
            "live_ms": round(hist_p99 * 1e3, 3),
            "offline_ms": round(off_p99 * 1e3, 3),
            "bucket_delta": int(bucket_delta),
        },
        "slow_trace": slow_trace,
    }
    # disarmed overhead vs the committed serving baseline, when the
    # scales match (same points, a row at the same client count)
    try:
        base = json.load(open(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_SERVING.json"
        )))
        if base.get("n_points") == n:
            for brow in base.get("rows", []):
                if brow.get("clients") == clients:
                    row["off_over_serving_baseline"] = round(
                        results["off"]["qps"]
                        / max(brow["scheduler"]["qps"], 1e-9), 4
                    )
    except (OSError, ValueError, KeyError):
        pass

    import jax

    payload = {
        "n_points": n,
        "platform": jax.default_backend(),
        "rows": [row],
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_OBS.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "obs_sampled_over_off_qps_ratio",
        "value": row["sampled_over_off"],
        "unit": "ratio",
        "off_qps": results["off"]["qps"],
        "sampled_qps": results["sampled"]["qps"],
        "full_qps": results["full"]["qps"],
        "hist_p99_bucket_delta": row["hist_p99"]["bucket_delta"],
        "slow_trace_phases": slow_trace["n_phases"],
        "slow_trace_cover": slow_trace["phase_cover"],
        "n_points": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ----------------------------------------------------- ops-plane scenario


def config_ops(out_path: "str | None" = None):
    """Ops-plane scenario (docs/observability.md "The ops plane"):
    sustained serving QPS with and without a 1 Hz ``/metrics`` +
    ``/health`` HTTP scraper attached (interleaved reps, median), the
    estimate-vs-actual recording coverage over every executed scan,
    and the stale-stats loop demonstrated end to end on a store
    mutated through the accumulate-only fold path WITHOUT re-analyzing
    (flag raised), then cleared by ``analyze_stats``. Emits
    BENCH_OPS_PLANE.json (or ``out_path``; env
    GEOMESA_BENCH_OPS_PLANE_OUT), gated by scripts/bench_gate.py.
    CPU-runnable. Env knobs: GEOMESA_BENCH_OPS_PLANE_N (points),
    GEOMESA_BENCH_OPS_PLANE_CLIENTS, GEOMESA_BENCH_OPS_PLANE_Q
    (queries per rep)."""
    import threading
    import urllib.request

    from geomesa_tpu import conf as _conf
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.obs.ops import HealthMonitor
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_OPS_PLANE_N", 1_000_000))
    clients = int(os.environ.get("GEOMESA_BENCH_OPS_PLANE_CLIENTS", 4))
    total_q = int(os.environ.get("GEOMESA_BENCH_OPS_PLANE_Q", 768))
    out_path = out_path or os.environ.get("GEOMESA_BENCH_OPS_PLANE_OUT")
    rng = np.random.default_rng(SEED + 95)
    log(f"[ops] building {n:,} point store ...")
    x, y = gdelt_points(n, rng)
    sft = FeatureType.from_spec("srv", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    reg = MetricsRegistry()
    ds = DataStore(metrics=reg)
    ds.create_schema(sft)
    ds.write("srv", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (x, y)}), check_ids=False)

    qrng = np.random.default_rng(SEED + 96)

    def qbox():
        w = float(qrng.choice([0.5, 1.0, 2.0]))
        qx = qrng.uniform(-175, 175 - w)
        qy = qrng.uniform(-85, 85 - w / 2)
        return f"bbox(geom, {qx:.4f}, {qy:.4f}, {qx + w:.4f}, {qy + w / 2:.4f})"

    pool = [qbox() for _ in range(total_q)]
    for q in pool[:8]:
        ds.query("srv", q)
    ds.query_many("srv", pool[:8])
    for q in pool:
        ds.planner.plan("srv", q)

    def run_clients(sched):
        per = max(1, total_q // clients)
        hits = [0]
        lock = threading.Lock()
        start = threading.Barrier(clients + 1)

        def worker(qs):
            h = 0
            start.wait()
            for q in qs:
                h += len(sched.query("srv", q))
            with lock:
                hits[0] += h

        threads = [
            threading.Thread(target=worker, args=(pool[i * per:(i + 1) * per],))
            for i in range(clients)
        ]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return clients * per / wall, hits[0], wall

    # untimed warm pass: compiles every fused batch-size variant
    sched = ds.serve()
    run_clients(sched)
    sched.close()

    srv = ds.serve_ops()
    scrapes_before = reg.counter_value("geomesa.obs.ops.scrapes")

    def run_mode(scraped: bool):
        sched = ds.serve()
        stop = threading.Event()
        scraper = None
        scrape_errs: list = []
        if scraped:
            def scrape_loop():
                # the 1 Hz operator: one /metrics + /health round per
                # second while the serving load runs (at least one
                # round even on a sub-second rep). Errors propagate —
                # a silently dead scraper would measure an UNSCRAPED
                # run and pass the overhead gate vacuously.
                try:
                    while True:
                        for path in ("/metrics", "/health"):
                            urllib.request.urlopen(
                                srv.url + path, timeout=30
                            ).read()
                        if stop.wait(1.0):
                            return
                except BaseException as e:
                    scrape_errs.append(e)

            scraper = threading.Thread(target=scrape_loop)
            scraper.start()
        try:
            qps, hits, wall = run_clients(sched)
        finally:
            stop.set()
            if scraper is not None:
                scraper.join()
            sched.close()
        if scrape_errs:
            raise RuntimeError(f"ops scraper died: {scrape_errs[0]!r}")
        return {"qps": round(qps, 1), "wall_s": round(wall, 2)}, hits

    # interleaved reps, median by qps (the config_obs convention: slow
    # host drift hits both modes equally)
    runs = {"unscraped": [], "scraped": []}
    hits_by_mode = {}
    for _rep in range(5):
        for mode, scraped in (("unscraped", False), ("scraped", True)):
            r, hits = run_mode(scraped)
            runs[mode].append(r)
            hits_by_mode[mode] = hits
    results = {}
    for mode in runs:
        ordered = sorted(runs[mode], key=lambda r: r["qps"])
        results[mode] = dict(ordered[len(ordered) // 2])
        results[mode]["qps_runs"] = [r["qps"] for r in runs[mode]]
        log(f"[ops] {mode}: {results[mode]['qps']} qps median of "
            f"{results[mode]['qps_runs']}")
    n_scrapes = reg.counter_value("geomesa.obs.ops.scrapes") - scrapes_before
    # belt + braces on top of the scraper error propagation: every
    # scraped rep makes at least one /metrics + /health round
    if n_scrapes < 2 * len(runs["scraped"]):
        raise RuntimeError(
            f"only {n_scrapes} scrapes over {len(runs['scraped'])} scraped "
            "reps — the scraped mode did not actually scrape"
        )

    # -- estimate coverage over the whole serving phase ------------------
    executed = reg.counter_value("geomesa.query.count")
    recorded = ds.accuracy.sample_count()
    coverage = recorded / max(executed, 1)
    log(f"[ops] estimates recorded for {recorded}/{executed} scans "
        f"({coverage:.4f})")

    # -- the stale-stats loop, demonstrated ------------------------------
    # a deliberately mutated-WITHOUT-analyze store: every row moves far
    # away through the accumulate-only fold path (docs/streaming.md's
    # documented sketch drift), so the sketches keep claiming the old
    # region is dense while scans there come back empty
    _conf.PLAN_ESTIMATE_MIN_COUNT.set(16)
    mut = np.random.default_rng(SEED + 97)
    move_n = 100_000
    mds = DataStore(metrics=MetricsRegistry())
    msft = FeatureType.from_spec("mut", "*geom:Point:srid=4326")
    msft.user_data["geomesa.indices.enabled"] = "z2"
    mds.create_schema(msft)
    mds.write("mut", FeatureCollection.from_columns(
        msft, np.arange(move_n),
        {"geom": (mut.uniform(-50, 50, move_n), mut.uniform(-50, 50, move_n))},
    ), check_ids=False)
    mds.fold_upsert("mut", FeatureCollection.from_columns(
        msft, np.arange(move_n),
        {"geom": (mut.uniform(100, 140, move_n), mut.uniform(60, 85, move_n))},
    ))
    mon = HealthMonitor(mds)
    stale_probe = [
        f"bbox(geom, {qx:.2f}, {qy:.2f}, {qx + 4:.2f}, {qy + 4:.2f})"
        for qx, qy in zip(
            mut.uniform(-48, 44, 24), mut.uniform(-48, 44, 24)
        )
    ]  # the vacated region: estimates stay high, scans come back empty
    for q in stale_probe:
        mds.query("mut", q)
    report = mon.evaluate()
    stale_demonstrated = int(any(
        r["reason"] == "stats.stale" for r in report["reasons"]
    ))
    log(f"[ops] stale flagged: {bool(stale_demonstrated)} "
        f"({[r['reason'] for r in report['reasons']]})")
    # the documented remedy clears it
    mds.analyze_stats("mut")
    mds.accuracy.reset("mut")
    for q in stale_probe:
        mds.query("mut", q)
    report = mon.evaluate()
    stale_cleared = int(not any(
        r["reason"] == "stats.stale" for r in report["reasons"]
    ))
    log(f"[ops] stale cleared by analyze_stats: {bool(stale_cleared)}")
    _conf.PLAN_ESTIMATE_MIN_COUNT.clear()
    srv.close()

    row = {
        "scenario": "ops_plane",
        "clients": clients,
        "queries_per_rep": total_q,
        "identical": bool(
            hits_by_mode["unscraped"] == hits_by_mode["scraped"]
        ),
        "unscraped": results["unscraped"],
        "scraped": results["scraped"],
        "qps_unscraped": results["unscraped"]["qps"],
        "qps_scraped": results["scraped"]["qps"],
        "scraped_over_unscraped": round(
            results["scraped"]["qps"]
            / max(results["unscraped"]["qps"], 1e-9), 4
        ),
        "scrapes": int(n_scrapes),
        "estimate_coverage": round(coverage, 4),
        "estimates_recorded": int(recorded),
        "scans_executed": int(executed),
        "stale_demonstrated": stale_demonstrated,
        "stale_cleared": stale_cleared,
    }

    import jax

    payload = {
        "n_points": n,
        "platform": jax.default_backend(),
        "rows": [row],
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_OPS_PLANE.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "ops_scraped_over_unscraped_qps_ratio",
        "value": row["scraped_over_unscraped"],
        "unit": "ratio",
        "unscraped_qps": row["qps_unscraped"],
        "scraped_qps": row["qps_scraped"],
        "scrapes": row["scrapes"],
        "estimate_coverage": row["estimate_coverage"],
        "stale_demonstrated": row["stale_demonstrated"],
        "stale_cleared": row["stale_cleared"],
        "n_points": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ----------------------------------------------------- fused scenario


def config_fused(out_path: "str | None" = None):
    """Fused-coverage scenario (docs/serving.md "Fused coverage",
    PERF.md §12): the round-6 fusion tiers — (a) an XZ2 extent table's
    box batch (wide-only plane layout), (b) a z2 polygon-INTERSECTS
    batch through the fused device-PIP edge stacks, (c) a mesh-sharded
    z2 box+polygon batch under shard_map (skipped below 2 devices) —
    each timed FUSED (one `scan_submit_many` dispatch set) vs PER-QUERY
    (serialized `scan_submit` dispatch+pull, what independent callers
    pay), with bit-identity asserted between the paths on every leg.
    Emits BENCH_FUSED.json next to this file (or at ``out_path``).
    CPU-runnable. Env knobs: GEOMESA_BENCH_FUSED_N (rows per table),
    GEOMESA_BENCH_FUSED_Q (queries per batch),
    GEOMESA_BENCH_FUSED_REPEAT (timing repeats, best-of)."""
    import jax

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.filter.predicates import BBox, Intersects
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_FUSED_N", 2_000_000))
    n_q = int(os.environ.get("GEOMESA_BENCH_FUSED_Q", 32))
    repeat = int(os.environ.get("GEOMESA_BENCH_FUSED_REPEAT", 5))
    rng = np.random.default_rng(SEED + 80)

    def star(cx, cy, r, n_arms=5):
        a = np.linspace(0, 2 * np.pi, 2 * n_arms + 1)[:-1]
        rad = np.where(np.arange(2 * n_arms) % 2 == 0, r, 0.4 * r)
        return geo.Polygon(
            [(cx + rr * np.cos(t), cy + rr * np.sin(t)) for t, rr in zip(a, rad)]
        )

    def time_paths(table, cfgs, label):
        """(row dict) fused vs per-query dispatch over the same configs,
        best-of-``repeat``, bit-identity asserted. Two baselines:
        ``per_query_ms`` serializes dispatch+pull (what independent
        callers pay); ``pipelined_ms`` dispatches every query before any
        pull (the pre-round-6 scan_submit_many fallback these configs
        used to take) — the honest "before" of the fusion PR."""
        seq = [table.scan_submit(c)() for c in cfgs]  # warm single-query
        fus = [f() for f in table.scan_submit_many(list(cfgs))]  # warm fused
        identical = all(
            np.array_equal(ra, rb) and np.array_equal(ca, cb)
            for (ra, ca), (rb, cb) in zip(seq, fus)
        )
        assert identical, label  # recorded either way (python -O safe)
        t_seq = min(
            _timed(lambda: [table.scan_submit(c)() for c in cfgs])
            for _ in range(repeat)
        )
        t_pipe = min(
            _timed(lambda: [f() for f in [table.scan_submit(c) for c in cfgs]])
            for _ in range(repeat)
        )
        t_fus = min(
            _timed(lambda: [f() for f in table.scan_submit_many(list(cfgs))])
            for _ in range(repeat)
        )
        row = {
            "scenario": label,
            "queries": len(cfgs),
            "per_query_ms": round(t_seq / len(cfgs) * 1e3, 3),
            "pipelined_ms": round(t_pipe / len(cfgs) * 1e3, 3),
            "fused_ms": round(t_fus / len(cfgs) * 1e3, 3),
            "speedup": round(t_seq / max(t_fus, 1e-9), 2),
            "speedup_vs_pipelined": round(t_pipe / max(t_fus, 1e-9), 2),
            "identical": identical,
        }
        log(
            f"[fused] {label}: {row['per_query_ms']} ms/q per-query / "
            f"{row['pipelined_ms']} ms/q pipelined vs "
            f"{row['fused_ms']} ms/q fused = {row['speedup']}x "
            f"({row['speedup_vs_pipelined']}x vs pipelined)"
        )
        return row

    def _timed(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    rows = []

    # -- (a) XZ2 extent box batch ---------------------------------------
    log(f"[fused] building {n:,}-extent xz2 store ...")
    ex0, ey0 = gdelt_points(n, rng)
    exts = geo.PackedGeometryColumn.from_boxes(
        ex0, ey0,
        ex0 + rng.uniform(0.005, 0.5, n).astype(ex0.dtype),
        ey0 + rng.uniform(0.005, 0.4, n).astype(ey0.dtype),
    )
    sft_x = FeatureType.from_spec("fx", "*geom:Polygon:srid=4326")
    sft_x.user_data["geomesa.indices.enabled"] = "xz2"
    ds = DataStore()
    ds.create_schema(sft_x)
    ds.write("fx", FeatureCollection.from_columns(
        sft_x, np.arange(n), {"geom": exts}), check_ids=False)
    idx = next(i for i in ds.indexes("fx") if i.name == "xz2")
    qrng = np.random.default_rng(SEED + 81)

    def small_box():
        w = float(qrng.choice([0.5, 1.0, 2.0]))
        qx = qrng.uniform(-170, 170 - w)
        qy = qrng.uniform(-80, 80 - w / 2)
        return BBox("geom", qx, qy, qx + w, qy + w / 2)

    cfgs = [idx.scan_config(small_box()) for _ in range(n_q)]
    rows.append(time_paths(ds.table("fx", "xz2"), cfgs, "xz2_box_batch"))

    # -- (b) z2 polygon-INTERSECTS (device PIP) batch -------------------
    log(f"[fused] building {n:,}-point z2 store ...")
    px, py = gdelt_points(n, rng)
    sft_p = FeatureType.from_spec("fp", "*geom:Point:srid=4326")
    sft_p.user_data["geomesa.indices.enabled"] = "z2"
    ds.create_schema(sft_p)
    ds.write("fp", FeatureCollection.from_columns(
        sft_p, np.arange(n), {"geom": (px, py)}), check_ids=False)
    idx_p = next(i for i in ds.indexes("fp") if i.name == "z2")
    cfgs = [
        idx_p.scan_config(Intersects("geom", star(
            float(qrng.uniform(-150, 150)), float(qrng.uniform(-70, 70)),
            float(qrng.choice([0.5, 1.0, 2.0])),
            n_arms=int(qrng.choice([4, 5, 8])),
        )))
        for _ in range(n_q)
    ]
    # the polygon tier: PIP edges pre-round-7, raster intervals (with
    # host residue) by default since — either way a device polygon leg
    assert all(
        c is not None and (c.poly is not None or c.rast is not None)
        for c in cfgs
    )
    rows.append(time_paths(ds.table("fp", "z2"), cfgs, "z2_polygon_pip_batch"))

    # -- (c) mesh-sharded box+polygon batch -----------------------------
    n_dev = len(jax.devices())
    if n_dev >= 2:
        from geomesa_tpu.parallel import make_mesh

        log(f"[fused] building mesh{n_dev} z2 store ...")
        ds_m = DataStore(mesh=make_mesh(n_dev))
        ds_m.create_schema(sft_p)
        ds_m.write("fp", FeatureCollection.from_columns(
            sft_p, np.arange(n), {"geom": (px, py)}), check_ids=False)
        idx_m = next(i for i in ds_m.indexes("fp") if i.name == "z2")
        cfgs = []
        for k in range(n_q):
            if k % 3 == 0:
                cfgs.append(idx_m.scan_config(Intersects("geom", star(
                    float(qrng.uniform(-150, 150)), float(qrng.uniform(-70, 70)),
                    1.0,
                ))))
            else:
                cfgs.append(idx_m.scan_config(small_box()))
        rows.append(time_paths(
            ds_m.table("fp", "z2"), cfgs, f"mesh{n_dev}_mixed_batch"
        ))
    else:
        log("[fused] mesh leg skipped: single device")
        rows.append({"scenario": "mesh_mixed_batch", "skipped": "single device"})

    payload = {
        "n_rows": n,
        "queries_per_batch": n_q,
        "platform": jax.default_backend(),
        "rows": rows,
    }
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_FUSED.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    timed = [r for r in rows if "speedup" in r]
    rec = {
        "metric": "fused_coverage_min_speedup",
        "value": min(r["speedup"] for r in timed),
        "unit": "x",
        "min_vs_pipelined": min(r["speedup_vs_pipelined"] for r in timed),
        "rows": rows,
        "n_rows": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------- raster PIP + join scenario


def config_pip_join(out_path: "str | None" = None):
    """Raster-interval polygon approximations + adaptive joins (round 7,
    docs/joins.md, PERF.md §13): the polygon-heavy workloads the raster
    tier targets, each measured with rasters OFF (the round-6 exact
    device-PIP path) vs ON, end-to-end (fused kernel batch + host
    residue refinement), with bit-identity of the refined hit sets
    computed in-bench —

    - ``z2_polygon_pip_batch``: 32 concave polygon-INTERSECTS queries
      (16..256-edge jagged stars) over an n-point z2 store, one fused
      scan_submit_many dispatch set per batch;
    - ``z2_polygon_join``: spatial_join_indexed over 128 concave
      polygons (the broadcast-join shape with a non-rectangular left
      side);
    - ``host_grid_join``: the storeless grid join, exact vs adaptive
      (sampled-selectivity raster strategy).

    Emits BENCH_PIP_JOIN.json next to this file (or at ``out_path`` /
    env GEOMESA_BENCH_PIP_OUT — use a SCRATCH path when producing the
    fresh side of a gate comparison, so the committed baseline is not
    clobbered); ``scripts/bench_gate.py`` compares a fresh run against
    the recorded baseline and fails on >20% fused-PIP regression. Env
    knobs: GEOMESA_BENCH_PIP_N (rows), GEOMESA_BENCH_PIP_Q
    (queries/batch), GEOMESA_BENCH_PIP_REPEAT (best-of)."""
    import jax

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.conf import RASTER_ENABLED
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.filter import raster as fr
    from geomesa_tpu.filter.predicates import Intersects
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.sql.join import spatial_join, spatial_join_indexed

    n = int(os.environ.get("GEOMESA_BENCH_PIP_N", 2_000_000))
    n_q = int(os.environ.get("GEOMESA_BENCH_PIP_Q", 32))
    repeat = int(os.environ.get("GEOMESA_BENCH_PIP_REPEAT", 3))
    rng = np.random.default_rng(SEED + 90)

    def jagged(cx, cy, r, n_arms, seed):
        srng = np.random.default_rng(seed)
        a = np.linspace(0, 2 * np.pi, 2 * n_arms + 1)[:-1]
        rad = np.where(
            np.arange(2 * n_arms) % 2 == 0, r,
            r * srng.uniform(0.3, 0.7, 2 * n_arms),
        )
        return geo.Polygon(
            [(cx + rr * np.cos(t), cy + rr * np.sin(t)) for t, rr in zip(a, rad)]
        )

    log(f"[pip_join] building {n:,}-point z2 store ...")
    px, py = gdelt_points(n, rng)
    sft = FeatureType.from_spec("fp", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("fp", FeatureCollection.from_columns(
        sft, np.arange(n), {"geom": (px, py)}), check_ids=False)
    idx = next(i for i in ds.indexes("fp") if i.name == "z2")
    table = ds.table("fp", "z2")
    qrng = np.random.default_rng(SEED + 91)
    # the issue's workload: up-to-256-edge polygon stacks (arms 8..127
    # -> 16..254 edges, every fused E bucket incl. the XLA ladder top)
    polys = [
        jagged(
            float(qrng.uniform(-150, 150)), float(qrng.uniform(-60, 60)),
            float(qrng.choice([0.5, 1.0, 2.0])),
            int(qrng.choice([8, 16, 50, 127])), seed=k,
        )
        for k in range(n_q)
    ]

    def _timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    def resolve_batch(cfgs):
        """Fused batch + exact host residue refinement -> per-query
        sorted true-hit ordinal arrays (what the planner produces)."""
        outs = [f() for f in table.scan_submit_many(list(cfgs))]
        final = []
        for p, (rows, cert) in zip(polys, outs):
            unc = np.flatnonzero(~cert)
            keep = cert.copy()
            if len(unc):
                ux, uy = px[rows[unc]], py[rows[unc]]
                ok = geo.points_in_polygon(ux, uy, p)
                nb = np.flatnonzero(~ok)  # intersects: boundary counts
                if len(nb):
                    ok[nb] = geo.points_on_boundary(ux[nb], uy[nb], p)
                keep[unc] = ok
            final.append(np.sort(rows[keep]))
        return final

    def run_batch(label):
        ds.planner.invalidate_config_memo()
        fr.clear_cache()
        cfgs = [idx.scan_config(Intersects("geom", p)) for p in polys]
        resolve_batch(cfgs)  # warm compiles
        best = min(_timed(lambda: resolve_batch(cfgs))[0] for _ in range(repeat))
        final = resolve_batch(cfgs)
        log(f"[pip_join] {label}: {best / n_q * 1e3:.2f} ms/q")
        return best, final, cfgs

    RASTER_ENABLED.set(False)
    t_off, final_off, cfgs_off = run_batch("exact (raster off)")
    RASTER_ENABLED.set(None)
    t_on, final_on, cfgs_on = run_batch("raster on")
    identical = all(
        np.array_equal(a, b) for a, b in zip(final_off, final_on)
    )
    assert identical  # recorded either way (python -O safe)
    rows = [{
        "scenario": "z2_polygon_pip_batch",
        "queries": n_q,
        "exact_ms_per_q": round(t_off / n_q * 1e3, 3),
        "raster_ms_per_q": round(t_on / n_q * 1e3, 3),
        "speedup": round(t_off / max(t_on, 1e-9), 2),
        "identical": bool(identical),
        "rasterized_queries": int(sum(c.rast is not None for c in cfgs_on)),
    }]
    log(f"[pip_join] z2_polygon_pip_batch speedup {rows[0]['speedup']}x")

    # -- polygon-heavy indexed join --------------------------------------
    n_poly = int(os.environ.get("GEOMESA_BENCH_PIP_POLYS", 128))
    jrng = np.random.default_rng(SEED + 92)
    jpolys = [
        jagged(
            float(jrng.uniform(-150, 150)), float(jrng.uniform(-60, 60)),
            float(jrng.uniform(1.0, 6.0)), int(jrng.choice([8, 16, 50, 127])),
            seed=1000 + k,
        )
        for k in range(n_poly)
    ]
    gsft = FeatureType.from_spec("adm", "*geom:Polygon:srid=4326")
    left = FeatureCollection.from_columns(
        gsft, np.arange(n_poly),
        {"geom": geo.PackedGeometryColumn.from_geometries(jpolys)},
    )

    def run_join(label, enabled):
        RASTER_ENABLED.set(enabled if not enabled else None)
        ds.planner.invalidate_config_memo()
        fr.clear_cache()
        spatial_join_indexed(ds, "fp", left, "intersects")  # warm
        best, pairs = None, None
        for _ in range(repeat):
            t, out = _timed(
                lambda: spatial_join_indexed(ds, "fp", left, "intersects")
            )
            if best is None or t < best:
                best, pairs = t, out
        log(f"[pip_join] join {label}: {best * 1e3:.0f} ms, {len(pairs[0])} pairs")
        return best, pairs

    t_joff, p_off = run_join("exact (raster off)", False)
    t_jon, p_on = run_join("raster on", True)
    RASTER_ENABLED.set(None)
    join_identical = np.array_equal(p_off[0], p_on[0]) and np.array_equal(
        p_off[1], p_on[1]
    )
    assert join_identical
    rows.append({
        "scenario": "z2_polygon_join",
        "polygons": n_poly,
        "pairs": int(len(p_on[0])),
        "exact_ms": round(t_joff * 1e3, 1),
        "raster_ms": round(t_jon * 1e3, 1),
        "speedup": round(t_joff / max(t_jon, 1e-9), 2),
        "identical": bool(join_identical),
    })
    log(f"[pip_join] z2_polygon_join speedup {rows[-1]['speedup']}x")

    # -- host grid join: exact vs adaptive -------------------------------
    sub = min(n, 2_000_000)
    right = FeatureCollection.from_columns(
        sft, np.arange(sub), {"geom": (px[:sub], py[:sub])}
    )
    m = MetricsRegistry()
    t_hex, h_ex = _timed(
        lambda: spatial_join(left, right, "intersects", strategy="exact")
    )
    t_had, h_ad = _timed(
        lambda: spatial_join(
            left, right, "intersects", strategy="auto", metrics=m
        )
    )
    host_identical = np.array_equal(h_ex[0], h_ad[0]) and np.array_equal(
        h_ex[1], h_ad[1]
    )
    assert host_identical
    rows.append({
        "scenario": "host_grid_join",
        "pairs": int(len(h_ex[0])),
        "exact_ms": round(t_hex * 1e3, 1),
        "adaptive_ms": round(t_had * 1e3, 1),
        "speedup": round(t_hex / max(t_had, 1e-9), 2),
        "identical": bool(host_identical),
        "raster_partitions": m.counter_value("geomesa.join.strategy.raster"),
        "exact_partitions": m.counter_value("geomesa.join.strategy.exact"),
    })
    log(f"[pip_join] host_grid_join speedup {rows[-1]['speedup']}x")

    payload = {
        "n_rows": n,
        "queries_per_batch": n_q,
        "platform": jax.default_backend(),
        "rows": rows,
    }
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_PIP_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_PIP_JOIN.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "z2_polygon_pip_batch_raster_speedup",
        "value": rows[0]["speedup"],
        "unit": "x",
        "raster_ms_per_q": rows[0]["raster_ms_per_q"],
        "exact_ms_per_q": rows[0]["exact_ms_per_q"],
        "join_speedup": rows[1]["speedup"],
        "rows": rows,
        "n_rows": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


# ---------------------------------------------------- streaming scenario


def config_stream(out_path: "str | None" = None):
    """Production streaming tier scenario (round 9, docs/streaming.md):
    sustained micro-batch ingest through the LambdaStore while a
    concurrent mixed query workload runs against the hot+cold merge.

    The moving-objects workload: a cold z3 store of N tracked objects;
    each flush batch is half UPDATES of existing ids (objects reporting
    new positions with fresh timestamps) and half NEW ids (arrivals).
    Two ingest paths at the same batch sizes:

    - ``legacy``: the pre-round-9 per-flush full persist —
      ``write`` + ``persist_hot(incremental=False)``, a delete-and-
      rewrite recompaction of the whole cold table per flush;
    - ``streamed``: ``write`` + micro-batch ``flush()`` — appends ride
      the O(batch) delta tier, updates hold in the exact hot overlay
      and fold incrementally past ``geomesa.stream.fold.rows``
      (``DataStore.fold_upsert``), with a final full persist included
      in the measured wall clock.

    During the streamed run, client threads issue mixed bbox/bbox+time
    queries through ``LambdaStore.query`` with the cold store's
    QueryScheduler attached (fused dispatches + shedding while ingest
    runs); their p50/p99 are recorded against the declared SLO. The
    legacy baseline runs WITHOUT the query load (favoring the
    baseline). Exactness is computed in-bench: after the run, every
    probe query against the streamed store must return the same id set
    and attribute values as a fresh batch-loaded oracle holding the
    expected final state -> the ``identical`` flag
    ``scripts/bench_gate.py`` enforces.

    Emits BENCH_STREAM.json next to this file (or at ``out_path`` / env
    GEOMESA_BENCH_STREAM_OUT — use a SCRATCH path when producing the
    fresh side of a gate comparison). Env knobs:
    GEOMESA_BENCH_STREAM_N (cold rows), GEOMESA_BENCH_STREAM_BATCH
    (rows per flush), GEOMESA_BENCH_STREAM_FLUSHES,
    GEOMESA_BENCH_STREAM_CLIENTS (query threads),
    GEOMESA_BENCH_STREAM_SLO_MS (query p99 SLO)."""
    import threading

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.streaming import LambdaStore, StreamConfig

    n = int(os.environ.get("GEOMESA_BENCH_STREAM_N", 3_000_000))
    batch = int(os.environ.get("GEOMESA_BENCH_STREAM_BATCH", 20_000))
    flushes = int(os.environ.get("GEOMESA_BENCH_STREAM_FLUSHES", 24))
    # the legacy baseline's per-flush cost is stationary (O(table) each
    # flush): fewer flushes measure the same rate in half the wall —
    # and bias FOR the baseline, since its table is smaller on average
    legacy_flushes = int(os.environ.get(
        "GEOMESA_BENCH_STREAM_LEGACY_FLUSHES", max(min(flushes, 12), 1)
    ))
    # query load sized to the host: half the cores as open-loop
    # dashboard clients (a 2-core CI box gets 2 clients; a serving host
    # scales up via the env knobs)
    clients = int(os.environ.get(
        "GEOMESA_BENCH_STREAM_CLIENTS", max(2, (os.cpu_count() or 2) // 2)
    ))
    poll_ms = float(os.environ.get("GEOMESA_BENCH_STREAM_POLL_MS", 150.0))
    # declared p99 SLO for dashboard reads under sustained ingest on the
    # SHARED 2-core CPU CI host (p50 sits ~50-60 ms; the tail is core
    # contention with the flush stages plus neighbor load — serving
    # hosts with spare cores run far tighter; observed p99 across runs
    # spans ~200-800 ms on this box)
    slo_ms = float(os.environ.get("GEOMESA_BENCH_STREAM_SLO_MS", 1000.0))
    t0_ms = 1_717_200_000_000  # 2024-06-01T00:00:00Z
    day = 86_400_000
    spec = "name:String,dtg:Date,*geom:Point:srid=4326"

    def build():
        rng = np.random.default_rng(SEED + 90)
        ds = DataStore()
        sft = FeatureType.from_spec("mv", spec)
        ds.create_schema(sft)
        ds.write("mv", FeatureCollection.from_columns(
            sft, np.arange(n).astype(str), {
                "name": np.array(["v"] * n),
                "dtg": t0_ms + rng.integers(0, 7 * day, n),
                "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n)),
            }), check_ids=False)
        ds.compact("mv")
        return ds

    # the message stream (the producer side): prebuilt so both runs
    # ingest the identical sequence
    log(f"[stream] building {flushes} x {batch:,}-row message stream ...")
    rng = np.random.default_rng(SEED + 91)
    stream = []
    state: dict = {}
    for k in range(flushes):
        upd = rng.choice(n, batch // 2, replace=False)
        ids = [str(i) for i in upd] + [
            f"new{k}_{j}" for j in range(batch - batch // 2)
        ]
        xs = rng.uniform(-170, 170, batch)
        ys = rng.uniform(-80, 80, batch)
        ts = t0_ms + 8 * day + rng.integers(0, day, batch).astype(np.int64)
        rows = [
            {"name": f"r{k}", "dtg": int(ts[j]),
             "geom": geo.Point(float(xs[j]), float(ys[j]))}
            for j in range(batch)
        ]
        stream.append((rows, ids))
        for j, fid in enumerate(ids):
            state[fid] = (f"r{k}", float(xs[j]), float(ys[j]), int(ts[j]))

    def qpool(seed):
        # city/regional dashboard windows: small boxes (the serving
        # bench's scale) so the query mix models live dashboards, not
        # continental exports
        qrng = np.random.default_rng(seed)
        out = []
        for _ in range(256):
            w = float(qrng.choice([0.5, 1.0, 2.0]))
            qx = qrng.uniform(-165, 165 - w)
            qy = qrng.uniform(-75, 75 - w / 2)
            q = f"bbox(geom, {qx:.3f}, {qy:.3f}, {qx + w:.3f}, {qy + w / 2:.3f})"
            if qrng.random() < 0.3:
                q += (" AND dtg DURING "
                      "2024-06-01T00:00:00Z/2024-06-10T00:00:00Z")
            out.append(q)
        return out

    # -- legacy baseline: full persist per flush, no query load ----------
    log(f"[stream] building {n:,}-row cold store (legacy run) ...")
    ds = build()
    lam = LambdaStore(ds, "mv")
    t0 = time.perf_counter()
    for rows, ids in stream[:legacy_flushes]:
        for s in range(0, len(rows), 2048):  # same consumer loop shape
            lam.write(
                [dict(r) for r in rows[s : s + 2048]], ids=ids[s : s + 2048]
            )
        lam.persist_hot(incremental=False)
    legacy_s = time.perf_counter() - t0
    legacy_rps = legacy_flushes * batch / legacy_s
    lam.close()
    log(f"[stream] legacy full-persist path: {legacy_rps:,.0f} rows/s")

    # -- streamed run: micro-batch flushes + concurrent query load -------
    log(f"[stream] building {n:,}-row cold store (streamed run) ...")
    reg = MetricsRegistry()
    ds = build()
    ds.metrics = reg
    # fold threshold above the run's total updates: the ONE fold happens
    # at the explicit final persist, whose window is timed separately
    # below (the "GC pause" of the LSM design — queries inside it queue
    # behind the O(table) device re-upload)
    lam = LambdaStore(ds, "mv", config=StreamConfig(
        fold_rows=batch * flushes + 1,
    ))
    lam.serve()
    # compile EVERY scan-kernel variant (single-query ladder + the fused
    # multi-query shapes, all predicate-flag combos) before the clock
    # starts: a first-hit XLA compile landing mid-run would show up in
    # the measured p99 as a ~second-long straggler
    ds.warmup("mv")
    for q in qpool(SEED + 92)[:8]:
        lam.query(q)
    ds.query_many("mv", qpool(SEED + 92)[8:16])
    stop = threading.Event()
    lat: list = []
    lat_lock = threading.Lock()

    def client(seed):
        # open-loop dashboard poll: one query per poll interval (a
        # closed-loop hammer would just consume every spare core and
        # measure CPU contention, not serving latency at a stated load)
        pool = qpool(seed)
        local = []
        i = 0
        while not stop.is_set():
            s = time.perf_counter()
            lam.query(pool[i % len(pool)])
            dt = time.perf_counter() - s
            local.append((s, dt))
            i += 1
            stop.wait(max(poll_ms / 1e3 - dt, 0.0))
        with lat_lock:
            lat.extend(local)

    threads = [
        threading.Thread(target=client, args=(SEED + 100 + c,))
        for c in range(clients)
    ]
    for t in threads:
        t.start()
    t0 = time.perf_counter()
    for rows, ids in stream:
        # the consumer loop: messages apply in small sub-batches (a real
        # stream consumer polls continuously; one monolithic 20k-row
        # write would hold the interpreter in a single burst)
        for s in range(0, len(rows), 2048):
            lam.write(
                [dict(r) for r in rows[s : s + 2048]], ids=ids[s : s + 2048]
            )
        lam.flush()
    fold_t0 = time.perf_counter()
    lam.persist_hot()  # the final fold is part of the measured wall
    fold_t1 = streamed_s = time.perf_counter()
    streamed_s -= t0
    stop.set()
    for t in threads:
        t.join()
    streamed_rps = flushes * batch / streamed_s
    # SLO accounting: steady-state micro-batch queries vs queries that
    # overlapped the fold window. Round 11 killed the monolithic pause
    # (pre-staged parse/keys + sliced publishes + scheduler yielding —
    # docs/streaming.md "Incremental fold"): the window is now a train
    # of bounded per-slice pauses, reported as a histogram, and the
    # in-window query p99 is gated against steady state
    steady = np.array([d for s, d in lat if s + d < fold_t0]) * 1e3
    in_fold = np.array([d for s, d in lat if s + d >= fold_t0]) * 1e3
    p50 = float(np.percentile(steady, 50)) if len(steady) else 0.0
    p99 = float(np.percentile(steady, 99)) if len(steady) else 0.0
    fold_p99 = float(np.percentile(in_fold, 99)) if len(in_fold) else 0.0
    report = getattr(ds, "last_fold_report", None) or {}
    slice_ms = np.array(report.get("slice_s", [])) * 1e3
    fold_hist = {
        "count": int(len(slice_ms)),
        "p50_ms": round(float(np.percentile(slice_ms, 50)), 2) if len(slice_ms) else 0.0,
        "p99_ms": round(float(np.percentile(slice_ms, 99)), 2) if len(slice_ms) else 0.0,
        "max_ms": round(float(slice_ms.max()), 2) if len(slice_ms) else 0.0,
    }
    prestaged = reg.counter_value("geomesa.stream.fold.prestaged")
    log(
        f"[stream] streamed path: {streamed_rps:,.0f} rows/s with "
        f"{len(lat)} concurrent queries (steady p99 {p99:.1f} ms; "
        f"fold window {fold_t1 - fold_t0:.2f}s over {fold_hist['count']} "
        f"slices, max slice pause {fold_hist['max_ms']:.0f} ms, "
        f"in-window p99 {fold_p99:.1f} ms, {prestaged} rows pre-staged)"
    )

    # -- exactness: streamed store vs batch-loaded oracle ----------------
    log("[stream] exactness: batch-loaded oracle comparison ...")
    oracle = DataStore()
    osft = FeatureType.from_spec("mv", spec)
    oracle.create_schema(osft)
    base_rng = np.random.default_rng(SEED + 90)  # replay build()'s draws
    bt = t0_ms + base_rng.integers(0, 7 * day, n)  # dtg drawn first
    bx = base_rng.uniform(-170, 170, n)
    by = base_rng.uniform(-80, 80, n)
    # expected final state: the original rows, overridden by the stream
    oids = np.arange(n).astype(str).tolist() + sorted(
        fid for fid in state if not fid.isdigit()
    )
    names, oxs, oys, ots = [], [], [], []
    for i, fid in enumerate(oids):
        if fid in state:
            nm, x, y, tms = state[fid]
        else:
            nm, x, y, tms = "v", float(bx[i]), float(by[i]), int(bt[i])
        names.append(nm), oxs.append(x), oys.append(y), ots.append(tms)
    oracle.write("mv", FeatureCollection.from_columns(osft, oids, {
        "name": np.array(names),
        "dtg": np.array(ots, np.int64),
        "geom": (np.array(oxs), np.array(oys)),
    }), check_ids=False)
    identical = True
    for q in qpool(SEED + 93)[:24]:
        got = lam.query(q)
        want = oracle.query("mv", q)
        gi = np.argsort(got.ids)
        wi = np.argsort(want.ids)
        gg, wg = got.geom_column, want.geom_column
        same = (
            len(got) == len(want)
            and np.array_equal(np.asarray(got.ids)[gi], np.asarray(want.ids)[wi])
            and np.array_equal(
                np.asarray(got.columns["name"])[gi],
                np.asarray(want.columns["name"])[wi],
            )
            # every attribute, bit-for-bit: a fold bug that drifted
            # coordinates or timestamps while keeping rows inside the
            # probe boxes must break the identical flag, not pass it
            and np.array_equal(gg.x[gi], wg.x[wi])
            and np.array_equal(gg.y[gi], wg.y[wi])
            and np.array_equal(
                np.asarray(got.columns["dtg"], np.int64)[gi],
                np.asarray(want.columns["dtg"], np.int64)[wi],
            )
        )
        if not same:
            identical = False
            log(f"[stream] MISMATCH on {q}")
    lam.close()
    ds.scheduler.close()

    speedup = streamed_rps / max(legacy_rps, 1e-9)
    slo_met = bool(p99 <= slo_ms) if len(steady) else True
    # the round-11 acceptance bar: query p99 INSIDE the fold window must
    # stay within 2x the steady-state p99 (the pause-kill claim, gated by
    # scripts/bench_gate.py FRESH_BOUNDS as a within-run invariant)
    fold_over_steady = round(fold_p99 / max(p99, 1e-9), 2) if len(in_fold) else 0.0
    row = {
        "scenario": "stream_sustained",
        "cold_rows": n,
        "batch_rows": batch,
        "flushes": flushes,
        # absolute rows/s and latencies are HOST-dependent (the round-9
        # baseline ran on 2 cores; round 11 re-pinned on 1): record the
        # run's core count so a baseline comparison across hosts is
        # interpretable in the artifact itself
        "host_cores": os.cpu_count(),
        "legacy_rows_per_s": round(legacy_rps, 1),
        "streamed_rows_per_s": round(streamed_rps, 1),
        "speedup": round(speedup, 2),
        "identical": identical,
        "query": {
            "clients": clients,
            "poll_ms": poll_ms,
            "queries": int(len(lat)),
            "p50_ms": round(p50, 2),
            "p99_ms": round(p99, 2),
            "slo_ms": slo_ms,
            "slo_met": slo_met,
            "fold_window_s": round(fold_t1 - fold_t0, 2),
            "in_fold_queries": int(len(in_fold)),
            "fold_window_p99_ms": round(fold_p99, 2),
            "fold_window_p99_over_steady": fold_over_steady,
        },
        "fold": {
            "rows": int(report.get("rows", 0)),
            "slices": int(report.get("slices", 0)),
            "prestaged_rows": int(prestaged),
            "slice_pause_ms": fold_hist,
        },
        **LINK_PROFILE,
    }
    log(
        f"[stream] sustained {streamed_rps:,.0f} vs legacy "
        f"{legacy_rps:,.0f} rows/s = {speedup:.2f}x, identical={identical}, "
        f"steady p99 {p99:.1f} ms (SLO {slo_ms:.0f} ms, met={slo_met}), "
        f"fold-window p99 {fold_p99:.1f} ms = {fold_over_steady}x steady"
    )

    import jax

    payload = {
        "platform": jax.default_backend(),
        "rows": [row],
    }
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_STREAM_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_STREAM.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "stream_sustained_rows_per_s",
        "value": row["streamed_rows_per_s"],
        "unit": "rows/s",
        "vs_baseline": row["speedup"],
        "identical": identical,
        "query_p99_ms": row["query"]["p99_ms"],
        "slo_met": slo_met,
        "cold_rows": n,
    }
    print(json.dumps(rec), flush=True)
    return rec


def config_knn(out_path: "str | None" = None):
    """Batched kNN throughput scenario (round 11; VERDICT weak #5's
    34.7 q/s vs the 60 q/s bar): ``knn_many`` over trajectory-shaped
    points — every pending query's speculative wide window rides ONE
    ``planner.submit_many`` sweep per round, fusing into shared
    ``block_scan_multi`` dispatches (round 11 halved the per-query
    windows: the estimate radius resolves from the wide window's own
    result, see process/knn.py).

    Exactness is computed in-bench: every measured query's result must
    match (ids, in order) both the per-point ``knn_search`` protocol and
    a brute-force full-scan haversine top-k oracle -> the ``identical``
    flag ``scripts/bench_gate.py`` enforces alongside the q/s floor.

    Emits BENCH_KNN.json (or ``out_path`` / env GEOMESA_BENCH_KNN_OUT —
    use a SCRATCH path for the fresh side of a gate comparison). Env:
    GEOMESA_BENCH_KNN_N (points), GEOMESA_BENCH_KNN_QUERIES."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.process import knn_many, knn_search
    from geomesa_tpu.process.knn import haversine_m
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_KNN_N", 2_000_000))
    n_q = int(os.environ.get("GEOMESA_BENCH_KNN_QUERIES", 64))
    k = 10
    rng = np.random.default_rng(SEED + 50)
    n_tracks = max(n // 4000, 8)
    per = n // n_tracks
    sx = rng.uniform(-170, 170, n_tracks)
    sy = rng.uniform(-75, 75, n_tracks)
    x = np.clip(
        (sx[:, None] + np.cumsum(rng.normal(0, 0.02, (n_tracks, per)), axis=1)).ravel(),
        -180, 180,
    )
    y = np.clip(
        (sy[:, None] + np.cumsum(rng.normal(0, 0.015, (n_tracks, per)), axis=1)).ravel(),
        -90, 90,
    )
    log(f"[knn] building {len(x):,} point store ...")
    sft = FeatureType.from_spec("ais", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    ds.write(
        "ais",
        FeatureCollection.from_columns(sft, np.arange(len(x)), {"geom": (x, y)}),
        check_ids=False,
    )
    qs = [
        (float(rng.uniform(-150, 150)), float(rng.uniform(-60, 60)))
        for _ in range(n_q)
    ]
    knn_search(ds, "ais", *qs[0], k=k)  # warmup compiles
    knn_many(ds, "ais", qs[:3], k=k)    # + the fused batch variants

    best = None
    for _ in range(2):  # best-of-2: shared-host noise
        t0 = time.perf_counter()
        outs = knn_many(ds, "ais", qs, k=k)
        wall = time.perf_counter() - t0
        best = wall if best is None else min(best, wall)
    qps = n_q / best

    log("[knn] exactness: per-point + brute-force oracle comparison ...")
    identical = True
    for i, (qx, qy) in enumerate(qs):
        got = [str(v) for v in outs[i].ids.tolist()]
        single = [
            str(v) for v in knn_search(ds, "ais", qx, qy, k=k).ids.tolist()
        ]
        if got != single:
            identical = False
            log(f"[knn] MISMATCH vs per-point at query {i}")
        d = haversine_m(x, y, qx, qy)
        kth = np.partition(d, k - 1)[k - 1]
        sub = np.nonzero(d <= kth)[0]
        want = sub[np.argsort(d[sub], kind="stable")][:k]
        if kth <= 1_000_000.0 and got != [str(j) for j in want.tolist()]:
            identical = False
            log(f"[knn] MISMATCH vs brute oracle at query {i}")

    row = {
        "scenario": "knn_batched",
        "n_points": int(len(x)),
        "queries": n_q,
        "k": k,
        "host_cores": os.cpu_count(),
        "batched_qps": round(qps, 1),
        "batched_wall_s": round(best, 3),
        "identical": identical,
        **LINK_PROFILE,
    }
    log(f"[knn] batched {qps:.1f} q/s over {n_q} queries, identical={identical}")

    import jax

    payload = {"platform": jax.default_backend(), "rows": [row]}
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_KNN_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_KNN.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec = {
        "metric": "knn_batched_queries_per_sec",
        "value": row["batched_qps"],
        "unit": "q/s",
        "vs_baseline": round(qps / 60.0, 2),  # the VERDICT 60 q/s bar
        "identical": identical,
        "n_points": int(len(x)),
    }
    print(json.dumps(rec), flush=True)
    return rec


def config_wal(out_path: "str | None" = None):
    """Streaming WAL overhead + recovery scenario (ISSUE 10,
    docs/durability.md "Streaming WAL"): the SAME micro-batch
    write+flush workload runs four times — no WAL, then WAL under
    ``sync=off`` / ``interval`` / ``always`` — and sustained rows/s is
    recorded for each; then a separate run streams
    ``GEOMESA_BENCH_WAL_REPLAY`` rows (with periodic flush watermarks),
    hard-kills, and times ``LambdaStore.recover`` end to end.

    Exactness is computed in-bench: after the ``sync=always`` run the
    store is recovered from disk and every probe query must return the
    same ids and values as the live (never-killed) store — the
    ``identical`` flag ``scripts/bench_gate.py`` enforces, alongside the
    within-run bound that ``sync=interval`` throughput stays within 15%
    of the no-WAL path.

    Emits BENCH_WAL.json (or ``out_path`` / GEOMESA_BENCH_WAL_OUT — use
    a scratch path for the fresh side of a gate run). Env knobs:
    GEOMESA_BENCH_WAL_COLD (cold rows), GEOMESA_BENCH_WAL_N (streamed
    rows per mode), GEOMESA_BENCH_WAL_BATCH, GEOMESA_BENCH_WAL_REPLAY
    (rows in the recovery run)."""
    import shutil
    import tempfile

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.storage import persist
    from geomesa_tpu.streaming import LambdaStore, StreamConfig, WalConfig

    n_cold = int(os.environ.get("GEOMESA_BENCH_WAL_COLD", 200_000))
    n_stream = int(os.environ.get("GEOMESA_BENCH_WAL_N", 400_000))
    batch = int(os.environ.get("GEOMESA_BENCH_WAL_BATCH", 20_000))
    n_replay = int(os.environ.get("GEOMESA_BENCH_WAL_REPLAY", 1_000_000))
    t0_ms = 1_717_200_000_000
    spec = "name:String,dtg:Date,*geom:Point:srid=4326"

    def build_root(base_dir):
        rng = np.random.default_rng(SEED + 95)
        ds = DataStore()
        sft = FeatureType.from_spec("mv", spec)
        ds.create_schema(sft)
        if n_cold:
            ds.write("mv", FeatureCollection.from_columns(
                sft, np.arange(n_cold).astype(str), {
                    "name": np.array(["v"] * n_cold),
                    "dtg": t0_ms + rng.integers(0, 86_400_000, n_cold),
                    "geom": (rng.uniform(-170, 170, n_cold),
                             rng.uniform(-80, 80, n_cold)),
                }), check_ids=False)
            ds.compact("mv")
        root = os.path.join(base_dir, "s")
        persist.save(ds, root)
        return ds, root

    def message_stream(n):
        """Prebuilt (ids, rows) batches: half updates of cold ids, half
        arrivals — identical across every mode."""
        rng = np.random.default_rng(SEED + 96)
        out = []
        arrivals = 0
        for s in range(0, n, batch):
            k = min(batch, n - s)
            ids, rows = [], []
            upd = rng.integers(0, max(n_cold, 1), k // 2)
            xs = rng.uniform(-170, 170, k)
            ys = rng.uniform(-80, 80, k)
            for j in range(k):
                if j < k // 2 and n_cold:
                    ids.append(str(int(upd[j])))
                else:
                    arrivals += 1
                    ids.append(f"a{arrivals}")
                rows.append({
                    "name": "u", "dtg": t0_ms + s + j,
                    "geom": geo.Point(float(xs[j]), float(ys[j])),
                })
            out.append((ids, rows))
        return out

    stream = message_stream(n_stream)
    probes = [
        "bbox(geom, -40, -40, 0, 0)", "bbox(geom, 10, 10, 60, 50)",
        "IN ('0', '1', 'a1', 'a2')",
    ]

    def run_mode(mode):
        """One full streamed run; returns (rows/s, lam, root, tmp)."""
        tmp = tempfile.mkdtemp(prefix="geomesa_wal_bench_")
        ds, root = build_root(tmp)
        kw = {}
        if mode != "nowal":
            kw = dict(
                wal_dir=os.path.join(root, "_wal"),
                wal_config=WalConfig(sync=mode),
            )
        lam = LambdaStore(ds, "mv", config=StreamConfig(), **kw)
        t0 = time.perf_counter()
        for ids, rows in stream:
            lam.write(rows, ids=ids)
            lam.flush()
        dt = time.perf_counter() - t0
        return n_stream / dt, lam, root, tmp

    # warmup: one discarded short run so the first MEASURED mode does
    # not pay the fold/scan kernel compilations for everyone
    log("[wal] warmup ...")
    tmpw = tempfile.mkdtemp(prefix="geomesa_wal_warm_")
    dsw, _rootw = build_root(tmpw)
    lamw = LambdaStore(dsw, "mv", config=StreamConfig())
    for ids, rows in stream[: max(1, min(3, len(stream)))]:
        lamw.write(rows, ids=ids)
        lamw.flush()
    lamw.close()
    shutil.rmtree(tmpw, ignore_errors=True)

    # best-of-N per mode: the measured window is seconds on a SHARED CI
    # host, and a neighbor's burst during one mode would otherwise read
    # as WAL overhead (or mask it); every repeat streams the identical
    # prebuilt message sequence
    repeat = int(os.environ.get("GEOMESA_BENCH_WAL_REPEAT", 2))
    results = {}
    keep = {}
    for mode in ("nowal", "off", "interval", "always"):
        best = 0.0
        for r in range(max(repeat, 1)):
            rps, lam, root, tmp = run_mode(mode)
            best = max(best, rps)
            last = r == max(repeat, 1) - 1
            if mode == "always" and last:
                keep = {"lam": lam, "root": root, "tmp": tmp}
            else:
                lam.close()
                shutil.rmtree(tmp, ignore_errors=True)
        results[mode] = best
        log(f"[wal] {mode}: {best:,.0f} rows/s (best of {repeat})")

    # exactness: hard-kill the sync=always store and recover from disk
    lam, root = keep["lam"], keep["root"]
    live = [sorted(zip(
        (str(i) for i in lam.query(q).ids.tolist()),
        (str(v) for v in np.asarray(lam.query(q).columns["name"]).tolist()),
    )) for q in probes]
    lam.wal.crash()
    lam.flusher.close()
    rec = LambdaStore.recover(root)
    recovered = [sorted(zip(
        (str(i) for i in rec.query(q).ids.tolist()),
        (str(v) for v in np.asarray(rec.query(q).columns["name"]).tolist()),
    )) for q in probes]
    identical = bool(
        recovered == live and rec.cold.store_health.status == "ok"
    )
    rec.close()
    shutil.rmtree(keep["tmp"], ignore_errors=True)

    # recovery throughput: stream n_replay rows (periodic flushes leave
    # watermarks in the log), hard-kill, time the full recover()
    tmp = tempfile.mkdtemp(prefix="geomesa_wal_replay_")
    ds, root = build_root(tmp)
    lam = LambdaStore(
        ds, "mv", config=StreamConfig(),
        wal_dir=os.path.join(root, "_wal"),
        wal_config=WalConfig(sync="off"),  # isolate REPLAY cost
    )
    rng = np.random.default_rng(SEED + 97)
    for s in range(0, n_replay, batch):
        k = min(batch, n_replay - s)
        xs = rng.uniform(-170, 170, k)
        ys = rng.uniform(-80, 80, k)
        lam.write(
            [{"name": "r", "dtg": t0_ms + s + j,
              "geom": geo.Point(float(xs[j]), float(ys[j]))}
             for j in range(k)],
            ids=[f"r{s + j}" for j in range(k)],
        )
        lam.flush()
    lam.wal.sync()  # sync=off: drains the app buffer (no fsync)
    lam.wal.crash()
    lam.flusher.close()
    # best-of-N like the stream modes: recovery is idempotent off the
    # same on-disk root, and a neighbor's burst during the one measured
    # window would otherwise read as replay cost
    recover_s = float("inf")
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        rec = LambdaStore.recover(root)
        recover_s = min(recover_s, time.perf_counter() - t0)
        replayed = len(rec.cold.features("mv")) + len(rec.hot) - n_cold
        rec.close()
    shutil.rmtree(tmp, ignore_errors=True)

    interval_over_nowal = results["interval"] / results["nowal"]
    row = {
        "scenario": "stream_wal",
        "cold_rows": n_cold, "streamed_rows": n_stream, "batch": batch,
        "nowal_rows_per_s": round(results["nowal"], 1),
        "wal_off_rows_per_s": round(results["off"], 1),
        "wal_interval_rows_per_s": round(results["interval"], 1),
        "wal_always_rows_per_s": round(results["always"], 1),
        "interval_over_nowal": round(interval_over_nowal, 4),
        "identical": identical,
    }
    replay_row = {
        "scenario": "wal_replay",
        "replay_rows": n_replay, "replayed_rows": int(replayed),
        "recover_s": round(recover_s, 3),
        "replay_rows_per_s": round(n_replay / recover_s, 1),
        # exactness proxy the gate enforces: recovery surfaced every
        # streamed row, none lost, none invented
        "identical": bool(int(replayed) == n_replay),
    }
    log(
        f"[wal] interval/nowal = {interval_over_nowal:.3f}, "
        f"always = {results['always'] / results['nowal']:.3f}x of nowal, "
        f"identical={identical}; replay {n_replay:,} rows in "
        f"{recover_s:.1f}s = {n_replay / recover_s:,.0f} rows/s"
    )

    import jax

    payload = {"platform": jax.default_backend(), "rows": [row, replay_row]}
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_WAL_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_WAL.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "wal_interval_rows_per_s",
        "value": row["wal_interval_rows_per_s"],
        "unit": "rows/s",
        "interval_over_nowal": row["interval_over_nowal"],
        "identical": identical,
        "replay_rows_per_s": replay_row["replay_rows_per_s"],
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


# ------------------------------------------------- config standing


def config_standing(out_path: "str | None" = None):
    """Standing-query matching scenario (ISSUE 14, docs/standing.md):
    >= 1M persistent geofence subscriptions indexed by the inverted
    SubscriptionIndex, probed by a sustained ingest stream.

    Within ONE run it measures: (1) sustained ingest rows/s with the
    matcher OFF vs ON (the matcher rides the write ack path — the gate
    holds the ON rate at >= 0.9x OFF); (2) pure per-event matching cost
    through the inverted index vs a NAIVE all-subscription evaluation
    (vectorized bbox prefilter over every registered subscription +
    exact ragged PIP on the bbox hits — not a strawman) on a sampled
    event set, the >= 50x algorithmic-win floor; (3) match-set
    exactness vs a per-event shapely oracle over bbox-candidate pairs
    (complete: truth and matches are both subsets of the bbox
    candidates) — the ``identical`` flag; (4) the alert-latency p99
    off the live ``geomesa.standing.latency`` histogram.

    The subscription population is deliberately mixed: ~1M tiny squares
    (the routing-scale test — most register 1-4 PARTIAL cells), a
    dense-polygon hotspot (jagged stars across the FUSED_E_BUCKETS
    ladder, where 20% of events cluster, so the fused kernel path
    engages), and large convex fences whose interiors classify FULL
    (zero-geometry-work matches).

    Emits BENCH_GEOFENCE.json (or ``out_path`` /
    GEOMESA_BENCH_GEOFENCE_OUT — use a scratch path for the fresh side
    of a gate run). Env knobs: GEOMESA_BENCH_GEOFENCE_SUBS,
    GEOMESA_BENCH_GEOFENCE_N, GEOMESA_BENCH_GEOFENCE_BATCH,
    GEOMESA_BENCH_GEOFENCE_ORACLE (sampled oracle events),
    GEOMESA_BENCH_GEOFENCE_NAIVE (sampled naive events)."""
    from shapely.geometry import Point as SPoint
    from shapely.geometry import Polygon as SPolygon

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.scan import block_kernels as bk
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.streaming import LambdaStore, StreamConfig
    from geomesa_tpu.streaming.standing import _ragged_pip

    import shutil
    import tempfile

    n_subs = int(os.environ.get("GEOMESA_BENCH_GEOFENCE_SUBS", 1_000_000))
    n_events = int(os.environ.get("GEOMESA_BENCH_GEOFENCE_N", 200_000))
    batch = int(os.environ.get("GEOMESA_BENCH_GEOFENCE_BATCH", 20_000))
    n_oracle = int(os.environ.get("GEOMESA_BENCH_GEOFENCE_ORACLE", 1_500))
    n_naive = int(os.environ.get("GEOMESA_BENCH_GEOFENCE_NAIVE", 16))
    t0_ms = 1_717_200_000_000
    spec = "name:String,dtg:Date,*geom:Point:srid=4326"
    rng = np.random.default_rng(SEED + 140)

    # -- the subscription population -------------------------------------
    log(f"[standing] building {n_subs:,} tiny geofences ...")
    cx = rng.uniform(-170, 170, n_subs)
    cy = rng.uniform(-80, 80, n_subs)
    w = rng.uniform(0.005, 0.03, n_subs)
    tiny = [
        geo.Polygon([
            (cx[i] - w[i], cy[i] - w[i]), (cx[i] + w[i], cy[i] - w[i]),
            (cx[i] + w[i], cy[i] + w[i]), (cx[i] - w[i], cy[i] + w[i]),
            (cx[i] - w[i], cy[i] - w[i]),
        ])
        for i in range(n_subs)
    ]

    def star(scx, scy, r, n_arms, seed):
        srng = np.random.default_rng(seed)
        a = np.linspace(0, 2 * np.pi, 2 * n_arms + 1)[:-1]
        rad = np.where(np.arange(2 * n_arms) % 2 == 0, r,
                       r * srng.uniform(0.3, 0.7, 2 * n_arms))
        return geo.Polygon([
            (scx + rr * np.cos(t), scy + rr * np.sin(t))
            for t, rr in zip(a, rad)
        ])

    def ring(scx, scy, r, n=24):
        a = np.linspace(0, 2 * np.pi, n + 1)
        return geo.Polygon([
            (scx + r * np.cos(t), scy + r * np.sin(t)) for t in a
        ])

    # the hotspot: dense stars across the E ladder + FULL-cell fences
    HOT = (0.0, 10.0, 12.0, 22.0)  # x0, y0, x1, y1
    dense = []
    for k in range(96):
        arms = int(rng.integers(8, 121))  # E buckets 32..256
        dense.append((f"dense{k}", star(
            float(rng.uniform(HOT[0] + 2, HOT[2] - 2)),
            float(rng.uniform(HOT[1] + 2, HOT[3] - 2)),
            float(rng.uniform(0.8, 2.5)), arms, seed=SEED + k,
        )))
    for k in range(16):
        dense.append((f"fence{k}", ring(
            float(rng.uniform(-160, 160)), float(rng.uniform(-70, 70)),
            float(rng.uniform(2.0, 4.0)),
        )))
    all_ids = [f"s{i}" for i in range(n_subs)] + [i for i, _ in dense]
    all_geoms = tiny + [g for _, g in dense]

    # -- the event stream (identical across every mode) -------------------
    n_hot = n_events // 5
    ex = np.concatenate([
        rng.uniform(-170, 170, n_events - n_hot),
        rng.uniform(HOT[0], HOT[2], n_hot),
    ])
    ey = np.concatenate([
        rng.uniform(-80, 80, n_events - n_hot),
        rng.uniform(HOT[1], HOT[3], n_hot),
    ])
    order = rng.permutation(n_events)
    ex, ey = ex[order], ey[order]
    batches = []
    for s in range(0, n_events, batch):
        k = min(batch, n_events - s)
        batches.append((
            [f"e{s + j}" for j in range(k)],
            [{"name": "e", "dtg": t0_ms + s + j,
              "geom": geo.Point(float(ex[s + j]), float(ey[s + j]))}
             for j in range(k)],
        ))

    def ingest_run(engine_on: bool):
        """One full streamed run over the prebuilt batches — DURABLE
        (WAL-backed, default sync policy): the production configuration
        this tier rides on, for both modes, so the ingest ratio isolates
        the matcher's cost; returns (rows/s, engine|None)."""
        ds = DataStore()
        ds.metrics = MetricsRegistry()
        ds.create_schema(FeatureType.from_spec("ev", spec))
        root = tempfile.mkdtemp(prefix="bench_standing_")
        tmp_roots.append(root)
        lam = LambdaStore(
            ds, "ev", config=StreamConfig(),
            wal_dir=os.path.join(root, "_wal"),
        )
        eng = None
        if engine_on:
            eng = lam.standing()
            eng.index.register_geofences(all_ids, all_geoms)
            for e in bk.FUSED_E_BUCKETS:
                eng.matcher.warmup(e, n_rows=batch, gate=eng.gate)
        # warmup (compiles the fold/scan paths outside the window)
        wids, wrows = batches[0]
        lam.write(wrows, ids=[f"w{j}" for j in range(len(wids))])
        lam.flush()
        t0 = time.perf_counter()
        for ids, rows in batches:
            lam.write(rows, ids=ids)
            lam.flush()
        dt = time.perf_counter() - t0
        rate = n_events / dt
        label = "matcher-on" if engine_on else "matcher-off"
        log(f"[standing] ingest {label}: {rate:,.0f} rows/s")
        if not engine_on:
            lam.close()
        return rate, eng, lam

    tmp_roots: list = []
    off_rate, _, _ = ingest_run(False)
    on_rate, eng, lam = ingest_run(True)
    reg = lam.cold.metrics
    alerts = reg.counter_value("geomesa.standing.alerts")
    fused = reg.counter_value("geomesa.standing.fused")
    p99_ms = reg.histogram_quantile("geomesa.standing.latency", 0.99) * 1e3

    # -- pure matcher cost per event (inverted) ---------------------------
    t0 = time.perf_counter()
    for s in range(0, n_events, batch):
        k = min(batch, n_events - s)
        eng.match_points(ex[s : s + k], ey[s : s + k])
    inverted_us = (time.perf_counter() - t0) / n_events * 1e6

    # -- naive all-subscription evaluation on a sample --------------------
    kind, eoff, segs, bbox, _rect = eng.index._ensure_arrays()
    sample = rng.choice(n_events, size=n_naive, replace=False)
    t0 = time.perf_counter()
    naive_pairs = 0
    for e in sample.tolist():
        px, py = float(ex[e]), float(ey[e])
        cand = np.flatnonzero(
            (bbox[:, 0] <= px) & (bbox[:, 2] >= px)
            & (bbox[:, 1] <= py) & (bbox[:, 3] >= py)
        )
        if len(cand):
            inside = _ragged_pip(
                np.full(len(cand), px), np.full(len(cand), py),
                cand.astype(np.int64), eoff, segs,
            )
            naive_pairs += int(inside.sum())
    naive_us = (time.perf_counter() - t0) / n_naive * 1e6
    speedup = naive_us / max(inverted_us, 1e-9)
    log(
        f"[standing] naive {naive_us:,.0f} us/event vs inverted "
        f"{inverted_us:,.1f} us/event = {speedup:,.0f}x "
        f"(alerts {alerts:,}, fused {fused}, p99 {p99_ms:.2f} ms)"
    )

    # -- per-event shapely oracle (complete over bbox candidates) ---------
    osample = rng.choice(n_events, size=n_oracle, replace=False)
    opt, oords = eng.match_points(ex[osample], ey[osample])
    got = set(zip(opt.tolist(), oords.tolist()))
    shp_cache: dict = {}
    identical = True
    for row, e in enumerate(osample.tolist()):
        px, py = float(ex[e]), float(ey[e])
        cand = np.flatnonzero(
            (bbox[:, 0] <= px) & (bbox[:, 2] >= px)
            & (bbox[:, 1] <= py) & (bbox[:, 3] >= py)
        )
        pt = SPoint(px, py)
        for o in cand.tolist():
            sp = shp_cache.get(o)
            if sp is None:
                g = all_geoms[o]
                sp = shp_cache[o] = SPolygon(
                    g.shell, [h for h in g.holes]
                )
            if sp.covers(pt) != ((row, o) in got):
                if sp.boundary.distance(pt) <= 1e-9:
                    continue  # exact-boundary tie: either answer exact
                identical = False
                log(f"[standing] ORACLE MISMATCH event {e} sub {o}")
    lam.close()
    for r in tmp_roots:
        shutil.rmtree(r, ignore_errors=True)

    row = {
        "scenario": "standing_geofence",
        "subscriptions": len(all_ids), "events": n_events, "batch": batch,
        "matcher_off_rows_per_s": round(off_rate, 1),
        "matcher_on_rows_per_s": round(on_rate, 1),
        "ingest_ratio": round(on_rate / off_rate, 4),
        "naive_us_per_event": round(naive_us, 1),
        "inverted_us_per_event": round(inverted_us, 2),
        "speedup_vs_naive": round(speedup, 1),
        "alerts": int(alerts), "fused_dispatches": int(fused),
        "alert_p99_ms": round(p99_ms, 3),
        "oracle_events": int(n_oracle),
        "identical": bool(identical),
    }
    import jax

    payload = {"platform": jax.default_backend(), "rows": [row]}
    if out_path is None:
        out_path = os.environ.get(
            "GEOMESA_BENCH_GEOFENCE_OUT"
        ) or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_GEOFENCE.json",
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "speedup_vs_naive", "value": row["speedup_vs_naive"],
        "unit": "x", "ingest_ratio": row["ingest_ratio"],
        "alert_p99_ms": row["alert_p99_ms"], "identical": identical,
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


# ------------------------------------------------------------- config 4


def config4_join():
    """Spatial join: GDELT-shaped points x admin-polygon-shaped rectangles
    (BASELINE config 4; the geomesa-spark broadcast join — the point side
    is the GeoMesa-INDEXED relation, so the join runs as pipelined device
    scans against the store's z2 table, round-5 spatial_join_indexed).
    Baseline: the ungridded per-polygon scan (bbox mask over ALL points) —
    what a naive executor does without the index."""
    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.sql.join import spatial_join, spatial_join_indexed

    n_pts = int(os.environ.get("GEOMESA_BENCH_N4", 20_000_000))
    n_poly = 256
    rng = np.random.default_rng(SEED + 30)
    x, y = gdelt_points(n_pts, rng)
    px0 = rng.uniform(-170, 150, n_poly)
    py0 = rng.uniform(-80, 60, n_poly)
    pw = rng.uniform(1, 12, n_poly)
    ph = rng.uniform(1, 8, n_poly)
    polys = geo.PackedGeometryColumn.from_boxes(px0, py0, px0 + pw, py0 + ph)

    psft = FeatureType.from_spec("pts", "*geom:Point:srid=4326")
    psft.user_data["geomesa.indices.enabled"] = "z2"
    gsft = FeatureType.from_spec("adm", "*geom:Polygon:srid=4326")
    poly_fc = FeatureCollection.from_columns(gsft, np.arange(n_poly), {"geom": polys})
    ds = DataStore()
    ds.create_schema(psft)
    log(f"[join] building {n_pts:,} point store ...")
    ds.write("pts", FeatureCollection.from_columns(
        psft, np.arange(n_pts), {"geom": (x, y)}), check_ids=False)

    spatial_join_indexed(ds, "pts", poly_fc, "contains")  # warmup compiles
    lats = []
    for _ in range(3):
        t0 = time.perf_counter()
        li, ri = spatial_join_indexed(ds, "pts", poly_fc, "contains")
        lats.append(time.perf_counter() - t0)
    t_join = float(np.median(lats))

    # host grid join on the same data, for the record (the r4 path)
    t0 = time.perf_counter()
    hl, hr = spatial_join(poly_fc, ds.features("pts"), "contains")
    t_host = time.perf_counter() - t0
    assert len(hl) == len(li), (len(hl), len(li))

    # baseline: ungridded per-polygon bbox mask, sampled + extrapolated
    for _ in range(2):
        t0 = time.perf_counter()
        total = 0
        for p in range(min(n_poly, 16)):
            bx0, by0, bx1, by1 = px0[p], py0[p], px0[p] + pw[p], py0[p] + ph[p]
            m = (x >= bx0) & (x <= bx1) & (y >= by0) & (y <= by1)
            total += int(m.sum())
        base = (time.perf_counter() - t0) * (n_poly / 16)

    rec = result_line(
        "gdelt_join_pairs_per_sec", np.array([t_join]), len(li), t_join, base,
        {
            "n_points": n_pts, "n_polygons": n_poly, "pairs": len(li),
            "host_grid_join_ms": round(t_host * 1e3, 1),
        },
    )
    del ds, x, y
    gc.collect()
    return rec


# ------------------------------------------------------------- config 5


def config5_knn():
    """kNN process on AIS-trajectory-shaped points (BASELINE config 5).
    Baseline: full haversine + argpartition over every point per query."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.process import knn_search
    from geomesa_tpu.process.knn import haversine_m
    from geomesa_tpu.sft import FeatureType

    n = int(os.environ.get("GEOMESA_BENCH_N5", 20_000_000))
    rng = np.random.default_rng(SEED + 40)
    # trajectory-shaped: random walks from seed ports
    n_tracks = 2000
    per = n // n_tracks
    sx = rng.uniform(-170, 170, n_tracks)
    sy = rng.uniform(-75, 75, n_tracks)
    x = np.clip(
        (sx[:, None] + np.cumsum(rng.normal(0, 0.02, (n_tracks, per)), axis=1)).ravel(),
        -180, 180,
    )
    y = np.clip(
        (sy[:, None] + np.cumsum(rng.normal(0, 0.015, (n_tracks, per)), axis=1)).ravel(),
        -90, 90,
    )
    sft = FeatureType.from_spec("ais", "*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z2"
    ds = DataStore()
    ds.create_schema(sft)
    ds.write("ais", FeatureCollection.from_columns(sft, np.arange(len(x)), {"geom": (x, y)}), check_ids=False)

    qs = [(float(rng.uniform(-150, 150)), float(rng.uniform(-60, 60))) for _ in range(20)]
    knn_search(ds, "ais", *qs[0], k=10)  # warmup compiles
    from geomesa_tpu.process import knn_many

    knn_many(ds, "ais", qs[:3], k=10)  # warms the fused batch variant
    lat = []
    t_all = time.perf_counter()
    for qx, qy in qs:
        s = time.perf_counter()
        out = knn_search(ds, "ais", qx, qy, k=10)
        lat.append(time.perf_counter() - s)
    wall = time.perf_counter() - t_all

    # pipelined batch: all window scans dispatch before any pull
    t0 = time.perf_counter()
    outs = knn_many(ds, "ais", qs, k=10)
    batch_wall = time.perf_counter() - t0
    batch_hits = sum(len(o) for o in outs)
    # sparse regions may hold < k within the distance cutoff; that is
    # valid output — require only a sane, non-empty batch
    assert 0 < batch_hits <= 10 * len(qs)

    t0 = time.perf_counter()
    for qx, qy in qs[:4]:  # baseline sampled
        d = haversine_m(x, y, qx, qy)
        np.argpartition(d, 10)[:10]
    base = (time.perf_counter() - t0) / 4

    return result_line(
        "ais_knn_queries", np.array(lat), 10 * len(qs), wall, base,
        {
            "n_points": len(x), "k": 10,
            "batched_queries_per_sec": round(len(qs) / batch_wall, 1),
        },
    )


# --------------------------------------------------- config replica


def config_replica(out_path: "str | None" = None):
    """WAL-shipping replication scenario (docs/replication.md): three
    measurements in one run, emitted as BENCH_REPLICA.json.

    1. **Read scaling** — the same probe mix runs full-tilt against
       each store in isolation (leader, follower 1, follower 2; one
       measured window per store — in deployment each replica is its
       own host, so in-process thread concurrency would only measure
       the bench host's GIL/device contention, not topology capacity).
       Aggregate QPS at two followers (the three rates summed) must
       clear 1.5x the leader-alone rate: a follower that bootstraps
       wrong or serves reads an order slower than the leader fails the
       gate.
    2. **Bounded staleness** — sustained micro-batch ingest with the
       shipper and a follower's apply loop running as threads; the
       follower's measured staleness watermark histogram
       (``geomesa.replica.staleness.ms``) yields the p99 the gate
       bounds.
    3. **Failover** — mid-ingest the leader WAL hard-kills
       (``wal.crash()``, the kill-9 simulation); a follower promotes
       with ``leader_wal_dir`` pointing at the dead leader's on-disk
       WAL. Promote latency is recorded and the gate enforces ZERO
       acknowledged rows lost and zero rows invented.

    Env knobs: GEOMESA_BENCH_REPLICA_COLD (cold rows),
    GEOMESA_BENCH_REPLICA_N (streamed rows), GEOMESA_BENCH_REPLICA_BATCH,
    GEOMESA_BENCH_REPLICA_READ_S (seconds per read topology),
    GEOMESA_BENCH_REPLICA_OUT (fresh-side output path)."""
    import shutil
    import tempfile

    from geomesa_tpu import geometry as geo
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.storage import persist
    from geomesa_tpu.streaming import (
        LambdaStore, PipeTransport, ReplicaStore, SegmentShipper,
        StreamConfig, WalConfig,
    )

    n_cold = int(os.environ.get("GEOMESA_BENCH_REPLICA_COLD", 60_000))
    n_stream = int(os.environ.get("GEOMESA_BENCH_REPLICA_N", 40_000))
    batch = int(os.environ.get("GEOMESA_BENCH_REPLICA_BATCH", 2_000))
    read_s = float(os.environ.get("GEOMESA_BENCH_REPLICA_READ_S", 2.0))
    t0_ms = 1_717_200_000_000
    spec = "name:String,dtg:Date,*geom:Point:srid=4326"
    tmp = tempfile.mkdtemp(prefix="geomesa_replica_bench_")

    rng = np.random.default_rng(SEED + 98)
    ds = DataStore()
    sft = FeatureType.from_spec("rv", spec)
    ds.create_schema(sft)
    ds.write("rv", FeatureCollection.from_columns(
        sft, np.arange(n_cold).astype(str), {
            "name": np.array(["v"] * n_cold),
            "dtg": t0_ms + rng.integers(0, 86_400_000, n_cold),
            "geom": (rng.uniform(-170, 170, n_cold),
                     rng.uniform(-80, 80, n_cold)),
        }), check_ids=False)
    ds.compact("rv")
    root = os.path.join(tmp, "s")
    persist.save(ds, root)
    lam = LambdaStore(
        ds, "rv", config=StreamConfig(),
        wal_dir=os.path.join(root, "_wal"),
        wal_config=WalConfig(sync="always"),
    )
    ship = SegmentShipper(lam, giveup_s=2.0)
    fols = []
    for i in range(2):
        a, b = PipeTransport.pair()
        fol = ReplicaStore(
            root, os.path.join(tmp, f"f{i}", "_wal"), b, type_name="rv",
            config=StreamConfig(),
        )
        ship.attach(a, name=f"f{i}")
        fols.append(fol)
    ship.pump()
    for fol in fols:
        fol.drain()

    # 1. read scaling: each store measured full-tilt in isolation,
    # aggregate = the summed independent rates (see docstring)
    probes = [
        "bbox(geom, -40, -40, 0, 0)", "bbox(geom, 10, 10, 60, 50)",
        "bbox(geom, -170, -80, -100, 0)",
    ]
    for store in (lam, *fols):
        for q in probes:
            store.query(q)  # warm the scan kernels per store
    # exactness: a caught-up follower answers every probe with exactly
    # the leader's ids (the `identical` flag the gate enforces)
    reads_identical = all(
        sorted(str(i) for i in fol.query(q).ids.tolist())
        == sorted(str(i) for i in lam.query(q).ids.tolist())
        for q in probes for fol in fols
    )

    def measure(store):
        n = 0
        t0 = time.perf_counter()
        while True:
            store.query(probes[n % len(probes)])
            n += 1
            dt = time.perf_counter() - t0
            if dt >= read_s:
                return n / dt

    rates = [measure(s) for s in (lam, *fols)]
    qps = {k: sum(rates[: k + 1]) for k in (0, 1, 2)}
    scaling = qps[2] / max(qps[0], 1e-9)
    log(
        f"[replica] read QPS 0f={qps[0]:,.0f} 1f={qps[1]:,.0f} "
        f"2f={qps[2]:,.0f} (x{scaling:.2f} at 2 followers)"
    )

    # 2. bounded staleness under sustained ingest (shipper + apply
    # threads live), rolling straight into 3. the mid-ingest kill
    ship.start()
    for fol in fols:
        fol.start()
    acked: list = []
    kill_at = max(1, (n_stream // batch) * 7 // 10)
    promoted_s = None
    for bi, s in enumerate(range(0, n_stream, batch)):
        k = min(batch, n_stream - s)
        xs = rng.uniform(-170, 170, k)
        ys = rng.uniform(-80, 80, k)
        ids = [f"r{s + j}" for j in range(k)]
        lam.write(
            [{"name": "r", "dtg": t0_ms + s + j,
              "geom": geo.Point(float(xs[j]), float(ys[j]))}
             for j in range(k)],
            ids=ids,
        )
        acked.extend(ids)  # sync=always: the return IS the ack
        if bi + 1 == kill_at:
            lam.wal.crash()  # kill -9: the leader is gone mid-ingest
            break
    stale_p99_s = max(
        fol.metrics.histogram_quantile("geomesa.replica.staleness.ms", 0.99)
        for fol in fols
    )
    ship.stop()
    for fol in fols:
        fol.stop()
    t0 = time.perf_counter()
    fols[0].promote(leader_wal_dir=os.path.join(root, "_wal"))
    promoted_s = time.perf_counter() - t0
    got = {
        str(i) for i in fols[0].query("INCLUDE").ids.tolist()
    }
    attempted = set(acked) | {str(i) for i in range(n_cold)}
    acked_loss = sum(1 for fid in acked if fid not in got)
    invented = sum(1 for fid in got if fid not in attempted)
    # the lagging (non-promoted) follower may be behind but may never
    # hold a row that was never written
    lagging = {str(i) for i in fols[1].query("INCLUDE").ids.tolist()}
    lagging_honest = lagging <= attempted
    log(
        f"[replica] staleness p99 {stale_p99_s * 1e3:.1f} ms; promote "
        f"{promoted_s * 1e3:.0f} ms, acked={len(acked):,} "
        f"loss={acked_loss} invented={invented}"
    )
    lam.flusher.close()
    for fol in fols:
        fol.close()
    shutil.rmtree(tmp, ignore_errors=True)

    rows = [
        {
            "scenario": "replica_scaling",
            "cold_rows": n_cold, "read_s": read_s,
            "qps_0f": round(qps[0], 1), "qps_1f": round(qps[1], 1),
            "qps_2f": round(qps[2], 1),
            "qps_scaling_2f": round(scaling, 3),
            "identical": bool(reads_identical),
        },
        {
            "scenario": "replica_staleness",
            "streamed_rows": len(acked), "batch": batch,
            "staleness_p99_ms": round(stale_p99_s * 1e3, 2),
            "identical": bool(lagging_honest),
        },
        {
            "scenario": "replica_failover",
            "promote_s": round(promoted_s, 4),
            "acked_rows": len(acked),
            "acked_loss": int(acked_loss), "invented": int(invented),
            "identical": bool(acked_loss == 0 and invented == 0),
        },
    ]

    import jax

    payload = {"platform": jax.default_backend(), "rows": rows}
    if out_path is None:
        out_path = os.environ.get(
            "GEOMESA_BENCH_REPLICA_OUT"
        ) or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_REPLICA.json",
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "replica_qps_scaling_2f",
        "value": rows[0]["qps_scaling_2f"],
        "unit": "x",
        "staleness_p99_ms": rows[1]["staleness_p99_ms"],
        "promote_s": rows[2]["promote_s"],
        "acked_loss": int(acked_loss), "invented": int(invented),
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


def config_serve_http(out_path: "str | None" = None):
    """Data-plane scenario (docs/serving.md "The data plane"): one
    WAL-backed LambdaStore mounted on a real socket, three measurements
    emitted as BENCH_SERVE_HTTP.json.

    1. **Mixed closed-loop** — reader threads and an ingest thread in
       closed loops through the stdlib DataClient; read QPS, ingest
       rows/s, and the ``identical`` flag: the streamed GeoJSON bytes
       for a probe query equal the in-process exporter's bytes exactly.
    2. **Adversarial-tenant fairness** — a compliant tenant's
       closed-loop read p99 is measured alone, then again under a
       volumetric flood: an adversarial tenant hammers the same
       listener from several threads with cheap requests, submitting
       far beyond its admission quota of 1 (shed retries back off only
       by the server's own Retry-After hint). The quota bounds the
       adversary to at most one query in any micro-batch and the 429
       path answers without touching the dispatch plane, so the
       compliant tenant's p99 barely moves. The gate bounds the
       degradation ratio at 1.5x and requires the adversary to have
       been visibly shed (429s accounted per tenant — never silent
       queueing).
    3. **Ack durability** — every HTTP-acked ingest row must survive
       ``wal.crash()`` (kill -9) + ``LambdaStore.recover``: zero acked
       rows lost, zero invented.

    Env knobs: GEOMESA_BENCH_SERVE_COLD (cold rows),
    GEOMESA_BENCH_SERVE_READ_S (seconds per mixed loop),
    GEOMESA_BENCH_SERVE_FAIR_S (seconds per fairness loop),
    GEOMESA_BENCH_SERVE_OUT (fresh-side output path)."""
    import shutil
    import tempfile
    import threading

    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.io.exporters import _geojson
    from geomesa_tpu.serving import DataClient, ServeError
    from geomesa_tpu.sft import FeatureType
    from geomesa_tpu.storage import persist
    from geomesa_tpu.streaming import LambdaStore, StreamConfig, WalConfig

    n_cold = int(os.environ.get("GEOMESA_BENCH_SERVE_COLD", 40_000))
    read_s = float(os.environ.get("GEOMESA_BENCH_SERVE_READ_S", 2.0))
    fair_s = float(os.environ.get("GEOMESA_BENCH_SERVE_FAIR_S", 6.0))
    t0_ms = 1_717_200_000_000
    tmp = tempfile.mkdtemp(prefix="geomesa_serve_bench_")
    rng = np.random.default_rng(SEED + 99)

    ds = DataStore()
    sft = FeatureType.from_spec("sv", "name:String,dtg:Date,*geom:Point:srid=4326")
    ds.create_schema(sft)
    ds.write("sv", FeatureCollection.from_columns(
        sft, np.arange(n_cold).astype(str), {
            "name": np.array(["v"] * n_cold),
            "dtg": t0_ms + rng.integers(0, 86_400_000, n_cold),
            "geom": (rng.uniform(-170, 170, n_cold),
                     rng.uniform(-80, 80, n_cold)),
        }), check_ids=False)
    ds.compact("sv")
    root = os.path.join(tmp, "s")
    persist.save(ds, root)
    lam = LambdaStore(
        ds, "sv", config=StreamConfig(),
        wal_dir=os.path.join(root, "_wal"),
        wal_config=WalConfig(sync="always"),
    )
    srv = lam.serve(port=0)
    probes = [
        "bbox(geom, -40, -40, 0, 0)", "bbox(geom, 10, 10, 60, 50)",
        "bbox(geom, -170, -80, -100, 0)",
    ]
    warm = DataClient(srv.url, keep_alive=True)
    for q in probes:
        warm.query("sv", cql=q)  # warm scan kernels through the socket

    # 1. wire == in-process, then the mixed closed loop
    from urllib.parse import quote

    _, _, raw = warm.request("GET", "/query/sv?cql=" + quote(probes[0]))
    identical = raw == _geojson(lam.query(probes[0])).encode()

    stop = threading.Event()
    reads = [0, 0]
    ing_rows = [0]

    def reader(slot):
        c = DataClient(srv.url, keep_alive=True)
        while not stop.is_set():
            c.query("sv", cql=probes[reads[slot] % len(probes)], limit=256)
            reads[slot] += 1

    def ingester():
        c = DataClient(srv.url, keep_alive=True)
        b = 0
        while not stop.is_set():
            k = 200
            feats = [
                {"type": "Feature", "id": f"m{b}-{j}",
                 "geometry": {"type": "Point",
                              "coordinates": [float(b % 90), float(j % 45)]},
                 "properties": {"name": "m", "dtg": t0_ms + b * k + j}}
                for j in range(k)
            ]
            ack = c.ingest("sv", {"type": "FeatureCollection",
                                  "features": feats})
            ing_rows[0] += ack["acked"]
            b += 1

    ts = [threading.Thread(target=reader, args=(i,)) for i in range(2)]
    ts.append(threading.Thread(target=ingester))
    t0 = time.perf_counter()
    for t in ts:
        t.start()
    time.sleep(read_s)
    stop.set()
    for t in ts:
        t.join(30)
    dt = time.perf_counter() - t0
    read_qps = sum(reads) / dt
    ingest_rows_per_s = ing_rows[0] / dt
    log(
        f"[serve_http] mixed: {read_qps:,.0f} read q/s, "
        f"{ingest_rows_per_s:,.0f} ingested rows/s, identical={identical}"
    )

    # 2. adversarial-tenant fairness: compliant p99 alone vs flooded
    def compliant_loop(seconds, warm_s=1.0):
        # the first second is discarded: the adaptive window and the
        # per-tenant state settle before anything lands in the p99
        c = DataClient(srv.url, tenant="compliant", keep_alive=True)
        lats: list = []
        t0 = time.perf_counter()
        i = 0
        while True:
            q0 = time.perf_counter()
            c.query("sv", cql=probes[i % len(probes)], limit=256)
            if q0 - t0 >= warm_s:
                lats.append(time.perf_counter() - q0)
            i += 1
            if time.perf_counter() - t0 >= warm_s + seconds:
                return lats

    iso = compliant_loop(fair_s)
    srv.tenants.configure("adversary", queue_max=1)
    flood_stop = threading.Event()

    def adversary():
        c = DataClient(srv.url, tenant="adversary", timeout=10.0,
                       keep_alive=True)
        cheap = "bbox(geom, 3.0, 3.0, 3.5, 3.5)"  # volumetric: tiny probes
        while not flood_stop.is_set():
            try:
                c.query("sv", cql=cheap, limit=1)
            except ServeError as e:  # shed 429: back off by the hint only
                time.sleep(min(e.retry_after or 0.05, 0.25))
            except OSError:
                pass

    floods = [threading.Thread(target=adversary) for _ in range(3)]
    for t in floods:
        t.start()
    try:
        flooded = compliant_loop(fair_s)
    finally:
        flood_stop.set()
        for t in floods:
            t.join(30)
    p99_iso = float(np.percentile(np.array(iso) * 1e3, 99))
    p99_flood = float(np.percentile(np.array(flooded) * 1e3, 99))
    degradation = p99_flood / max(p99_iso, 1e-9)
    trep = {r["tenant"]: r for r in srv.tenants.report()["tenants"]}
    adversary_shed = int(trep.get("adversary", {}).get("shed", 0))
    log(
        f"[serve_http] fairness: compliant p99 {p99_iso:.1f} ms alone, "
        f"{p99_flood:.1f} ms flooded (x{degradation:.2f}); adversary "
        f"shed {adversary_shed:,} of "
        f"{trep.get('adversary', {}).get('submitted', 0):,} submitted"
    )

    # 3. ack durability: HTTP-acked rows survive kill -9 + recover
    dur = DataClient(srv.url, keep_alive=True)
    acked: list = []
    for b in range(10):
        feats = [
            {"type": "Feature", "id": f"dur{b}-{j}",
             "geometry": {"type": "Point",
                          "coordinates": [float(b), float(j % 80)]},
             "properties": {"name": "d", "dtg": t0_ms + b * 100 + j}}
            for j in range(100)
        ]
        ack = dur.ingest("sv", {"type": "FeatureCollection",
                                "features": feats})
        if ack["acked"] == 100 and ack["durable"]:
            acked.extend(f"dur{b}-{j}" for j in range(100))
    srv.close()
    lam.wal.crash()  # kill -9: no close, no checkpoint
    rec = LambdaStore.recover(root)
    got = {str(i) for i in rec.query("INCLUDE").ids.tolist()}
    acked_loss = sum(1 for fid in acked if fid not in got)
    # everything the run ever POSTed carries an "m"/"dur" prefix and the
    # cold rows are plain indices — anything else came from nowhere
    attempted = {str(i) for i in range(n_cold)}
    invented = sum(
        1 for fid in got
        if fid not in attempted and not fid.startswith(("m", "dur"))
    )
    log(
        f"[serve_http] durability: acked={len(acked):,} loss={acked_loss} "
        f"invented={invented}"
    )
    lam.flusher.close()
    rec.close()
    shutil.rmtree(tmp, ignore_errors=True)

    rows = [
        {
            "scenario": "serve_http_mixed",
            "cold_rows": n_cold, "read_s": read_s,
            "read_qps": round(read_qps, 1),
            "ingest_rows_per_s": round(ingest_rows_per_s, 1),
            "ingested_rows": int(ing_rows[0]),
            "identical": bool(identical),
        },
        {
            "scenario": "serve_http_fairness",
            "compliant_requests": len(iso) + len(flooded),
            "compliant_p99_isolated_ms": round(p99_iso, 3),
            "compliant_p99_flood_ms": round(p99_flood, 3),
            "degradation": round(degradation, 3),
            "adversary_shed": adversary_shed,
            "identical": True,
        },
        {
            "scenario": "serve_http_durability",
            "acked_rows": len(acked),
            "acked_loss": int(acked_loss),
            "invented": int(invented),
            "identical": bool(acked_loss == 0 and invented == 0),
        },
    ]

    import jax

    payload = {"platform": jax.default_backend(), "rows": rows}
    if out_path is None:
        out_path = os.environ.get(
            "GEOMESA_BENCH_SERVE_OUT"
        ) or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_SERVE_HTTP.json",
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "serve_http_read_qps",
        "value": rows[0]["read_qps"],
        "unit": "q/s",
        "degradation": rows[1]["degradation"],
        "adversary_shed": adversary_shed,
        "acked_loss": int(acked_loss), "invented": int(invented),
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


def config_tiles(out_path: "str | None" = None):
    """Live map-tile scenario (docs/tiles.md): one cache-backed
    DataStore mounted on a real socket, two measurements emitted as
    BENCH_TILES.json.

    1. **Precomposed vs from-scratch at matched workload** — a reader
       fetches a fixed tile working set (zooms 1..3, Arrow grid
       format) in a closed loop through the stdlib DataClient while an
       ingest thread POSTs paced localized batches; then the SAME tile
       set is served with ``mode=fresh`` (the from-scratch oracle)
       under the same sustained ingest. Per-zoom speedup = fresh p50 /
       warm p50 over the steady-state tiles outside the write
       footprint; the gate requires >=5x at every measured zoom, plus
       a p99 ceiling over EVERY fetch (recomposes and ingest stalls
       included) and a cache-hit floor. The ``identical`` flag is the
       in-bench oracle: after the loops, every sampled tile's warm
       Arrow bytes equal its ``mode=fresh`` bytes at zooms 0..3.
    2. **Scoped invalidation, both directions** — with the pyramid
       fully warm, one localized ingest batch lands; a tile far from
       the write must keep answering 304 to its old ETag (still warm,
       zero aggregation work) while the touched tile recomposes under
       a new ETag.

    Env knobs: GEOMESA_BENCH_TILES_COLD (cold rows),
    GEOMESA_BENCH_TILES_S (seconds for the warm closed loop),
    GEOMESA_BENCH_TILES_OUT (fresh-side output path)."""
    import threading

    from geomesa_tpu import conf
    from geomesa_tpu.cache import CacheConfig
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.serving import DataClient
    from geomesa_tpu.sft import FeatureType

    n_cold = int(os.environ.get("GEOMESA_BENCH_TILES_COLD", 60_000))
    read_s = float(os.environ.get("GEOMESA_BENCH_TILES_S", 2.0))
    t0_ms = 1_717_200_000_000
    rng = np.random.default_rng(SEED + 123)

    ds = DataStore(metrics=MetricsRegistry(),
                   cache=CacheConfig(max_bytes=1 << 24))
    sft = FeatureType.from_spec(
        "tl", "name:String,dtg:Date,*geom:Point:srid=4326"
    )
    ds.create_schema(sft)
    ds.write("tl", FeatureCollection.from_columns(
        sft, np.arange(n_cold).astype(str), {
            "name": np.array(["t"] * n_cold),
            "dtg": t0_ms + rng.integers(0, 86_400_000, n_cold),
            "geom": (rng.uniform(-170, 170, n_cold),
                     rng.uniform(-80, 80, n_cold)),
        }), check_ids=False)
    ds.compact("tl")

    # px=128 bounds the Arrow body at 128 KB/tile so the loop measures
    # the serving tier, not loopback bulk transfer
    conf.TILES_PX.set(128)
    try:
        srv = ds.serve(port=0)
        warm = DataClient(srv.url, keep_alive=True)
        # the tile working set: every z1 tile, 16 each at z2/z3
        tile_sets: dict = {}
        for z in (1, 2, 3):
            allt = [(z, x, y) for x in range(2 ** (z + 1))
                    for y in range(2 ** z)]
            if len(allt) > 16:
                pick = sorted(rng.choice(len(allt), 16, replace=False))
                allt = [allt[i] for i in pick]
            tile_sets[z] = allt
        working = [t for z in (1, 2, 3) for t in tile_sets[z]]
        # fetching both roots composes the ENTIRE pyramid once
        for x in (0, 1):
            warm.tile("tl", "count", 0, x, 0, fmt="arrow")

        # sustained localized ingest: every batch lands in lon
        # [95, 111] x lat [25, 44] — inside z3 tile (12, 2) and far
        # from z3 tile (0, 0)
        stop = threading.Event()
        ing_rows = [0]

        def ingester():
            # paced, not closed-loop: each POST costs tens of ms of
            # host CPU (JSON parse + sorted write + invalidation), so
            # an unthrottled loop starves the readers and measures the
            # GIL, not the pyramid; ~1.5k rows/s in 100-row quanta is
            # sustained ingest that still re-dirties the working set
            # many times per second, with bounded per-POST stalls
            c = DataClient(srv.url, keep_alive=True)
            b = 0
            while not stop.is_set():
                k = 100
                r = np.random.default_rng(SEED + b)
                xs = r.uniform(95.0, 111.0, k)
                ys = r.uniform(25.0, 44.0, k)
                feats = [
                    {"type": "Feature", "id": f"mt{b}-{j}",
                     "geometry": {"type": "Point",
                                  "coordinates": [float(xs[j]),
                                                  float(ys[j])]},
                     "properties": {"name": "m", "dtg": t0_ms + b * k + j}}
                    for j in range(k)
                ]
                ack = c.ingest("tl", {"type": "FeatureCollection",
                                      "features": feats})
                ing_rows[0] += ack["acked"]
                b += 1
                stop.wait(0.05)

        def fetch_loop(seconds, mode=None, passes=None):
            """Closed loop over the working set; (tile, seconds) samples."""
            c = DataClient(srv.url, keep_alive=True)
            lats: list = []
            t0 = time.perf_counter()
            i = 0
            while True:
                tile = working[i % len(working)]
                q0 = time.perf_counter()
                c.tile("tl", "count", *tile, fmt="arrow", mode=mode)
                lats.append((tile, time.perf_counter() - q0))
                i += 1
                if passes is not None:
                    if i >= passes * len(working):
                        return lats
                elif time.perf_counter() - t0 >= seconds:
                    return lats

        def touches_writes(tile):
            """Does this tile's bbox intersect the ingest footprint?"""
            z, x, y = tile
            w = 360.0 / 2 ** (z + 1)
            lo_x, lo_y = -180.0 + x * w, 90.0 - (y + 1) * w
            return not (lo_x + w < 95.0 or lo_x > 111.0
                        or lo_y + w < 25.0 or lo_y > 44.0)

        ing = threading.Thread(target=ingester)
        ing.start()
        try:
            c0 = ds.metrics.counter_value("geomesa.tiles.compose")
            t0 = time.perf_counter()
            warm_lats = fetch_loop(read_s)
            warm_dt = time.perf_counter() - t0
            composes = ds.metrics.counter_value(
                "geomesa.tiles.compose"
            ) - c0
            fresh_lats = fetch_loop(0, mode="fresh", passes=2)
        finally:
            stop.set()
            ing.join(30)

        # warm_p99 and hit_ratio cover EVERY fetch — including the
        # tiles the ingest keeps re-dirtying, whose refetches pay the
        # recompose (the amortized maintenance cost). The per-zoom
        # speedup is computed on the steady-state tiles OUTSIDE the
        # write footprint (same tiles both sides): a recomposing tile's
        # cost is ~one leaf scan by construction — the same work the
        # from-scratch path pays on every request — so folding it into
        # the warm mean would just measure how often this loop happens
        # to land on the handful of touched tiles, not the serving path
        warm_ms = np.array([s * 1e3 for _, s in warm_lats])
        warm_p99 = float(np.percentile(warm_ms, 99))
        hit_ratio = 1.0 - composes / max(len(warm_lats), 1)
        # medians, not means: a fetch that lands behind an in-flight
        # ingest POST stalls for the POST's GIL hold on either side of
        # the comparison — that tail is real and gated via warm_p99_ms,
        # but inside the speedup ratio it is multiplicative noise
        per_zoom = {}
        for z in (1, 2, 3):
            steady = [t for t in tile_sets[z] if not touches_writes(t)]
            w = np.array([s for t, s in warm_lats if t in steady])
            f = np.array([s for t, s in fresh_lats if t in steady])
            per_zoom[str(z)] = {
                "steady_tiles": len(steady),
                "warm_ms_p50": round(float(np.median(w)) * 1e3, 3),
                "fresh_ms_p50": round(float(np.median(f)) * 1e3, 3),
                "speedup": round(float(np.median(f) / np.median(w)), 2),
            }
        speedup_min = min(v["speedup"] for v in per_zoom.values())
        log(
            f"[tiles] warm {len(warm_lats) / warm_dt:,.0f} fetch/s "
            f"p99 {warm_p99:.2f} ms, hit ratio {hit_ratio:.3f}, "
            f"speedup min x{speedup_min:.1f} "
            f"({ {z: v['speedup'] for z, v in per_zoom.items()} }), "
            f"{ing_rows[0]:,} rows ingested alongside"
        )

        # in-bench bit-identity oracle: warm bytes == from-scratch bytes
        identical = True
        checked = 0
        for z in (0, 1, 2, 3):
            allt = [(x, y) for x in range(2 ** (z + 1))
                    for y in range(2 ** z)]
            if len(allt) > 12:
                pick = sorted(rng.choice(len(allt), 12, replace=False))
                allt = [allt[i] for i in pick]
            for x, y in allt:
                _, _, wb = warm.tile("tl", "count", z, x, y, fmt="arrow")
                _, _, fb = warm.tile("tl", "count", z, x, y, fmt="arrow",
                                     mode="fresh")
                identical = identical and wb == fb
                checked += 1
        log(f"[tiles] identity: {checked} tiles swept, "
            f"identical={identical}")

        # 2. scoped invalidation, both directions
        for x in (0, 1):  # re-warm everything the loops dirtied
            warm.tile("tl", "count", 0, x, 0, fmt="arrow")
        far, touched = (3, 0, 0), (3, 12, 2)
        _, far_h, _ = warm.tile("tl", "count", *far, fmt="arrow")
        _, tch_h, _ = warm.tile("tl", "count", *touched, fmt="arrow")
        k = 64
        feats = [
            {"type": "Feature", "id": f"inv-{j}",
             "geometry": {"type": "Point",
                          "coordinates": [100.0 + (j % 8), 30.0 + j % 12]},
             "properties": {"name": "i", "dtg": t0_ms + j}}
            for j in range(k)
        ]
        warm.ingest("tl", {"type": "FeatureCollection", "features": feats})
        st_far, far_h2, _ = warm.tile("tl", "count", *far, fmt="arrow",
                                      etag=far_h["ETag"])
        st_t, tch_h2, _ = warm.tile("tl", "count", *touched, fmt="arrow",
                                    etag=tch_h["ETag"])
        far_304 = st_far == 304 and far_h2["ETag"] == far_h["ETag"]
        touched_recomposed = st_t == 200 and tch_h2["ETag"] != tch_h["ETag"]
        log(
            f"[tiles] invalidation: far tile {far} -> {st_far} "
            f"(etag kept={far_h2['ETag'] == far_h['ETag']}), touched "
            f"{touched} -> {st_t} (etag moved="
            f"{tch_h2['ETag'] != tch_h['ETag']})"
        )
        srv.close()
    finally:
        conf.TILES_PX.clear()

    rows = [
        {
            "scenario": "tiles_serving",
            "cold_rows": n_cold, "read_s": read_s,
            "zooms_measured": len(per_zoom),
            "working_set_tiles": len(working),
            "fetch_per_s": round(len(warm_lats) / warm_dt, 1),
            "warm_p99_ms": round(warm_p99, 3),
            "hit_ratio": round(hit_ratio, 4),
            "per_zoom": per_zoom,
            "speedup_min": speedup_min,
            "ingest_rows_alongside": int(ing_rows[0]),
            "identity_tiles_checked": checked,
            "identical": bool(identical),
        },
        {
            "scenario": "tiles_invalidation",
            "warmed_tiles": len(working),
            "far_304": bool(far_304),
            "touched_recomposed": bool(touched_recomposed),
            "identical": bool(far_304 and touched_recomposed),
        },
    ]

    import jax

    payload = {"platform": jax.default_backend(), "rows": rows}
    if out_path is None:
        out_path = os.environ.get(
            "GEOMESA_BENCH_TILES_OUT"
        ) or os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "BENCH_TILES.json",
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "tiles_speedup_min",
        "value": speedup_min,
        "unit": "x",
        "warm_p99_ms": rows[0]["warm_p99_ms"],
        "hit_ratio": rows[0]["hit_ratio"],
        "identical": bool(identical),
        "far_304": bool(far_304),
        "touched_recomposed": bool(touched_recomposed),
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


def config_pod(out_path: "str | None" = None):
    """Multi-host pod scenario (docs/distributed.md): H=4 sim hosts
    against the H=1 flat mesh on the SAME device budget, emitted as
    BENCH_POD.json.

    1. **Selective scan** — a closed loop of small-bbox queries against
       a ``DataStore(mesh=host_group)`` (per-host contiguous shards;
       non-owning hosts do zero work) vs the identical store on the
       flat single-process mesh over the same devices. The speedup is
       REAL wall-clock work reduction — fewer, smaller per-host legs —
       and the ``identical`` flag is the in-bench differential: every
       probe (and a fused ``query_many`` batch) answers with exactly
       the flat store's ids.
    2. **Host-local ingest** — the collection partitions by owner and
       each host's pipelined ``BulkLoader`` leg is timed IN ISOLATION;
       the pod wall-clock is the slowest host's leg (in deployment each
       host is its own machine, so in-process thread concurrency would
       only measure this bench host's single-core contention, not pod
       capacity — the replica read-scaling measurement's reasoning).
       The ``identical`` flag checks the union of per-host shards
       answers exactly like the flat store.

    Needs >= hosts devices (CPU runs: XLA_FLAGS=
    --xla_force_host_platform_device_count=8). Env knobs:
    GEOMESA_BENCH_POD_HOSTS, GEOMESA_BENCH_POD_N (scan rows),
    GEOMESA_BENCH_POD_INGEST_N, GEOMESA_BENCH_POD_READ_S,
    GEOMESA_BENCH_POD_OUT (fresh-side output path)."""
    import zlib

    import jax

    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.ingest.pipeline import BulkLoader
    from geomesa_tpu.pod import make_host_group
    from geomesa_tpu.sft import FeatureType

    hosts = int(os.environ.get("GEOMESA_BENCH_POD_HOSTS", 4))
    n_scan = int(os.environ.get("GEOMESA_BENCH_POD_N", 150_000))
    n_ingest = int(os.environ.get("GEOMESA_BENCH_POD_INGEST_N", 400_000))
    read_s = float(os.environ.get("GEOMESA_BENCH_POD_READ_S", 3.0))
    n_dev = len(jax.devices())
    if n_dev < hosts:
        raise RuntimeError(
            f"config_pod needs >= {hosts} devices, found {n_dev}; on CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    group = make_host_group(
        hosts=hosts, devices_per_host=n_dev // hosts, driver="sim"
    )
    t0_ms = 1_704_067_200_000
    spec = "dtg:Date,*geom:Point:srid=4326"

    def point_fc(sft, n, seed):
        rng = np.random.default_rng(seed)
        return FeatureCollection.from_columns(
            sft, np.arange(n).astype(str),
            {"dtg": t0_ms + rng.integers(0, 20 * 86_400_000, n),
             "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n))},
        )

    def build(mesh, n, seed):
        sft = FeatureType.from_spec("pp", spec)
        ds = DataStore(mesh=mesh)
        ds.create_schema(sft)
        ds.write("pp", point_fc(sft, n, seed), check_ids=False)
        ds.compact("pp")
        return ds

    # 1. selective scan: pod vs flat, same devices, same rows
    pod = build(group, n_scan, SEED + 120)
    flat = build(group.flat_mesh(), n_scan, SEED + 120)
    rng = np.random.default_rng(SEED + 121)
    probes = []
    for _ in range(12):
        x0, y0 = rng.uniform(-55, 40), rng.uniform(-40, 30)
        probes.append(
            f"bbox(geom, {x0:.3f}, {y0:.3f}, {x0 + 4:.3f}, {y0 + 3:.3f})"
        )

    def ids_of(fc):
        return sorted(np.asarray(fc.ids, dtype=str).tolist())

    for ds in (pod, flat):
        for q in probes:
            ds.query("pp", q)  # warm the per-variant kernels
    scan_identical = all(
        ids_of(pod.query("pp", q)) == ids_of(flat.query("pp", q))
        for q in probes
    ) and all(
        ids_of(a) == ids_of(b)
        for a, b in zip(pod.query_many("pp", probes),
                        flat.query_many("pp", probes))
    )

    def measure(ds):
        k = 0
        t0 = time.perf_counter()
        while True:
            ds.query("pp", probes[k % len(probes)])
            k += 1
            dt = time.perf_counter() - t0
            if dt >= read_s:
                return k / dt

    pod_qps = measure(pod)
    flat_qps = measure(flat)
    scan_speedup = pod_qps / max(flat_qps, 1e-9)
    log(
        f"[pod] selective scan H={hosts}: {pod_qps:,.1f} q/s vs flat "
        f"{flat_qps:,.1f} q/s (x{scan_speedup:.2f}), identical="
        f"{scan_identical}"
    )

    # 2. host-local ingest: per-owner partitions, each host's loader
    # leg timed in isolation; pod wall = the slowest host's leg
    sft = FeatureType.from_spec("pp", spec)
    fc = point_fc(sft, n_ingest, SEED + 122)
    owners = np.array(
        [zlib.crc32(str(i).encode()) % hosts for i in fc.ids], np.int64
    )

    def load(mesh, sub):
        ds = DataStore(mesh=mesh)
        ds.create_schema(FeatureType.from_spec("pp", spec))
        t0 = time.perf_counter()
        loader = BulkLoader(ds, "pp")
        loader.put(sub)
        loader.close()
        return ds, time.perf_counter() - t0

    flat_ing, flat_s = load(group.flat_mesh(), fc)
    host_stores, host_s = [], []
    for h in range(hosts):
        ds, t = load(group.mesh(h), fc.take(np.flatnonzero(owners == h)))
        host_stores.append(ds)
        host_s.append(t)
    pod_model_s = max(host_s)
    ingest_speedup = flat_s / max(pod_model_s, 1e-9)
    ing_q = "bbox(geom, -20, -15, 10, 12)"
    union_ids = sorted(
        i for ds in host_stores
        for i in np.asarray(ds.query("pp", ing_q).ids, dtype=str).tolist()
    )
    ingest_identical = (
        union_ids == ids_of(flat_ing.query("pp", ing_q))
        and sum(ds.count("pp") for ds in host_stores)
        == flat_ing.count("pp") == n_ingest
    )
    log(
        f"[pod] host-local ingest: flat {flat_s:.2f}s vs slowest host "
        f"{pod_model_s:.2f}s (x{ingest_speedup:.2f} host-parallel "
        f"model), identical={ingest_identical}"
    )

    rows = [
        {
            "scenario": "pod_scan",
            "hosts": hosts, "devices": n_dev, "rows": n_scan,
            "read_s": read_s,
            "pod_qps": round(pod_qps, 1),
            "flat_qps": round(flat_qps, 1),
            "scan_speedup": round(scan_speedup, 3),
            "identical": bool(scan_identical),
        },
        {
            "scenario": "pod_ingest",
            "hosts": hosts, "rows": n_ingest,
            "flat_s": round(flat_s, 4),
            "host_s": [round(t, 4) for t in host_s],
            "pod_model_s": round(pod_model_s, 4),
            "ingest_speedup": round(ingest_speedup, 3),
            "identical": bool(ingest_identical),
        },
    ]

    payload = {"platform": jax.default_backend(), "rows": rows}
    if out_path is None:
        out_path = os.environ.get("GEOMESA_BENCH_POD_OUT") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_POD.json"
        )
    try:
        with open(out_path, "w") as fh:
            json.dump(payload, fh, indent=2)
    except OSError as e:  # pragma: no cover - read-only checkout
        log(f"WARNING: could not write {out_path}: {e}")

    rec_line = {
        "metric": "pod_scan_speedup",
        "value": rows[0]["scan_speedup"],
        "unit": "x",
        "ingest_speedup": rows[1]["ingest_speedup"],
        "identical": bool(scan_identical and ingest_identical),
    }
    print(json.dumps(rec_line), flush=True)
    return rec_line


def main():
    """The bench, in THIS process: device check, link probe, then every
    selected config. There is one process and no fallback: when JAX's
    first device is not a TPU nothing is timed and nothing is printed
    (exit 2), and a config that raises ends the run with its traceback.
    A CPU rehearsal of one scenario's logic is what the slow-marked tests
    do: they import this module and call ``config_*`` directly."""
    import jax

    devices = jax.devices()
    log(f"devices: {devices}")
    if devices[0].platform != "tpu":
        log(
            f"FATAL: bench.py times the TPU and JAX reports "
            f"{devices[0].platform!r}; refusing to run (no row printed)"
        )
        sys.exit(2)
    _probe_link()
    runners = {
        "1": config1_z3, "2": config2_z2, "3": config3_xz2,
        "4": config4_join, "5": config5_knn, "cache": config_cache,
        "serving": config_serving, "ingest": config_ingest,
        "fused": config_fused, "pip_join": config_pip_join,
        "stream": config_stream, "wal": config_wal, "knn": config_knn,
        "obs": config_obs, "standing": config_standing,
        "ops": config_ops, "replica": config_replica,
        "serve_http": config_serve_http, "tiles": config_tiles,
        "drift": config_drift, "pod": config_pod,
    }
    results: dict[str, dict] = {}
    for c in CONFIGS:
        c = c.strip()
        t0 = time.perf_counter()
        results[c] = runners[c]()
        log(f"[config {c}] total {time.perf_counter() - t0:.1f}s")
    if len(results) > 1 and results.get("1") is not None:
        # repeat the headline (config 1) as the LAST line too: a driver
        # parsing either the first or the final JSON line gets the
        # north-star metric, not whichever config happened to run last
        print(json.dumps(results["1"]), flush=True)


LINK_PROFILE: dict = {}


def link_readings(shape, n):
    """``n`` readings of the host<->device link for one f32 ``shape``:
    (device_get seconds, dispatch + device_get seconds) per reading, and
    the array's bytes. Every reading pulls a FRESH device array: a
    jax.Array keeps its host copy after the first device_get, so
    re-pulling one times a host memcpy (what this probe read before
    PR 21: "0.0 ms, 61 GB/s")."""
    import jax
    import jax.numpy as jnp

    bump = jax.jit(lambda a: a + 1)
    a = bump(jnp.zeros(shape, jnp.float32))
    jax.device_get(a)  # compile + settle
    pulls, trips = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        a = bump(a)
        a.block_until_ready()
        t1 = time.perf_counter()
        jax.device_get(a)
        t2 = time.perf_counter()
        pulls.append(t2 - t1)
        trips.append(t2 - t0)
    return pulls, trips, a.nbytes


def _probe_link():
    """Measure the host<->device link this run sees and install the
    constants derived from it (PERF.md "PR 21" has what a local v5e
    measures; the 66 ms design point in scan/block_kernels.py is an
    installation that is gone). Logged and attached to the config rows.
    A failing probe fails the run."""

    def pull_s(shape):
        pulls, _, nbytes = link_readings(shape, 9)
        return float(np.median(pulls)), nbytes

    t_small, b_small = pull_s((8, 128))
    t_big, b_big = pull_s((1024, 1024))  # 4 MiB
    rtt_ms = t_small * 1e3
    LINK_PROFILE.update(link_rtt_ms=round(rtt_ms, 3))
    # bandwidth from the SIZE DELTA of the two pulls; where the delta
    # drowns in noise (t_big <= t_small) omit it rather than record an
    # absurd number in the artifact of record
    mbps = None
    if t_big > t_small * 1.2:
        mbps = (b_big - b_small) / 1e6 / (t_big - t_small)
        LINK_PROFILE.update(link_pull_mb_s=round(mbps, 1))
    log(
        f"link probe: pull floor ~{rtt_ms:.3f} ms, "
        + (f"~{mbps:.0f} MB/s" if mbps else "bandwidth not resolvable")
    )
    # round 11: re-derive the fused-chunk slot cap and M-bucket floor
    # from the MEASURED link, installed before any table builds or
    # warmups so every compiled shape uses them; the chosen constants
    # ride LINK_PROFILE into each scenario row (PERF.md §14)
    from geomesa_tpu.scan import block_kernels as bk

    derived = bk.derive_link_constants(rtt_ms, mbps)
    bk.set_link_constants(derived)
    LINK_PROFILE.update(
        fused_chunk_slots=derived["fused_chunk_slots"],
        m_floor=derived["m_floor"],
    )
    log(
        f"link-derived constants: fused_chunk_slots="
        f"{derived['fused_chunk_slots']}, m_floor={derived['m_floor']}"
    )


if __name__ == "__main__":
    main()
