"""1B-row north-star validation (VERDICT r4 #4, option (a) layout).

Builds the config-1 store at N=1e9 with the packed-time z3 layout
(12 B/row device columns) and validates end to end:
- per-chip HBM accounting printed against the v5e 16 GB budget;
- a query set checked EXACTLY against chunked brute-force truth;
- a 2M recent append through the delta tier + compaction, re-checked.

On the TPU the same configuration runs via
``GEOMESA_BENCH_N=1000000000 python bench.py`` (bench.py enables
packed-time past 600M rows). This script is the CPU-backend scale
validation (PERF.md 4d at 100M, extended to 1e9): the host "device"
is RAM, so the layout, sort, scan, decode and refinement paths are the
real ones; only the kernel backend differs (XLA gather vs Pallas DMA).

Usage: JAX_PLATFORMS=cpu python scripts/validate_1b.py  [N override via
GEOMESA_1B_N]. The platform is whatever JAX is given from outside (no
default is set here) and the output row names it (``platform``,
``device_kind``): a row that says ``cpu`` is a layout validation, never
a chip time.
"""

import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType

N = int(os.environ.get("GEOMESA_1B_N", 1_000_000_000))
DAY = 86_400_000
SEED = 7


def log(msg):
    print(f"[1b] {msg}", file=sys.stderr, flush=True)


def gen_points(n, rng):
    """GDELT-shaped points, f32, chunked generation (no f64 temporaries
    at the full N)."""
    x = np.empty(n, np.float32)
    y = np.empty(n, np.float32)
    cx = rng.uniform(-160, 160, 64)
    cy = rng.uniform(-55, 65, 64)
    chunk = 50_000_000
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        m = e - s
        half = m // 2
        x[s : s + half] = rng.uniform(-180, 180, half).astype(np.float32)
        y[s : s + half] = rng.uniform(-90, 90, half).astype(np.float32)
        which = rng.integers(0, 64, m - half)
        x[s + half : e] = np.clip(
            cx[which] + rng.normal(0, 3.0, m - half), -180, 180
        ).astype(np.float32)
        y[s + half : e] = np.clip(
            cy[which] + rng.normal(0, 2.0, m - half), -90, 90
        ).astype(np.float32)
        log(f"gen {e:,}/{n:,}")
    return x, y


def truth_count_ids(x, y, t, q, sample_cap=50):
    """Chunked brute force: (count, first ids) for one query tuple."""
    x0, y0, x1, y1, lo, hi = q
    total = 0
    ids = []
    chunk = 100_000_000
    for s in range(0, len(x), chunk):
        e = min(s + chunk, len(x))
        m = (
            (x[s:e] >= x0) & (x[s:e] <= x1)
            & (y[s:e] >= y0) & (y[s:e] <= y1)
            & (t[s:e] >= lo) & (t[s:e] < hi)
        )
        total += int(m.sum())
        if len(ids) < sample_cap:
            ids.extend((s + np.flatnonzero(m)[: sample_cap - len(ids)]).tolist())
    return total, ids


def main():
    rng = np.random.default_rng(SEED)
    t_start = time.perf_counter()
    log(f"generating {N:,} points ...")
    x, y = gen_points(N, rng)
    t0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
    span = 120 * DAY
    t = t0 + rng.integers(0, span, N)
    log(f"generated in {time.perf_counter() - t_start:.0f}s")

    sft = FeatureType.from_spec("gdelt", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3"
    sft.user_data["geomesa.z3.packed-time"] = "true"
    ds = DataStore()
    ds.create_schema(sft)
    fc = FeatureCollection.from_columns(
        sft, np.arange(N), {"dtg": t, "geom": (x, y)}
    )
    t_in = time.perf_counter()
    ds.write("gdelt", fc, check_ids=False)
    ingest_s = time.perf_counter() - t_in
    table = ds.table("gdelt", "z3")
    tbl = getattr(table, "main", table)
    dev_gb = tbl.nbytes_device / 1e9
    log(
        f"ingest {ingest_s:.0f}s ({N / ingest_s:,.0f} rows/s); device "
        f"columns {dev_gb:.2f} GB ({tbl.nbytes_device / N:.1f} B/row) "
        f"vs v5e HBM 16 GB"
    )

    qs = []
    r = np.random.default_rng(SEED + 1)
    for _ in range(12):
        w = float(r.choice([1.0, 5.0, 20.0, 40.0]))
        qx = float(r.uniform(-175, 175 - w))
        qy = float(r.uniform(-85, 85 - w / 2))
        lo = int(t0 + r.integers(0, span - 7 * DAY))
        hi = lo + int(r.choice([1, 7, 21])) * DAY
        # round THROUGH the expr's %.4f formatting so the brute-force
        # truth tests the exact values the parser will see (an unrounded
        # bound differs by up to 5e-5 deg — at 1e9 rows that sliver holds
        # a point every few million hits)
        qs.append((
            float(f"{qx:.4f}"), float(f"{qy:.4f}"),
            float(f"{qx + w:.4f}"), float(f"{qy + w / 2:.4f}"), lo, hi,
        ))

    lat = []
    ok = 0
    for i, q in enumerate(qs):
        expr = (
            f"bbox(geom, {q[0]:.4f}, {q[1]:.4f}, {q[2]:.4f}, {q[3]:.4f}) "
            f"AND dtg DURING {np.datetime64(q[4], 'ms')}Z/"
            f"{np.datetime64(q[5], 'ms')}Z"
        )
        s = time.perf_counter()
        out = ds.query("gdelt", expr)
        lat.append(time.perf_counter() - s)
        want_n, want_ids = truth_count_ids(x, y, t, q)
        got_ids = np.asarray(out.ids)
        assert len(out) == want_n, (expr, len(out), want_n)
        assert set(want_ids) <= set(got_ids[np.isin(got_ids, want_ids)].tolist())
        ok += 1
        log(f"query {i}: {len(out):,} hits in {lat[-1]:.2f}s — exact")

    # recent-time append through the delta tier, then compaction
    n2 = 2_000_000
    t_ap = time.perf_counter()
    ds.write("gdelt", FeatureCollection.from_columns(
        sft, np.arange(N, N + n2),
        {
            "dtg": t0 + span - np.abs(r.integers(0, 3 * DAY, n2)),
            "geom": (
                r.uniform(-180, 180, n2).astype(np.float32),
                r.uniform(-90, 90, n2).astype(np.float32),
            ),
        },
    ), check_ids=False)
    append_s = time.perf_counter() - t_ap
    q = qs[0]
    expr = (
        f"bbox(geom, {q[0]:.4f}, {q[1]:.4f}, {q[2]:.4f}, {q[3]:.4f}) "
        f"AND dtg DURING {np.datetime64(q[4], 'ms')}Z/{np.datetime64(q[5], 'ms')}Z"
    )
    n_after = len(ds.query("gdelt", expr))
    log(f"append 2M in {append_s:.1f}s; post-append query {n_after:,} hits")
    # exactness across main + delta: re-check query 0's truth including
    # the appended rows (their dtg window rarely overlaps q0, but the
    # check is structural, not probabilistic)
    fc2 = ds.features("gdelt")
    ax = np.asarray(fc2.geom_column.x)[N:]
    ay = np.asarray(fc2.geom_column.y)[N:]
    at = np.asarray(fc2.columns["dtg"])[N:]
    want0, _ = truth_count_ids(x, y, t, q)
    want_extra = int(
        ((ax >= q[0]) & (ax <= q[2]) & (ay >= q[1]) & (ay <= q[3])
         & (at >= q[4]) & (at < q[5])).sum()
    )
    assert n_after == want0 + want_extra, (n_after, want0, want_extra)
    t_c = time.perf_counter()
    ds.compact("gdelt")
    compact_s = time.perf_counter() - t_c
    n_compacted = len(ds.query("gdelt", expr))
    assert n_compacted == n_after, (n_compacted, n_after)
    log(f"compaction {compact_s:.1f}s; post-compaction query exact")

    print(json.dumps({
        "n_rows": N,
        "device_bytes_per_row": round(tbl.nbytes_device / N, 2),
        "device_gb": round(dev_gb, 2),
        "hbm_budget_gb": 16.0,
        "ingest_rows_per_s": round(N / ingest_s, 1),
        "queries_exact": ok,
        "query_p50_s": round(float(np.percentile(lat, 50)), 2),
        "append_2m_s": round(append_s, 1),
        "post_append_exact": True,
        "compact_s": round(compact_s, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    }), flush=True)


if __name__ == "__main__":
    main()
