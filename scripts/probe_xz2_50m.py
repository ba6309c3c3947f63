"""Probe: XZ2 (extent) query path end to end at 50M polygons.

Builds a 50M-row extent store (clustered small boxes), then times bbox
queries across selectivities — the wide-only plane rule and the XZ
candidate pruning under real skew. Run on the TPU:
    python scripts/probe_xz2_50m.py
"""

import sys; sys.path.insert(0, "/root/repo")
import time
import numpy as np
import jax
from geomesa_tpu import geometry as geo
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.filter import ecql
from geomesa_tpu.scan import block_kernels as bk

n = 50_000_000
rng = np.random.default_rng(62)
cx = rng.uniform(-160, 160, 256); cy = rng.uniform(-55, 65, 256)
which = rng.integers(0, 256, n)
x0 = np.clip(cx[which] + rng.normal(0, 0.5, n), -179.9, 179.8)
y0 = np.clip(cy[which] + rng.normal(0, 0.4, n), -89.9, 89.8)
w = rng.uniform(0.0002, 0.002, n); h = rng.uniform(0.0002, 0.002, n)
col = geo.PackedGeometryColumn.from_boxes(x0, y0, x0+w, y0+h)
sft = FeatureType.from_spec("bld", "*geom:Polygon:srid=4326")
sft.user_data["geomesa.indices.enabled"] = "xz2"
ds = DataStore(); ds.create_schema(sft)
fc = FeatureCollection.from_columns(sft, np.arange(n), {"geom": col})
t = time.perf_counter(); ds.write("bld", fc, check_ids=False)
print("ingest", round(time.perf_counter()-t, 1), flush=True)
table = ds.table("bld", "xz2")
print("n_blocks:", table.n_blocks, flush=True)
idx = ds.indexes("bld")[0]

def mk(seed, k):
    r = np.random.default_rng(seed); out = []
    for _ in range(k):
        c = r.integers(0, 256); qw = float(r.choice([0.02, 0.05, 0.1, 0.5, 2.0]))
        qx = cx[c]+r.uniform(-1, 1); qy = cy[c]+r.uniform(-0.8, 0.8)
        poly = (f"POLYGON(({qx:.4f} {qy:.4f}, {qx+qw:.4f} {qy:.4f}, "
                f"{qx+qw:.4f} {qy+qw:.4f}, {qx:.4f} {qy+qw:.4f}, {qx:.4f} {qy:.4f}))")
        out.append((qw, f"INTERSECTS(geom, {poly})"))
    return out

t=time.perf_counter()
for _, q in mk(1, 40):
    ds.query("bld", q)
print("warmup", round(time.perf_counter()-t,1), flush=True)

rows_out = []
for qw, q in mk(2, 40):
    cfg = idx.scan_config(ecql.parse(q))
    t0 = time.perf_counter()
    overlap, contained = table.candidate_spans_split(cfg)
    t_spans = time.perf_counter() - t0
    blocks = table.candidate_blocks(overlap)
    bids, n_real = bk.pad_bids(table._full_or(blocks), table.n_blocks)
    t1 = time.perf_counter()
    res = ds.query("bld", q)
    t_q = time.perf_counter() - t1
    cont_rows = contained.n_rows()
    rows_out.append((t_q, qw, len(overlap), len(contained), cont_rows,
                     len(blocks), len(bids), t_spans, len(res.ids)))
rows_out.sort(reverse=True)
print(" q_ms |  qw  | ov | cont | cont_rows | blocks | bucket | spans_ms | hits")
for t_q, qw, ov, co, cr, bl, bu, ts, h in rows_out[:12]:
    print(f"{t_q*1e3:6.0f} | {qw:4.2f} | {ov:3d} | {co:3d} | {cr:9d} | {bl:6d} | {bu:6d} | {ts*1e3:7.1f} | {h}")
tot = sum(r[0] for r in rows_out); hits = sum(r[-1] for r in rows_out)
lat = sorted(r[0] for r in rows_out)
print(f"mean {tot/40*1e3:.0f} ms  p50 {lat[20]*1e3:.0f}  p99 {lat[-1]*1e3:.0f}  hits {hits}")
