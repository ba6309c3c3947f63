"""chip_smoke.py - the store end to end on a TPU, checked against NumPy.

    python chip_smoke.py              # one chip: load, query, serve, fold, ingest
    python chip_smoke.py --chips 4    # the mesh path and its reference, nothing else

One process, one store built through the public API, GDELT-shaped data made
from ``--seed``. Every answer is compared with a plain NumPy pass over the generator's columns: row queries, counts and
tiles under the store's exact f64 semantics, the gather-free device
aggregations (density, bounds, Count() estimate) under the semantics they
document - f32 columns, box edges one ulp wide, whole-second time offsets;
a density pixel is exact for every row f32 arithmetic can place.

Exits non-zero before any work when JAX's first device is not a TPU, and
from any phase that fails: no exception is caught. One JSON object per
phase goes to stdout; the LAST line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

TYPE = "gdelt"
SPEC = "dtg:Date,*geom:Point:srid=4326"
T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
SPAN_MS = 120 * 86_400_000
N_ASKED = 100_000_000  # ISSUE 21's size
# The default is cut to the floor ISSUE 21 allows, because fold_upsert does
# not fit one chip's machine at 1e8 rows (PR 21 chip runs): the 1e8 run was
# killed in the fold phase at the machine's 40 GiB of host memory after
# every earlier phase had passed, and at 2**26 rows the device fold plan's
# eager full-table temporaries already peak at 12.2 GB of the 16 GB HBM
# (1.6 GB resident). --rows 100000000 runs everything before the fold.
N_DEFAULT = 1 << 26
N_MIN = 1 << 26  # below this a run finds faults; it is not the smoke
GRID = 256


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def host_memory_gb() -> dict:
    """This process's host memory: getrusage's resident peak and, where
    /proc/self/status has them (the chip machines' does not), the
    anonymous, file-backed and shared parts now. On a TPU host the peak
    counts pages the runtime maps for the device (PR 21: 51 GB read on a
    machine that has 40 GiB), so it bounds the working set from above."""
    out = {"maxrss": round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9, 2)}
    want = {"RssAnon": "anon", "RssFile": "file", "RssShmem": "shmem"}
    with open("/proc/self/status") as fh:
        for line in fh:
            key, _, rest = line.partition(":")
            if key in want:
                out[want[key]] = round(int(rest.split()[0]) * 1024 / 1e9, 2)
    return out


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


# ------------------------------------------------------------------ data


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


class Columns:
    """The generator's columns: the NumPy reference's whole input, owned
    by the reference (the store is handed copies). Row i has feature id
    i, so an id set is an index set. Upserts edit it the way the store is
    told to; a little spare capacity keeps appends from copying 10^8 rows."""

    SPARE = 1 << 16

    def __init__(self, n: int, seed: int):
        rng = np.random.default_rng(seed)
        x, y = gdelt_points(n, rng)
        t = T0 + rng.integers(0, SPAN_MS, n)
        self._n = n
        self._x, self._y, self._t = (
            np.concatenate([c, np.zeros(self.SPARE, c.dtype)]) for c in (x, y, t)
        )

    x = property(lambda self: self._x[: self._n])
    y = property(lambda self: self._y[: self._n])
    t = property(lambda self: self._t[: self._n])

    def __len__(self) -> int:
        return self._n

    def upsert(self, ids, x, y, t) -> None:
        ids = np.asarray(ids, np.int64)
        new = ids >= self._n
        k = int(new.sum())
        check(
            np.array_equal(ids[new], self._n + np.arange(k))
            and self._n + k <= len(self._x),
            "appended ids must continue the id range, within the spare rows",
        )
        self._n += k
        self._x[ids], self._y[ids], self._t[ids] = x, y, t


def feature_batch(sft, ids, x, y, t):
    from geomesa_tpu.features import FeatureCollection

    return FeatureCollection.from_columns(
        sft, np.asarray(ids, np.int64), {"dtg": t, "geom": (x, y)}
    )


def random_rows(rng, n):
    """n fresh GDELT-shaped rows (x, y, t)."""
    x, y = gdelt_points(n, rng)
    return x, y, T0 + rng.integers(0, SPAN_MS, n)


# ------------------------------------------------------------- reference


def _iso(ms: int) -> str:
    return f"{np.datetime64(int(ms), 'ms')}Z"


class Query:
    """One ECQL filter with the plain description the reference reads:
    ``box`` (x0, y0, x1, y1), optional ``win`` (lo, hi ms, DURING = open
    interval) and optional polygon ``ring`` [(x, y), ...]."""

    def __init__(self, box, win=None, ring=None):
        self.box, self.win, self.ring = tuple(float(v) for v in box), win, ring
        if ring is not None:
            pts = ", ".join(f"{px!r} {py!r}" for px, py in ring + ring[:1])
            spatial = f"INTERSECTS(geom, POLYGON(({pts})))"
        else:
            spatial = "bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*self.box)
        self.ecql = spatial + (
            f" AND dtg DURING {_iso(win[0])}/{_iso(win[1])}" if win else ""
        )


def _in_ring(px, py, ring) -> np.ndarray:
    """Even-odd ray cast in f64 (the textbook crossing test)."""
    inside = np.zeros(len(px), bool)
    for (x0, y0), (x1, y1) in zip(ring, ring[1:] + ring[:1]):
        if y0 == y1:
            continue
        cross = (y0 > py) != (y1 > py)
        xi = x0 + (py - y0) * (x1 - x0) / (y1 - y0)
        inside ^= cross & (px < xi)
    return inside


def ref_ids(cols: Columns, q: Query) -> np.ndarray:
    """Exact answer: ascending ids of the rows the filter keeps (f64)."""
    x0, y0, x1, y1 = q.box
    m = cols.x >= x0
    m &= cols.x <= x1
    m &= cols.y >= y0
    m &= cols.y <= y1
    if q.win is not None:
        m &= cols.t > q.win[0]
        m &= cols.t < q.win[1]
    rows = np.nonzero(m)[0]
    if q.ring is not None:
        rows = rows[_in_ring(cols.x[rows], cols.y[rows], q.ring)]
    return rows


def ref_ids_many(cols: Columns, queries) -> list:
    with ThreadPoolExecutor(8) as pool:
        return list(pool.map(lambda q: ref_ids(cols, q), queries))


def ref_loose_rows(cols: Columns, q: Query):
    """The gather-free aggregations' documented semantics: columns
    rounded to f32, the box one f32 ulp wider on every side, time offsets
    in whole seconds (the open DURING bounds are second-aligned here, so
    [lo, hi) in seconds). Returns (f32 x, f32 y) of the kept rows."""
    f32 = np.float32
    x0, y0, x1, y1 = (f32(v) for v in q.box)
    lo = [np.nextafter(v, f32(-np.inf)) for v in (x0, y0)]
    hi = [np.nextafter(v, f32(np.inf)) for v in (x1, y1)]
    x32, y32 = cols.x.astype(f32), cols.y.astype(f32)
    m = (x32 >= lo[0]) & (x32 <= hi[0]) & (y32 >= lo[1]) & (y32 <= hi[1])
    if q.win is not None:
        check(q.win[0] % 1000 == 0 and q.win[1] % 1000 == 0, "second-aligned window")
        m &= (cols.t >= q.win[0]) & (cols.t < q.win[1])
    return x32[m], y32[m]


def ref_density_f32(x32, y32, env, width, height) -> np.ndarray:
    """The density kernel's arithmetic, op for op, in NumPy's IEEE f32
    (row 0 = the envelope's south edge)."""
    f32 = np.float32
    x0, y0, x1, y1 = (f32(v) for v in env)
    m = (x32 >= x0) & (x32 <= x1) & (y32 >= y0) & (y32 <= y1)
    x32, y32 = x32[m], y32[m]
    px = np.clip(((x32 - x0) / (x1 - x0) * f32(width)).astype(np.int32), 0, width - 1)
    py = np.clip(((y32 - y0) / (y1 - y0) * f32(height)).astype(np.int32), 0, height - 1)
    flat = py.astype(np.int64) * width + px
    return np.bincount(flat, minlength=width * height).reshape(height, width)


# what three f32 operations can move a pixel coordinate below 512: each
# rounds within half an ulp (the chip's divide within one), 2**-12 px is
# ten times that
PIXEL_EPS = 2.0 ** -12


def check_density(grid, x32, y32, env, width, height, what: str) -> dict:
    """The device grid against the rows it was made from. Which rows are
    in (f32 compares) and how many there are is exact. A row's pixel is
    exact too unless its coordinate, computed in f64 from the same f32
    inputs, lies within PIXEL_EPS of a pixel edge: only such a row may
    land on either side. So: sum(grid) == rows, and per pixel
    decided <= grid <= decided + edge rows that can reach it. Also
    reports how the grid compares with IEEE f32 arithmetic, op for op."""
    f32 = np.float32
    x0, y0, x1, y1 = (f32(v) for v in env)
    m = (x32 >= x0) & (x32 <= x1) & (y32 >= y0) & (y32 <= y1)
    fx = (x32[m].astype(np.float64) - x0) / (np.float64(x1) - x0) * width
    fy = (y32[m].astype(np.float64) - y0) / (np.float64(y1) - y0) * height

    def cell(f, size):
        return np.clip(np.floor(f).astype(np.int64), 0, size - 1)

    lo = cell(fy - PIXEL_EPS, height) * width + cell(fx - PIXEL_EPS, width)
    hi = cell(fy + PIXEL_EPS, height) * width + cell(fx + PIXEL_EPS, width)
    decided = lo == hi
    n = width * height
    lower = np.bincount(lo[decided], minlength=n)
    reach = lower.copy()
    e = ~decided
    for cy in (fy[e] - PIXEL_EPS, fy[e] + PIXEL_EPS):
        for cx in (fx[e] - PIXEL_EPS, fx[e] + PIXEL_EPS):
            reach += np.bincount(cell(cy, height) * width + cell(cx, width), minlength=n)
    g = np.asarray(grid).astype(np.int64).ravel()
    check(np.asarray(grid).shape == (height, width), f"{what}: grid shape")
    check(int(g.sum()) == int(m.sum()),
          f"{what}: grid sums to {int(g.sum())}, {int(m.sum())} rows are in the envelope")
    bad = int(((g < lower) | (g > reach)).sum())
    check(bad == 0, f"{what}: {bad} pixels outside what their rows allow")
    ieee = ref_density_f32(x32, y32, env, width, height).ravel()
    return {"rows": int(m.sum()), "edge_rows": int(e.sum()),
            "pixels_differing_from_ieee_f32": int((g != ieee).sum())}


def ref_tile_f64(cols: Columns, bbox, px: int) -> np.ndarray:
    """One map tile from scratch in f64: half-open pixels on the tile's
    own lattice (the world's closed east/north edge joins the last
    pixel), row 0 = north."""
    x0, y0, x1, y1 = bbox
    m = (cols.x >= x0) & (cols.x <= x1) & (cols.y >= y0) & (cols.y <= y1)
    x, y = cols.x[m], cols.y[m]
    col = np.floor((x - x0) / ((x1 - x0) / px)).astype(np.int64)
    row = np.floor((y - y0) / ((y1 - y0) / px)).astype(np.int64)
    if x1 >= 180.0:
        col = np.minimum(col, px - 1)
    if y1 >= 90.0:
        row = np.minimum(row, px - 1)
    keep = (col < px) & (row < px)
    flat = (px - 1 - row[keep]) * px + col[keep]
    return np.bincount(flat, minlength=px * px).reshape(px, px)


# ---------------------------------------------------------------- queries


def _ngon(cx, cy, rx, ry, k):
    return [
        (round(cx + rx * math.cos(2 * math.pi * i / k), 4),
         round(cy + ry * math.sin(2 * math.pi * i / k), 4))
        for i in range(k)
    ]


def _ring_box(ring):
    xs, ys = [p[0] for p in ring], [p[1] for p in ring]
    return min(xs), min(ys), max(xs), max(ys)


def make_queries(seed: int, n_each: int) -> dict:
    """The query mix, from the seed: boxes and windows (city
    to continent scale, 6 h to 2 weeks), and untimed polygons with 6
    edges (below geomesa.raster.min.edges: the device point-in-polygon
    tier) and with 24 edges (raster-approximated)."""
    rng = np.random.default_rng(seed + 1)
    # a fused group needs more than eight members that still have blocks
    # to scan after pruning: a few spare polygons keep that true
    n_poly = n_each + 6
    boxes = box_queries(rng, n_each + 2 * n_poly)
    # whole-second bounds: the device's time offsets are seconds
    wins = [(lo // 1000 * 1000, hi // 1000 * 1000)
            for lo, hi in time_windows(rng, n_each, T0, SPAN_MS)]
    small = [b for b in boxes if b[2] - b[0] <= 10.0] or boxes
    out = {
        "z3": [Query(b, w) for b, w in zip(boxes[:n_each], wins)],
        "z2": [Query(small[i % len(small)]) for i in range(n_each)],
    }
    for g, (name, k) in enumerate((("pip", 6), ("raster", 24))):
        qs = []
        for j in range(n_poly):
            x0, y0, x1, y1 = boxes[n_each + g * n_poly + j]
            ring = _ngon((x0 + x1) / 2, (y0 + y1) / 2,
                         min(x1 - x0, 12.0) / 2, min(y1 - y0, 6.0) / 2, k)
            qs.append(Query(_ring_box(ring), None, ring))
        out[name] = qs
    return out


def agg_queries(queries: dict, kept: dict) -> list:
    """The z3 and the z2 query with the most hits: the two the
    aggregation and fold phases repeat."""
    return [max(queries[k], key=lambda q: len(kept[q.ecql])) for k in ("z3", "z2")]


def result_ids(fc) -> np.ndarray:
    return np.sort(np.asarray(fc.ids).astype(np.int64))


def check_rows(got_fc, want_ids, what: str) -> int:
    got = result_ids(got_fc)
    check(np.array_equal(got, want_ids),
          f"{what}: {len(got)} ids returned, reference has {len(want_ids)}")
    return len(got)


class KernelCalls:
    """Counts dispatches into the device entry points, so a phase can
    assert the path it means to exercise is the one that ran. Observes
    only. A single-device table calls the block_* dispatchers once per
    dispatch; a mesh table looks its jit(shard_map) program up in the
    ``_dist_*`` factories once per dispatch (the block_* call inside is
    traced once), so those are what is counted there."""

    SINGLE = {
        "block_scan": ("bk", "block_scan"), "block_scan_multi": ("bk", "block_scan_multi"),
        "block_pops": ("agg", "block_pops"), "block_density": ("agg", "block_density"),
        "block_bounds": ("agg", "block_bounds"),
    }
    MESH = {
        "block_scan": ("dt", "_dist_scan"), "block_scan_multi": ("dt", "_dist_scan_multi"),
        "block_pops": ("dt", "_dist_pops"), "block_density": ("dt", "_dist_density"),
        "block_bounds": ("dt", "_dist_bounds"),
    }

    def __init__(self, mesh: bool = False):
        from geomesa_tpu.parallel import dtable
        from geomesa_tpu.scan import aggregations, block_kernels

        mods = {"bk": block_kernels, "agg": aggregations, "dt": dtable}
        self.calls: list = []
        self._undo = []
        for name, (mod, attr) in (self.MESH if mesh else self.SINGLE).items():
            fn = getattr(mods[mod], attr)
            self._undo.append((mods[mod], attr, fn))
            setattr(mods[mod], attr, self._wrap(name, fn, mesh))

    def _wrap(self, name, fn, mesh):
        def counted(*a, **kw):
            if mesh and "scan" in name:  # (mesh, names, boxes, windows, extent, E, R)
                e, r = (tuple(a) + (0, 0))[5:7]
            else:
                e, r = kw.get("n_edges", 0), kw.get("n_rints", 0)
            self.calls.append((name, e, r))
            return fn(*a, **kw)

        return counted

    def close(self) -> None:
        for mod, attr, fn in self._undo:
            setattr(mod, attr, fn)

    def count(self, name, edges=None, rints=None, since=0) -> int:
        return sum(
            1 for n, e, r in self.calls[since:]
            if n == name and (edges is None or bool(e) == edges)
            and (rints is None or bool(r) == rints)
        )


class CompileEvents:
    """JAX's own compile accounting (jax.monitoring): compile requests,
    persistent-cache hits, and seconds inside the backend compiler."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _evt(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits, self.seconds


# ------------------------------------------------------------------ phases


def build_store(cols: Columns, mesh=None, tile=None):
    """DataStore -> create_schema (z3 + z2) -> write -> device tables."""
    import jax

    from geomesa_tpu import native
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.sft import FeatureType

    check(native._load() is not None,
          "the native host tier did not build/load (g++ output is logged above)")
    sft = FeatureType.from_spec(TYPE, SPEC)
    sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    ds = DataStore(mesh=mesh, tile=tile)
    ds.create_schema(sft)
    n = len(cols)
    t_in = time.perf_counter()
    ds.write(TYPE, feature_batch(sft, np.arange(n), cols.x.copy(), cols.y.copy(), cols.t),
             check_ids=False)
    tables = {name: ds.table(TYPE, name) for name in ("z3", "z2")}
    for tb in tables.values():
        jax.block_until_ready(list(tb.cols3.values()))
    ingest_s = time.perf_counter() - t_in
    check(all(tb.n == n for tb in tables.values()), "every index holds every row")
    stats = jax.devices()[0].memory_stats() or {}
    cache_dir = jax.config.jax_compilation_cache_dir
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    check(cache_dir and (not env_dir or cache_dir == env_dir),
          f"compile cache not placed before the first compile: {cache_dir!r}")
    emit(
        "load", rows=n, ingest_s=round(ingest_s, 2),
        rows_per_s=round(n / ingest_s),
        nbytes_device={k: tb.nbytes_device for k, tb in tables.items()},
        n_blocks={k: tb.n_blocks for k, tb in tables.items()},
        native_loaded=True,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"),
        compile_cache_dir=cache_dir, host_memory_gb=host_memory_gb(),
    )
    return ds


def probe_machine(ds, events: CompileEvents) -> None:
    """What the machine is: the host<->device link, and what the kernel
    ladder costs to compile cold and again warm. Printed, never tuned to."""

    def pull_ms(shape):
        """Median ms of (device_get of a fresh result, dispatch + pull)."""
        pulls, trips, nbytes = link_readings(shape, 20)
        return float(np.median(pulls)) * 1e3, float(np.median(trips)) * 1e3, nbytes

    small_ms, small_trip, small_b = pull_ms((8, 128))
    big_ms, big_trip, big_b = pull_ms((1024, 1024))
    emit(
        "link", device_get_4KiB_ms=small_ms, device_get_4MiB_ms=big_ms,
        dispatch_and_pull_4KiB_ms=small_trip, dispatch_and_pull_4MiB_ms=big_trip,
        pull_MB_s=(big_b - small_b) / 1e6 / max(big_ms - small_ms, 1e-6) * 1e3,
        readings=20,
    )
    for label in ("warmup_cold", "warmup_again"):
        r0, h0, s0 = events.snapshot()
        t = time.perf_counter()
        calls = ds.warmup(TYPE)
        wall = time.perf_counter() - t
        r1, h1, s1 = events.snapshot()
        emit(
            label, kernel_calls=calls, wall_s=round(wall, 2),
            compile_requests=r1 - r0, persistent_cache_hits=h1 - h0,
            compiled=r1 - r0 - (h1 - h0), backend_compile_s=round(s1 - s0, 2),
        )
    check(r1 - r0 == 0, f"second warmup pass recompiled {r1 - r0} programs")


def embedded_queries(ds, cols: Columns, queries: dict, calls: KernelCalls) -> dict:
    """Each answer against the NumPy pass; returns {ecql: ids} of a few
    queries for the served phase to repeat."""
    kept = {}
    for kind in ("z3", "z2", "pip", "raster"):
        qs = queries[kind]
        want = ref_ids_many(cols, qs)
        hits, ms = [], []
        mark = len(calls.calls)
        for q, w in zip(qs, want):
            plan = ds.planner.plan(TYPE, q.ecql)
            check(plan.index == ("z3" if kind == "z3" else "z2"), f"{kind}: index {plan.index}")
            if kind == "pip":
                check(plan.config.poly is not None and plan.config.rast is None,
                      "6-edge polygon must take the device point-in-polygon tier")
            if kind == "raster":
                check(plan.config.rast is not None, "24-edge polygon must rasterize")
            t = time.perf_counter()
            out = ds.query(TYPE, q.ecql)
            ms.append((time.perf_counter() - t) * 1e3)
            hits.append(check_rows(out, w, f"{kind} query {q.ecql}"))
            kept[q.ecql] = w
        # a query whose z-ranges lie wholly inside its box needs no
        # kernel, so the device scans are counted over the kind
        scans = calls.count(
            "block_scan", since=mark,
            edges=True if kind == "pip" else None,
            rints=True if kind == "raster" else None,
        )
        check(scans >= 1, f"{kind}: no device scan of that tier ran")
        emit(f"query_{kind}", queries=len(qs), hits=hits, wall_ms=ms,
             device_scans=scans, exact=True)

    # aggregations: exact count, then the gather-free device forms
    for q in agg_queries(queries, kept):
        want = kept[q.ecql]
        check(ds.count(TYPE, q.ecql) == len(want), f"count {q.ecql}")
        x32, y32 = ref_loose_rows(cols, q)
        mark = len(calls.calls)
        (cnt,) = ds.stats_query(TYPE, "Count()", q.ecql, estimate=True)
        check(calls.count("block_pops", since=mark) >= 1, "Count() estimate: no pops kernel ran")
        check(int(cnt.count) == len(x32),
              f"device Count() {cnt.count} != f32 reference {len(x32)}")
        mark = len(calls.calls)
        got_b = ds.bounds(TYPE, q.ecql)
        check(calls.count("block_bounds", since=mark) >= 1, "bounds: no bounds kernel ran")
        want_b = (float(x32.min()), float(y32.min()), float(x32.max()), float(y32.max()))
        check(tuple(got_b) == want_b, f"bounds {got_b} != {want_b}")
        mark = len(calls.calls)
        t = time.perf_counter()
        grid = ds.density(TYPE, q.ecql, envelope=q.box, width=GRID, height=GRID)
        dens_ms = (time.perf_counter() - t) * 1e3
        check(calls.count("block_density", since=mark) >= 1, "density: no density kernel ran")
        dens = check_density(grid, x32, y32, q.box, GRID, GRID, f"density {q.ecql}")
        emit("aggregate", ecql=q.ecql, count=len(want), loose_count=len(x32),
             bounds=want_b, density=dens, density_ms=dens_ms, exact=True)
    return kept


def fused_batch(ds, queries: dict, kept: dict, calls: KernelCalls) -> None:
    """One query_many batch of box, 6-edge and 24-edge polygon members
    (more than eight of each, so no group is routed to the single-query
    kernel): the fused multi-query kernel runs its polygon legs compiled."""
    batch = queries["z3"] + queries["pip"] + queries["raster"]
    want = [kept[q.ecql] for q in batch]
    mark = len(calls.calls)
    t = time.perf_counter()
    outs = ds.query_many(TYPE, [q.ecql for q in batch])
    wall = time.perf_counter() - t
    for q, out, w in zip(batch, outs, want):
        check_rows(out, w, f"query_many member {q.ecql}")
    fused = {
        "box": calls.count("block_scan_multi", edges=False, rints=False, since=mark),
        "pip": calls.count("block_scan_multi", edges=True, since=mark),
        "raster": calls.count("block_scan_multi", rints=True, since=mark),
    }
    check(fused["pip"] >= 1 and fused["raster"] >= 1,
          f"the fused kernel's polygon legs did not run: {fused}")
    emit("query_many", members=len(batch), hits=int(sum(len(w) for w in want)),
         wall_ms=wall * 1e3, fused_dispatches=fused, exact=True)


def knn(ds, cols: Columns, seed: int) -> None:
    from geomesa_tpu.process.knn import knn_search

    k = 10
    i = int(np.random.default_rng(seed + 2).integers(len(cols)))
    px, py = float(cols.x[i]) + 0.01, float(np.clip(cols.y[i], -80, 80)) + 0.01
    t = time.perf_counter()
    out = knn_search(ds, TYPE, px, py, k)
    wall = time.perf_counter() - t
    # reference: haversine (R = 6,371,000 m) over a window that must hold
    # the k nearest, nearest first
    near = np.nonzero((np.abs(cols.x - px) < 5.0) & (np.abs(cols.y - py) < 2.0))[0]
    lon1, lat1, lon2, lat2 = (np.radians(v) for v in (cols.x[near], cols.y[near], px, py))
    a = (np.sin((lat2 - lat1) / 2) ** 2
         + np.cos(lat1) * np.cos(lat2) * np.sin((lon2 - lon1) / 2) ** 2)
    d = 2 * 6_371_000.0 * np.arcsin(np.minimum(1.0, np.sqrt(a)))
    order = np.argsort(d, kind="stable")[:k]
    check(d[order[-1]] < 100_000.0, "reference window too small for k neighbours")
    got = np.asarray(out.ids).astype(np.int64)
    check(np.array_equal(got, near[order]), f"kNN ids {got} != {near[order]}")
    emit("knn", k=k, wall_ms=wall * 1e3, farthest_m=float(d[order[-1]]), exact=True)


def served(ds, cols: Columns, kept: dict, seed: int) -> None:
    """The data plane on a real socket, WAL-backed: /query in GeoJSON and
    Arrow, one acknowledged /ingest read back, one /tiles leaf tile."""
    from geomesa_tpu.io.arrow import read_arrow_table
    from geomesa_tpu.serving.http import DataClient
    from geomesa_tpu.streaming import LambdaStore, WalConfig

    tmp = tempfile.mkdtemp(prefix="chip_smoke_wal_")
    lam = LambdaStore(ds, TYPE, wal_dir=os.path.join(tmp, "_wal"),
                      wal_config=WalConfig(sync="always"))
    try:
        srv = lam.serve(port=0)
        client = DataClient(srv.url, timeout=300.0)
        probes = sorted(kept, key=lambda e: len(kept[e]))
        probes = [e for e in probes if 0 < len(kept[e]) <= 50_000][:3]
        check(probes, "no probe query small enough to serve as GeoJSON")
        ms = []
        for ecql in probes:
            t = time.perf_counter()
            gj = client.query(TYPE, cql=ecql)  # non-2xx raises ServeError
            ms.append((time.perf_counter() - t) * 1e3)
            got = np.sort(np.array([int(f["id"]) for f in gj["features"]], np.int64))
            check(np.array_equal(got, kept[ecql]), f"/query geojson {ecql}")
            tbl = read_arrow_table(client.query(TYPE, cql=ecql, fmt="arrow"))
            got = np.sort(np.asarray(tbl.column("id").to_pylist()).astype(np.int64))
            check(np.array_equal(got, kept[ecql]), f"/query arrow {ecql}")
        emit("serve_query", requests=2 * len(probes), status=200,
             hits=[len(kept[e]) for e in probes], geojson_ms=ms, exact=True)

        # an acknowledged write is durable (WAL fsynced before the ack)
        # and is read back by the next query
        rng = np.random.default_rng(seed + 3)
        k = 64
        bx, by, bt = random_rows(rng, k)
        bt = bt // 1000 * 1000
        ids = len(cols) + np.arange(k)
        feats = [
            {"type": "Feature", "id": str(int(i)),
             "geometry": {"type": "Point", "coordinates": [float(x), float(y)]},
             "properties": {"dtg": int(t)}}
            for i, x, y, t in zip(ids, bx, by, bt)
        ]
        ack = client.ingest(TYPE, {"type": "FeatureCollection", "features": feats})
        check(ack["acked"] == k and ack["durable"] is True, f"/ingest ack {ack}")
        cols.upsert(ids, bx, by, bt)
        j = int(np.argmin(np.abs(bx)))
        q = Query((bx[j] - 0.5, by[j] - 0.5, bx[j] + 0.5, by[j] + 0.5))
        want = ref_ids(cols, q)
        check(ids[j] in want, "reference lost the ingested row")
        gj = client.query(TYPE, cql=q.ecql)
        got = np.sort(np.array([int(f["id"]) for f in gj["features"]], np.int64))
        check(np.array_equal(got, want), "/ingest read-back through /query")
        # hot -> cold: the flush is the fold_upsert path; the store then
        # answers the same query from the device table alone
        flushed = lam.flush()
        check(flushed == k, f"flush moved {flushed} of {k} rows")
        check_rows(ds.query(TYPE, q.ecql), want, "read-back after the flush")
        emit("serve_ingest", acked=k, durable=True, read_back=len(want),
             flushed=flushed, exact=True)

        # one leaf tile, raw counts: pyramid == NumPy f64 from scratch.
        # ds.density over the tile's envelope is the device's f32 grid
        # (check_density); it differs from the tile only by the rows f32
        # moves across a pixel edge.
        lat = srv.tiles.lattice
        z = lat.leaf_zoom
        cx, cy = lat.n_tiles(z)
        tx, ty = cx // 2, cy // 2 - 1
        t = time.perf_counter()
        status, _, body = client.tile(TYPE, "density", z, tx, ty, fmt="arrow")
        tile_ms = (time.perf_counter() - t) * 1e3
        check(status == 200, f"/tiles status {status}")
        tbl = read_arrow_table(body)
        tile = np.asarray(tbl.column(0).to_pylist(), np.float64).reshape(lat.px, lat.px)
        bbox = lat.tile_bbox(z, tx, ty)
        want_tile = ref_tile_f64(cols, bbox, lat.px)
        check(np.array_equal(tile, want_tile),
              f"/tiles grid differs from the f64 reference in "
              f"{int((tile != want_tile).sum())} pixels")
        status, _, png = client.tile(TYPE, "density", z, tx, ty)
        check(status == 200 and png[:8] == b"\x89PNG\r\n\x1a\n", "/tiles png")
        qt = Query(bbox)
        grid = ds.density(TYPE, qt.ecql, envelope=bbox, width=lat.px, height=lat.px)
        dens = check_density(grid, *ref_loose_rows(cols, qt), bbox, lat.px, lat.px,
                             "density over the tile's envelope")
        moved = int(np.abs(grid[::-1].astype(np.float64) - tile).sum())
        check(moved <= 2 * max(1, int(tile.sum()) // 1000),
              f"tile vs device density: {moved} pixel-count differences")
        emit("serve_tile", tile=[z, tx, ty], status=200, rows=int(tile.sum()),
             tile_ms=tile_ms, density=dens, tile_vs_density_count_diffs=moved,
             exact=True)
    finally:
        lam.close()
        if ds.server is not None:
            ds.server.close()
        if ds.scheduler is not None:
            ds.scheduler.close()
        shutil.rmtree(tmp, ignore_errors=True)


def fold(ds, cols: Columns, targets: list, seed: int) -> None:
    """fold_upsert of replaced-and-new rows: on a TPU the device fold plan
    is the default, and only the batch's rows may cross the link."""
    import jax

    from geomesa_tpu.storage.table import _device_fold_enabled

    sft = ds.get_schema(TYPE)
    rng = np.random.default_rng(seed + 4)
    n_rep, n_new = 3000, 1000
    ids = np.concatenate([
        rng.choice(len(cols), n_rep, replace=False),
        len(cols) + np.arange(n_new),
    ])
    x, y, t = random_rows(rng, n_rep + n_new)
    t = t // 1000 * 1000
    # land some of the batch inside the queries re-run below
    for k, q in enumerate(targets):
        x0, y0, x1, y1 = q.box
        sl = slice(k * 200, (k + 1) * 200)
        x[sl] = rng.uniform(x0, x1, 200)
        y[sl] = rng.uniform(y0, y1, 200)
        if q.win is not None:
            t[sl] = rng.integers(q.win[0] // 1000 + 1, q.win[1] // 1000, 200) * 1000
    t0 = time.perf_counter()
    ds.fold_upsert(TYPE, feature_batch(sft, ids, x, y, t))
    new = {name: ds.table(TYPE, name) for name in ("z3", "z2")}
    for tb in new.values():
        jax.block_until_ready(list(tb.cols3.values()))
    wall = time.perf_counter() - t0
    cols.upsert(ids, x, y, t)
    uploaded = {name: int(tb.rows_uploaded) for name, tb in new.items()}
    check(_device_fold_enabled() or jax.default_backend() != "tpu",
          "on a TPU the device fold plan is the default")
    if _device_fold_enabled():
        check(all(v == len(ids) for v in uploaded.values()),
              f"the device fold plan did not run: rows_uploaded {uploaded}, batch {len(ids)}")
    check(all(tb.n == len(cols) for tb in new.values()),
          "fold did not publish tables of the new size")
    for q in targets:
        check_rows(ds.query(TYPE, q.ecql), ref_ids(cols, q), f"after fold: {q.ecql}")
    emit("fold", batch=len(ids), replaced=n_rep, appended=n_new,
         device_plan=_device_fold_enabled(), rows_uploaded=uploaded,
         wall_s=round(wall, 3), rows=len(cols),
         host_memory_gb=host_memory_gb(),
         hbm_peak_bytes=(jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
         exact=True)


def split_ingest(ds, seed: int) -> None:
    """A delimited file through the converter path with two worker
    processes, started from the process that holds the chip."""
    from geomesa_tpu.ingest.pipeline import ingest_files
    from geomesa_tpu.io.converters import Converter, FieldSpec
    from geomesa_tpu.sft import FeatureType

    n = 40_000
    rng = np.random.default_rng(seed + 5)
    x, y, t = random_rows(rng, n)
    x, y = np.round(x, 5), np.round(y, 5)
    t = t // 1000 * 1000
    sft = FeatureType.from_spec("gdelt_csv", SPEC)
    ds.create_schema(sft)
    conv = Converter(
        sft=sft, fmt="delimited", skip_lines=1, id_field="$1",
        fields=[FieldSpec("geom", "point($2, $3)"), FieldSpec("dtg", "datetime($4)")],
    )
    tmp = tempfile.mkdtemp(prefix="chip_smoke_csv_")
    try:
        path = os.path.join(tmp, "events.csv")
        with open(path, "w") as fh:
            fh.write("id,lon,lat,when\n")
            fh.writelines(
                f"{i},{float(x[i])!r},{float(y[i])!r},{_iso(t[i])}\n" for i in range(n)
            )
        t0 = time.perf_counter()
        res = ingest_files(ds, conv, [path], workers=2, split_bytes=256 << 10)
        wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    check(res.written == n and res.errors == 0 and res.splits > 2,
          f"split ingest wrote {res.written}/{n}, errors {res.errors}, splits {res.splits}")
    box = (-30.0, -20.0, 30.0, 20.0)
    q = Query(box)
    m = (x >= box[0]) & (x <= box[2]) & (y >= box[1]) & (y <= box[3])
    got = result_ids(ds.query("gdelt_csv", q.ecql))
    check(np.array_equal(got, np.nonzero(m)[0]), "split ingest read-back")
    emit("split_ingest", rows=n, workers=2, splits=res.splits, wall_s=round(wall, 2),
         read_back=int(m.sum()), exact=True)


def mesh_phase(cols: Columns, queries: dict, n_chips: int, tile=None) -> None:
    """The store over a mesh of ``n_chips`` devices: placement, then scan,
    fused query_many with polygon members, count, density (the psum
    merge) and bounds against the same NumPy reference."""
    from geomesa_tpu.parallel import make_mesh
    from geomesa_tpu.parallel.dtable import DistributedIndexTable

    mesh = make_mesh(n_chips)
    ds = build_store(cols, mesh=mesh, tile=tile)
    placement = {}
    for name in ("z3", "z2"):
        tb = ds.table(TYPE, name)
        check(isinstance(tb, DistributedIndexTable), f"{name} is not a mesh table")
        for col, arr in tb.cols3.items():
            shards = arr.addressable_shards
            devs = {s.device for s in shards}
            check(len(shards) == n_chips and devs == set(mesh.devices.flat),
                  f"{name}.{col}: {len(devs)} devices hold its {len(shards)} shards")
            check(all(s.data.shape[1] == tb.n_blocks // n_chips and s.data.shape[0] == 1
                      for s in shards),
                  f"{name}.{col}: shard shapes {[s.data.shape for s in shards]}")
        placement[name] = {
            "devices": sorted(str(d) for d in mesh.devices.flat),
            "blocks_per_device": tb.n_blocks // n_chips,
            "columns": sorted(tb.cols3),
        }
    emit("mesh_placement", chips=n_chips, **placement)
    calls = KernelCalls(mesh=True)
    try:
        kept = embedded_queries(ds, cols, queries, calls)
        fused_batch(ds, queries, kept, calls)
    finally:
        calls.close()


# -------------------------------------------------------------------- main


def run(n: int, seed: int, chips: int, n_queries: int = 9, tile=None) -> None:
    """Every phase of one mode, in order; any failure raises."""
    t = time.perf_counter()
    cols = Columns(n, seed)
    queries = make_queries(seed, n_queries)
    emit("generate", rows=n, seed=seed, seconds=round(time.perf_counter() - t, 2))
    if chips > 1:
        mesh_phase(cols, queries, chips, tile=tile)
        return
    events = CompileEvents()
    ds = build_store(cols, tile=tile)
    probe_machine(ds, events)
    calls = KernelCalls()
    try:
        kept = embedded_queries(ds, cols, queries, calls)
        fused_batch(ds, queries, kept, calls)
    finally:
        calls.close()
    knn(ds, cols, seed)
    served(ds, cols, kept, seed)
    fold(ds, cols, agg_queries(queries, kept), seed)
    split_ingest(ds, seed)
    r, h, s = events.snapshot()
    stats = ds.table(TYPE, "z3").cols3["x"].devices().pop().memory_stats() or {}
    emit("totals", host_memory_gb=host_memory_gb(),
         compile_requests=r, persistent_cache_hits=h,
         backend_compile_s=round(s, 2),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         bytes_in_use=stats.get("bytes_in_use"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rows", type=int, default=N_DEFAULT)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {d0.platform!r}); refusing to run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports {len(devices)}",
              file=sys.stderr)
        return 2
    if args.rows != N_ASKED:
        emit("cut", rows=args.rows, asked=N_ASKED, real=args.rows >= N_MIN,
             why="fold_upsert at 1e8 rows exceeds a one-chip machine: 40 GiB host, 16 GB HBM"
             if args.rows == N_DEFAULT else "--rows")
    t = time.perf_counter()
    run(args.rows, args.seed, args.chips)
    emit("done", seconds=round(time.perf_counter() - t, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
