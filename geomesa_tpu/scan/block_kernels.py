"""Candidate-block scan kernels: one device call per query, bitmask out.

This is the round-3 redesign of the scan hot path, driven by the link
characteristics of the installation it was designed on, a remote chip
behind a slow link (design history; what a local v5e measures is in
PERF.md section 5):

- explicit ``device_put``/``jnp.asarray`` costs ~66 ms per call, but numpy
  arrays passed *as jit arguments* transfer in ~0.05 ms -> all query
  parameters ride the dispatch;
- every device->host pull pays a ~66 ms floor at ~30 MB/s, but one batched
  ``jax.device_get`` of several outputs pays the floor once -> one pull per
  query, sized in KB;
- HBM streams at ~460 GB/s but gathers/scatters (``jnp.nonzero``, fancy
  indexing) run ~1000x slower -> no gathers, no nonzero: the kernel DMAs
  whole candidate blocks picked by a scalar-prefetched id list and writes
  *packed bitmasks*, decoded on host with ``np.unpackbits``.

Layout: device columns are [n_blocks, SUB, 128] (BLOCK = SUB*128 rows,
row-major: local row = sublane*128 + lane). The host prunes the sorted
table to candidate blocks via searchsorted z-ranges (the tablet-server
seek analogue; reference scans ranges via
geomesa-index-api/.../index/utils/...ScanPlan with per-range seeks), pads
the block-id list to a static bucket M, and gets back two bit planes:

- ``wide``: f32/i32 predicate over widened bounds — superset of true hits
  (reference Z3Filter.inBounds semantics, index/filters/Z3Filter.scala:19-65);
- ``inner``: predicate over shrunk bounds — rows certain to be true hits
  at f64 precision, so host refinement touches only ``wide & ~inner`` rows
  (the automatic useFullFilter tier, Z3IndexKeySpace.scala:240-254).

Every shape is static per (table, M-bucket): zero recompiles at query time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
BLOCK = 16384  # default rows per scan block (4096 minimum: SUB % 32 == 0)
# candidate-block list sizes (static). The ladder is geometric with ratio
# 2 (round 4; rounds 2-3 used (32, 256, 1024, 4096)): plane pull bytes
# scale with the padded M, and at the measured ~30 MB/s pull bandwidth
# (PERF.md §1) the 8x jump from 32 to 256 made mid-size queries pull up
# to 8x the bytes their candidates needed. Each extra bucket costs one
# warmup compile per (table, col-set, flags) variant — untimed, amortized.
M_BUCKETS = (32, 64, 128, 256, 512, 1024, 2048, 4096)

# polygon-edge bucket ladder for the device point-in-polygon tier (round
# 5): query polygons pad their edge list to a static E; polygons past the
# largest bucket fall back to the host refinement path. The Pallas kernel
# unrolls edges, so big buckets ride the XLA variant (see block_scan).
E_BUCKETS = (16, 32, 64, 128, 256)
PALLAS_MAX_EDGES = 64  # above this the unrolled kernel gets too large

# fused-chunk edge ladder (round 6): a fused multi-query chunk carries ONE
# static [Q, E, 128] edge stack sized to its largest member polygon, so
# the compile key stays (columns, flags, E bucket) — a deliberately
# SMALLER ladder than E_BUCKETS (each entry is one more warmup compile
# per flag combo). Chunks with no polygon member use E = 0, the exact
# pre-PIP variant. pack_edges caps polygons at E_BUCKETS[-1], which is
# also FUSED_E_BUCKETS[-1]: every packed polygon fits a fused bucket.
FUSED_E_BUCKETS = (16, 64, 256)

# raster-interval ladder (round 7, arXiv 2307.01716): a polygon query may
# additionally carry a packed [1 + R, 128] interval stack
# (filter.raster.RasterApprox.pack_block) — sorted integer intervals of
# fully-inside / boundary cells over a Z2-aligned grid. The kernel
# classifies each candidate row by integer interval lookup (~5 vector ops
# per interval vs ~10 per PIP edge) and runs exact even-odd PIP only on
# the boundary residue — on device when the config also ships edges
# (masks bit-identical to the pre-raster path), else via host refinement
# of the uncertain rows. R = 0 is the no-raster variant. The stack is
# deliberately COARSE (geomesa.raster.kernel.intervals, default 16):
# the raster-derived z-ranges already prune out-cell rows host-side at
# full resolution, so the kernel intervals only classify rows within
# straddling blocks.
R_BUCKETS = (16, 32, 64, 256)
FUSED_R_BUCKETS = (16, 32, 64, 256)
PALLAS_MAX_RINTS = 64  # unrolled interval checks; larger R rides XLA


def fused_e_bucket(n: int) -> int:
    """Static fused-chunk edge bucket: the smallest FUSED_E_BUCKETS entry
    >= n, or 0 for a chunk with no polygon member."""
    if n <= 0:
        return 0
    return next(b for b in FUSED_E_BUCKETS if n <= b)


def fused_r_bucket(n: int) -> int:
    """Static fused-chunk raster-interval bucket: the smallest
    FUSED_R_BUCKETS entry >= n, or 0 for a chunk with no raster member."""
    if n <= 0:
        return 0
    return next(b for b in FUSED_R_BUCKETS if n <= b)


def r_bucket_of(n: int) -> int:
    """Static single-query interval bucket (R_BUCKETS ladder); run counts
    past the largest bucket coalesce into it (pack_block's safe grouping),
    so every raster fits a static shape."""
    if n <= 0:
        return 0
    return next((b for b in R_BUCKETS if n <= b), R_BUCKETS[-1])


def n_rints_of(rast: "np.ndarray | None") -> int:
    """Static interval-bucket size of a pack_block stack (row 0 is the
    grid header; 0 = no raster)."""
    return 0 if rast is None else rast.shape[0] - 1

# column-set signatures -> ordered device column names
POINT_COLS = ("x", "y")
POINT_TIME_COLS = ("x", "y", "tbin", "toff")
EXTENT_COLS = ("gxmin", "gymin", "gxmax", "gymax")
EXTENT_TIME_COLS = EXTENT_COLS + ("tbin", "toff")

# packed-time device column (round 5; the 1B-row single-chip layout): one
# i32 "tw" = bin << TW_BITS | (offset >> period shift) replaces the
# (tbin, toff) pair — 12 B/row instead of 16 B, so 1e9 rows fit a v5e's
# 16 GB HBM. TW_BITS is FIXED so kernels need no extra static parameter;
# the per-period tick shift lives host-side (index.z3.PACKED_SHIFT).
# Windows convert ms->ticks conservatively (floor for wide, shrink for
# inner), so tick-boundary rows refine on host exactly like f32 box edges.
TW_BITS = 16
TW_MASK = (1 << TW_BITS) - 1


def use_pallas() -> bool:
    """Pallas path: real TPU, or interpret mode when the
    geomesa.tpu.pallas property (env GEOMESA_TPU_PALLAS) is '1';
    '0' forces the XLA fallback."""
    from geomesa_tpu.conf import PALLAS_MODE

    mode = PALLAS_MODE.get()
    if mode == "0":
        return False
    return jax.default_backend() == "tpu" or mode == "1"


# --------------------------------------------------------------- params


def pack_boxes(wide: np.ndarray | None, inner: np.ndarray | None) -> np.ndarray:
    """[8, 128] f32 param block: lanes 0-3 wide box, 4-7 inner box.

    Pad slots can never match: wide xmin=+inf/xmax=-inf. Overflow past the
    8 kernel slots takes the safe direction per plane: wide boxes collapse
    into their bounding union (superset -> refined), inner boxes drop the
    smallest (subset -> rows just lose the certainty shortcut).
    """
    p = np.zeros((8, LANES), np.float32)
    p[:, 0] = np.inf
    p[:, 2] = -np.inf
    p[:, 4] = np.inf
    p[:, 6] = -np.inf
    if wide is not None and len(wide):
        w = np.asarray(wide, np.float32)
        if len(w) > 8:
            union = np.array(
                [[w[7:, 0].min(), w[7:, 1].min(), w[7:, 2].max(), w[7:, 3].max()]],
                np.float32,
            )
            w = np.concatenate([w[:7], union])
        p[: len(w), 0:4] = w
    if inner is not None and len(inner):
        i = np.asarray(inner, np.float32)
        if len(i) > 8:
            areas = np.maximum(i[:, 2] - i[:, 0], 0) * np.maximum(i[:, 3] - i[:, 1], 0)
            i = i[np.argsort(-areas)[:8]]
        p[: len(i), 4:8] = i
    return p


def pack_windows(wide: np.ndarray | None, inner: np.ndarray | None) -> np.ndarray:
    """[8, 128] i32 param block: lanes 0-3 wide slot, 4-7 inner slot.

    A slot is (bin_lo, bin_hi, off_lo, off_hi), all inclusive: the merged
    form of the reference's per-bin windows (timesByBin) — one interval
    covering bins [b0, b1] costs at most 3 slots (partial first bin,
    full-interior run, partial last bin). Pad slots have bin_lo=1 > bin_hi=0.
    """
    p = np.zeros((8, LANES), np.int32)
    p[:, 0] = 1
    p[:, 1] = 0
    p[:, 4] = 1
    p[:, 5] = 0
    if wide is not None and len(wide):
        p[: len(wide), 0:4] = wide
    if inner is not None and len(inner):
        p[: len(inner), 4:8] = inner
    return p


def merge_window_slots(
    windows: np.ndarray | None, overflow: str = "widen"
) -> np.ndarray | None:
    """Per-bin [W, 3] (bin, off_lo, off_hi) windows -> merged [k, 4] slots
    (bin_lo, bin_hi, off_lo, off_hi), consecutive bins with identical
    offset ranges collapsed into one slot.

    If k would exceed the 8 kernel slots, ``overflow`` picks the safe
    direction for the plane being built:
    - "widen" (wide plane): union adjacent slots — a *superset*, corrected
      by refinement;
    - "drop" (inner plane): discard the smallest slots — a *subset*, so no
      row is ever wrongly marked certain; dropped rows just get refined.
    """
    if windows is None or len(windows) == 0:
        return None
    w = np.asarray(windows)
    order = np.lexsort((w[:, 1], w[:, 0]))
    w = w[order]
    slots: list[list[int]] = []
    for b, lo, hi in w.tolist():
        if slots and slots[-1][1] == b - 1 and slots[-1][2] == lo and slots[-1][3] == hi:
            slots[-1][1] = b
        else:
            slots.append([b, b, lo, hi])
    if len(slots) > 8 and overflow == "drop":
        slots.sort(key=lambda s: (s[1] - s[0]) * (s[3] - s[2] + 1), reverse=True)
        slots = sorted(slots[:8])
    while len(slots) > 8:
        # widen: merge the two adjacent slots with the smallest bin gap
        gaps = [slots[i + 1][0] - slots[i][1] for i in range(len(slots) - 1)]
        i = int(np.argmin(gaps))
        a, b = slots[i], slots[i + 1]
        slots[i : i + 2] = [[a[0], b[1], min(a[2], b[2]), max(a[3], b[3])]]
    return np.array(slots, dtype=np.int32)


def pack_edges(geom) -> "np.ndarray | None":
    """Pad a Polygon/MultiPolygon's edges into the PIP kernel's static
    [E, 128] f32 param block, or None when the geometry exceeds the
    largest bucket. Lanes per edge k:

    0: y0   1: y1   2: x0   3: inverse slope (dx/dy; 0 for horizontals)
    4: eps_x (crossing-abscissa uncertainty, scaled by |islope|)
    5: eps_y (vertex-latitude uncertainty; 0 on pad rows)

    Even-odd parity over ALL rings (shells + holes, every part) is the
    point-in-polygon test; rows within the eps bands are *near* — their
    f32 parity may differ from f64 truth, so the kernel reports them
    uncertain and the host refines them exactly. Pad rows (zeros) never
    cross and are never near.
    """
    from geomesa_tpu import geometry as geo

    rings = []
    if isinstance(geom, geo.Polygon):
        rings = [geom.shell] + list(geom.holes)
    elif isinstance(geom, geo.MultiPolygon):
        for p in geom.parts:
            rings.extend([p.shell] + list(p.holes))
    else:
        return None
    segs = []
    for r in rings:
        c = np.asarray(r, np.float64)
        if len(c) < 2:
            continue
        if c[0, 0] != c[-1, 0] or c[0, 1] != c[-1, 1]:
            c = np.vstack([c, c[:1]])  # close the ring
        segs.append(np.stack([c[:-1, 0], c[:-1, 1], c[1:, 0], c[1:, 1]], axis=1))
    if not segs:
        return None
    return pack_edge_segments(np.concatenate(segs))


def pack_edge_segments(e: np.ndarray) -> "np.ndarray | None":
    """:func:`pack_edges` from raw segments: ``e`` is [n, 4] =
    (x0, y0, x1, y1) over all rings already concatenated. The standing
    subscription matcher (streaming/standing.py) keeps per-subscription
    edge lists in flat arrays instead of Geometry objects, so it packs
    kernel blocks from segments directly — one packing, no drift."""
    n = len(e)
    if n == 0 or n > E_BUCKETS[-1]:
        return None
    E = next(b for b in E_BUCKETS if n <= b)
    out = np.zeros((E, LANES), np.float32)
    dy = e[:, 3] - e[:, 1]
    horizontal = dy == 0.0
    islope = np.where(horizontal, 0.0, (e[:, 2] - e[:, 0]) / np.where(horizontal, 1.0, dy))
    out[:n, 0] = e[:, 1]  # y0
    out[:n, 1] = e[:, 3]  # y1
    out[:n, 2] = e[:, 0]  # x0
    out[:n, 3] = islope
    # conservative f32-uncertainty bands (coordinates are degrees, so the
    # absolute ulp scale is bounded by ulp(360) ~ 2.7e-5): points whose
    # crossing decision could flip under f32 rounding land inside them
    out[:n, 4] = 1e-3 + 3e-5 * np.abs(islope)
    out[:n, 5] = 1e-4
    return out


def n_edges_of(edges: "np.ndarray | None") -> int:
    """Static edge-bucket size of a pack_edges block (0 = no polygon)."""
    return 0 if edges is None else edges.shape[0]


def merge_window_slots_wide(config) -> np.ndarray | None:
    return merge_window_slots(config.windows, overflow="widen")


def merge_window_slots_inner(config) -> np.ndarray | None:
    """Inner slots from config.windows_inner; None (no certainty) when the
    index did not compute inner windows. Degenerate inner windows
    (off_lo > off_hi) never match — their rows stay uncertain. Overflow
    drops slots (subset) — widening an inner window would mark non-hits
    certain."""
    if config.windows_inner is None:
        return None
    w = np.asarray(config.windows_inner)
    w = w[w[:, 1] <= w[:, 2]] if len(w) else w
    return merge_window_slots(w, overflow="drop") if len(w) else None


# --------------------------------------------------------------- kernels


def _pip_edge_step(x, y, parity, near, edges, k):
    """ONE edge's contribution to the even-odd ray cast: the shared
    per-edge math of both PIP variants (unrolled Pallas / fori_loop XLA) —
    a numeric tweak here changes both backends together. ``edges``
    supports scalar [k, lane] indexing (Pallas ref or jnp array)."""
    y0, y1 = edges[k, 0], edges[k, 1]
    x0, isl = edges[k, 2], edges[k, 3]
    ex, ey = edges[k, 4], edges[k, 5]
    in_win = (y0 > y) != (y1 > y)
    xc = x0 + (y - y0) * isl
    return (
        parity ^ (in_win & (x < xc)),
        near
        | (jnp.abs(y - y0) < ey)
        | (jnp.abs(y - y1) < ey)
        | (in_win & (jnp.abs(x - xc) < ex)),
    )


def _pip_unrolled(x, y, edges, n_edges: int):
    """(parity, near) even-odd ray cast of [SUB, 128] points against the
    packed edge block — unrolled over the static edge count (Pallas and
    small-E XLA)."""
    parity = jnp.zeros(x.shape, dtype=jnp.bool_)
    near = jnp.zeros(x.shape, dtype=jnp.bool_)
    for k in range(n_edges):
        parity, near = _pip_edge_step(x, y, parity, near, edges, k)
    return parity, near


def _pip_loop(x, y, edges, n_edges: int):
    """Same contract as _pip_unrolled via lax.fori_loop (XLA variant for
    large E — keeps the HLO small; edges is a jnp array)."""
    from jax import lax

    def body(k, acc):
        return _pip_edge_step(x, y, acc[0], acc[1], edges, k)

    z = jnp.zeros(x.shape, dtype=jnp.bool_)
    return lax.fori_loop(0, n_edges, body, (z, z))


def _rint_step(c, in_grid, full, part, rast, k):
    """ONE interval's contribution to the raster cell classification —
    shared by the unrolled and fori_loop variants (``rast`` supports
    scalar [row, lane] indexing: Pallas ref or jnp array). Row k + 1
    (past the grid header) holds (lo, hi, cls); pad rows carry
    lo = 1 > hi = 0 and never match."""
    lo, hi, cl = rast[k + 1, 0], rast[k + 1, 1], rast[k + 1, 2]
    hit = in_grid & (c >= lo) & (c <= hi)
    return full | (hit & (cl > 0)), part | (hit & (cl < 0))


def _raster_cell(x, y, rast):
    """(cell id [SUB, 128] f32, in_grid bool) from the packed grid header.
    Cell ids are exact f32 integers (max.cells <= 2^24); sentinel pad
    rows (x = inf) fall outside the grid and classify OUT."""
    x0, y0 = rast[0, 0], rast[0, 1]
    icx, icy = rast[0, 2], rast[0, 3]
    nx, ny = rast[0, 4], rast[0, 5]
    cx = jnp.floor((x - x0) * icx)
    cy = jnp.floor((y - y0) * icy)
    in_grid = (cx >= 0) & (cx < nx) & (cy >= 0) & (cy < ny)
    return cy * nx + cx, in_grid


def _raster_unrolled(x, y, rast, n_rints: int):
    """(full, part) raster-interval classification of [SUB, 128] points —
    unrolled over the static interval count (Pallas and small-R XLA)."""
    c, in_grid = _raster_cell(x, y, rast)
    full = jnp.zeros(x.shape, dtype=jnp.bool_)
    part = jnp.zeros(x.shape, dtype=jnp.bool_)
    for k in range(n_rints):
        full, part = _rint_step(c, in_grid, full, part, rast, k)
    return full, part


def _raster_loop(x, y, rast, n_rints: int):
    """Same contract as _raster_unrolled via lax.fori_loop (XLA variant
    for large R — keeps the HLO small; rast is a jnp array)."""
    from jax import lax

    c, in_grid = _raster_cell(x, y, rast)

    def body(k, acc):
        return _rint_step(c, in_grid, acc[0], acc[1], rast, k)

    z = jnp.zeros(x.shape, dtype=jnp.bool_)
    return lax.fori_loop(0, n_rints, body, (z, z))


def _masks(
    cols: dict, boxes, wins, has_boxes: bool, has_windows: bool, extent: bool,
    edges=None, n_edges: int = 0, pip_loop: bool = False,
    rast=None, n_rints: int = 0,
):
    """(wide, inner) boolean masks for one block's columns.

    ``boxes``/``wins`` support scalar indexing (Pallas refs or jnp arrays).
    Unrolled over the 8 static slots — pad slots never match.
    In extent mode the inner plane is all-false (bbox-intersects certainty
    needs the actual geometry; XZ hits always refine, like the reference's
    XZ filters which are never "precise").

    With ``n_edges`` > 0 the spatial test is the exact device
    point-in-polygon tier instead of the box slots: wide = parity | near,
    inner = parity & ~near — rows outside the f32-uncertainty bands
    resolve ON DEVICE and the host refines only the near band (VERDICT r4
    #2: the always-refine polygon path moved on device).

    With ``n_rints`` > 0 the raster-interval tier classifies each row
    FIRST (arXiv 2307.01716): full cells are certain hits (wide + inner),
    out cells certain misses, and only the boundary residue consults the
    exact PIP — reusing _pip_unrolled/_pip_loop verbatim when edges ride
    along (device residue, bit-identical masks on partial rows), else
    wide-without-inner so the host refines the residue exactly.
    """
    one = None
    w_parts = []
    i_parts = []
    if n_rints:
        x, y = cols["x"], cols["y"]
        classify = _raster_loop if pip_loop else _raster_unrolled
        full, part = classify(x, y, rast, n_rints)
        if n_edges:
            pip = _pip_loop if pip_loop else _pip_unrolled
            parity, near = pip(x, y, edges, n_edges)
            w_parts.append(full | (part & (parity | near)))
            i_parts.append(full | (part & parity & ~near))
        else:
            w_parts.append(full | part)
            i_parts.append(full)
        one = x
    elif n_edges:
        x, y = cols["x"], cols["y"]
        pip = _pip_loop if pip_loop else _pip_unrolled
        parity, near = pip(x, y, edges, n_edges)
        w_parts.append(parity | near)
        i_parts.append(parity & ~near)
        one = x
    elif has_boxes:
        if extent:
            gx0, gy0 = cols["gxmin"], cols["gymin"]
            gx1, gy1 = cols["gxmax"], cols["gymax"]
            hit = jnp.zeros(gx0.shape, dtype=jnp.bool_)
            for k in range(8):
                hit |= (
                    (gx0 <= boxes[k, 2])
                    & (gx1 >= boxes[k, 0])
                    & (gy0 <= boxes[k, 3])
                    & (gy1 >= boxes[k, 1])
                )
            w_parts.append(hit)
            i_parts.append(jnp.zeros(gx0.shape, dtype=jnp.bool_))
            one = gx0
        else:
            x, y = cols["x"], cols["y"]
            wide = jnp.zeros(x.shape, dtype=jnp.bool_)
            inner = jnp.zeros(x.shape, dtype=jnp.bool_)
            for k in range(8):
                wide |= (
                    (x >= boxes[k, 0]) & (x <= boxes[k, 2])
                    & (y >= boxes[k, 1]) & (y <= boxes[k, 3])
                )
                inner |= (
                    (x >= boxes[k, 4]) & (x <= boxes[k, 6])
                    & (y >= boxes[k, 5]) & (y <= boxes[k, 7])
                )
            w_parts.append(wide)
            i_parts.append(inner)
            one = x
    if has_windows:
        if "tw" in cols:
            tw = cols["tw"]
            # pad sentinel -1 keeps tb = -1 (arithmetic shift): never
            # matches a real bin, so the & with the bin test stays safe
            tb = tw >> TW_BITS
            to = tw & TW_MASK
        else:
            tb, to = cols["tbin"], cols["toff"]
        wide = jnp.zeros(tb.shape, dtype=jnp.bool_)
        inner = jnp.zeros(tb.shape, dtype=jnp.bool_)
        for k in range(8):
            wide |= (
                (tb >= wins[k, 0]) & (tb <= wins[k, 1])
                & (to >= wins[k, 2]) & (to <= wins[k, 3])
            )
            inner |= (
                (tb >= wins[k, 4]) & (tb <= wins[k, 5])
                & (to >= wins[k, 6]) & (to <= wins[k, 7])
            )
        w_parts.append(wide)
        i_parts.append(inner)
    if not w_parts:
        # no predicate at all (INCLUDE-filter aggregations): the mask is
        # the row-validity test — table pad rows carry sentinels that must
        # not pollute counts/bounds. No constraint means every valid row is
        # a certain hit.
        if "x" in cols:
            v = jnp.isfinite(cols["x"])
        elif "gxmin" in cols:
            v = jnp.isfinite(cols["gxmin"])
        elif "tw" in cols:
            v = cols["tw"] >= 0
        else:
            v = cols["tbin"] >= 0
        return v, v
    w = w_parts[0]
    i = i_parts[0]
    for p, q in zip(w_parts[1:], i_parts[1:]):
        w = w & p
        i = i & q
    return w, i


_SHIFTS = None


def _pack_bits(m, pack):
    """[SUB, 128] bool -> [pack, 128] i32: bit b of word [j, lane] is local
    row (j*32 + b)*128 + lane. (i32 because Mosaic lacks unsigned reduces;
    the bit pattern is what matters.)"""
    u = m.astype(jnp.int32).reshape(pack, 32, LANES)
    shifts = jnp.arange(32, dtype=jnp.int32)[None, :, None]
    return (u << shifts).sum(axis=1, dtype=jnp.int32)


def skip_inner_plane(has_boxes: bool, extent: bool) -> bool:
    """Extent-mode box scans have an identically-false inner plane (bbox
    intersection can never certify the true geometry predicate — see
    _masks), so kernels skip emitting it and the host skips pulling it:
    at the measured ~30 MB/s pull bandwidth (PERF.md §1) the dead plane
    was ~half the per-query device time on XZ tables."""
    return extent and has_boxes


#: Device-op names the program chooses: a Pallas kernel's ``name`` becomes
#: its HLO instruction name, a ``jax.named_scope`` the ``op_name`` of the
#: XLA twin's fusions. The profiler's trace, and whatever reduces it
#: (benchmark/kernels/*.json match on these), find a kernel by them, and
#: they survive a renamed Python function.
SCAN_NAME = "geomesa_block_scan"
SCAN_MULTI_NAME = "geomesa_block_scan_multi"


def _make_pallas_kernel(
    col_names, has_boxes, has_windows, extent, pack, n_edges=0, n_rints=0
):
    n = len(col_names)
    skip = skip_inner_plane(has_boxes, extent)

    def kernel(bids_ref, boxes_ref, wins_ref, *refs):
        edges_ref = rast_ref = None
        if n_edges:
            edges_ref, refs = refs[0], refs[1:]
        if n_rints:
            rast_ref, refs = refs[0], refs[1:]
        cols = {name: refs[k][0] for k, name in enumerate(col_names)}
        w, i = _masks(
            cols, boxes_ref, wins_ref, has_boxes, has_windows, extent,
            edges=edges_ref, n_edges=n_edges, rast=rast_ref, n_rints=n_rints,
        )
        refs[n][0] = _pack_bits(w, pack)
        if not skip:
            refs[n + 1][0] = _pack_bits(i, pack)

    return kernel


@partial(
    jax.jit,
    static_argnames=(
        "col_names", "has_boxes", "has_windows", "extent", "interpret",
        "n_edges", "n_rints", "name",
    ),
)
def _pallas_block_scan(
    cols3, bids, boxes, wins, edges=None, rast=None, *, col_names, has_boxes,
    has_windows, extent, interpret, n_edges=0, n_rints=0, name=SCAN_NAME,
):
    """cols3: tuple of [n_blocks, SUB, 128] device arrays ordered by
    col_names. bids: i32 [M] candidate block ids (pads repeat block 0; host
    ignores pad slots). Returns (wide, inner) [M, PACK, 128] i32 planes.
    ``name``: the device op's name (the pops aggregation reuses this
    kernel under its own)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = bids.shape[0]
    SUB = cols3[0].shape[1]
    PACK = SUB // 32
    n_out = 1 if skip_inner_plane(has_boxes, extent) else 2
    kernel = _make_pallas_kernel(
        col_names, has_boxes, has_windows, extent, PACK, n_edges, n_rints
    )
    edge_specs = (
        [pl.BlockSpec((n_edges, LANES), lambda i, bids: (0, 0))] if n_edges else []
    )
    rast_specs = (
        [pl.BlockSpec((1 + n_rints, LANES), lambda i, bids: (0, 0))]
        if n_rints else []
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((8, LANES), lambda i, bids: (0, 0)),
            pl.BlockSpec((8, LANES), lambda i, bids: (0, 0)),
        ]
        + edge_specs
        + rast_specs
        + [
            pl.BlockSpec((1, SUB, LANES), lambda i, bids: (bids[i], 0, 0))
            for _ in col_names
        ],
        out_specs=[
            pl.BlockSpec((1, PACK, LANES), lambda i, bids: (i, 0, 0))
        ] * n_out,
    )
    extra = (() if not n_edges else (edges,)) + (() if not n_rints else (rast,))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, PACK, LANES), jnp.int32)] * n_out,
        interpret=interpret,
        name=name,
    )(bids, boxes, wins, *extra, *cols3)
    return (out[0], None) if n_out == 1 else (out[0], out[1])


@partial(
    jax.jit,
    static_argnames=(
        "col_names", "has_boxes", "has_windows", "extent", "n_edges", "n_rints"
    ),
)
@jax.named_scope(SCAN_NAME)
def _xla_block_scan(
    cols3, bids, boxes, wins, edges=None, rast=None, *, col_names, has_boxes,
    has_windows, extent, n_edges=0, n_rints=0,
):
    """Same contract as the Pallas kernel via plain XLA (gather of candidate
    blocks). Used on CPU (tests), as a portability fallback, and for
    large-E polygon scans (the unrolled Pallas kernel caps at
    PALLAS_MAX_EDGES; the fori_loop variant keeps the HLO small)."""
    gathered = {name: c[bids] for name, c in zip(col_names, cols3)}
    w, i = _masks(
        gathered, boxes, wins, has_boxes, has_windows, extent,
        edges=edges, n_edges=n_edges, pip_loop=True,
        rast=rast, n_rints=n_rints,
    )
    shifts = jnp.arange(32, dtype=jnp.int32)[None, None, :, None]
    M = bids.shape[0]
    PACK = cols3[0].shape[1] // 32

    def pack(m):
        u = m.astype(jnp.int32).reshape(M, PACK, 32, LANES)
        return (u << shifts).sum(axis=2, dtype=jnp.int32)

    if skip_inner_plane(has_boxes, extent):
        return pack(w), None
    return pack(w), pack(i)


def block_scan(
    cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent,
    edges=None, n_edges=0, rast=None, n_rints=0,
):
    """Dispatch to Pallas (TPU) / interpret / XLA by backend. All shapes
    static: (len(bids), col_names, flags, n_edges, n_rints) determine the
    compiled variant. Returns (wide, inner) planes; inner is None when
    skip_inner_plane() (extent box scans — identically false)."""
    if use_pallas() and n_edges <= PALLAS_MAX_EDGES and n_rints <= PALLAS_MAX_RINTS:
        interpret = jax.default_backend() != "tpu"
        return _pallas_block_scan(
            cols3, bids, boxes, wins, edges, rast,
            col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent, interpret=interpret, n_edges=n_edges, n_rints=n_rints,
        )
    return _xla_block_scan(
        cols3, bids, boxes, wins, edges, rast,
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
        extent=extent, n_edges=n_edges, n_rints=n_rints,
    )


# ------------------------------------------------ fused multi-query scan


def _make_pallas_kernel_multi(
    col_names, has_boxes, has_windows, extent, pack, n_edges=0, n_rints=0
):
    n = len(col_names)
    skip = skip_inner_plane(has_boxes, extent)
    poly_leg = bool(n_edges or n_rints)

    def kernel(bids_ref, qids_ref, *refs):
        from jax.experimental import pallas as pl

        del bids_ref, qids_ref  # consumed by the index maps
        edges_ref = rast_ref = None
        if poly_leg:
            spip_ref, boxes_ref, wins_ref = refs[:3]
            refs = refs[3:]
            if n_edges:
                edges_ref, refs = refs[0], refs[1:]
            if n_rints:
                rast_ref, refs = refs[0], refs[1:]
        else:
            boxes_ref, wins_ref = refs[:2]
            refs = refs[2:]
        cols = {name: refs[k][0] for k, name in enumerate(col_names)}
        w, i = _masks(cols, boxes_ref[0], wins_ref[0], has_boxes, has_windows, extent)
        if poly_leg:
            # polygon leg: the same _masks with this slot's query edge /
            # raster-interval blocks — selected per SLOT by the
            # scalar-prefetched spip flag, so box and polygon queries
            # share one fused chunk (a box query's slot keeps the box
            # leg; its zero-padded stack rows are unused)
            wp, ip = _masks(
                cols, boxes_ref[0], wins_ref[0], has_boxes, has_windows,
                extent, edges=edges_ref[0] if n_edges else None,
                n_edges=n_edges,
                rast=rast_ref[0] if n_rints else None, n_rints=n_rints,
            )
            # select on the i32 form _pack_bits packs anyway: Mosaic has
            # no vector select with boolean operands
            use_pip = spip_ref[pl.program_id(0)] > 0
            w = jnp.where(use_pip, wp.astype(jnp.int32), w.astype(jnp.int32))
            i = jnp.where(use_pip, ip.astype(jnp.int32), i.astype(jnp.int32))
        refs[n][0] = _pack_bits(w, pack)
        if not skip:
            refs[n + 1][0] = _pack_bits(i, pack)

    return kernel


@partial(
    jax.jit,
    static_argnames=(
        "col_names", "has_boxes", "has_windows", "extent", "interpret",
        "n_edges", "n_rints",
    ),
)
def _pallas_block_scan_multi(
    cols3, bids, qids, boxes, wins, edges=None, spip=None, rasts=None, *,
    col_names, has_boxes, has_windows, extent, interpret, n_edges=0, n_rints=0,
):
    """Fused form of _pallas_block_scan: slot i scans block bids[i] against
    query qids[i]'s packed params (boxes/wins are [Q, 8, 128]). Two
    scalar-prefetch operands drive the index maps; everything else is the
    single-query kernel per slot. With ``n_edges`` or ``n_rints`` > 0 a
    third scalar-prefetch operand ``spip`` ([M] i32, 1 = this slot's query
    runs the polygon tier) plus per-query [Q, n_edges, 128] ``edges`` /
    [Q, 1 + n_rints, 128] ``rasts`` stacks (gathered per slot by qid,
    like boxes/wins) add the fused point-in-polygon / raster-interval
    legs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = bids.shape[0]
    SUB = cols3[0].shape[1]
    PACK = SUB // 32
    n_out = 1 if skip_inner_plane(has_boxes, extent) else 2
    kernel = _make_pallas_kernel_multi(
        col_names, has_boxes, has_windows, extent, PACK, n_edges, n_rints
    )
    if n_edges or n_rints:
        by_q = lambda i, bids, qids, spip: (qids[i], 0, 0)  # noqa: E731
        by_b = lambda i, bids, qids, spip: (bids[i], 0, 0)  # noqa: E731
        by_i = lambda i, bids, qids, spip: (i, 0, 0)        # noqa: E731
        n_prefetch = 3
        param_specs = [
            pl.BlockSpec((1, 8, LANES), by_q),
            pl.BlockSpec((1, 8, LANES), by_q),
        ]
        extra = ()
        if n_edges:
            param_specs.append(pl.BlockSpec((1, n_edges, LANES), by_q))
            extra = extra + (edges,)
        if n_rints:
            param_specs.append(pl.BlockSpec((1, 1 + n_rints, LANES), by_q))
            extra = extra + (rasts,)
        args = (bids, qids, spip, boxes, wins) + extra
    else:
        by_b = lambda i, bids, qids: (bids[i], 0, 0)        # noqa: E731
        by_i = lambda i, bids, qids: (i, 0, 0)              # noqa: E731
        by_q = lambda i, bids, qids: (qids[i], 0, 0)        # noqa: E731
        n_prefetch = 2
        param_specs = [
            pl.BlockSpec((1, 8, LANES), by_q),
            pl.BlockSpec((1, 8, LANES), by_q),
        ]
        args = (bids, qids, boxes, wins)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(M,),
        in_specs=param_specs
        + [pl.BlockSpec((1, SUB, LANES), by_b) for _ in col_names],
        out_specs=[pl.BlockSpec((1, PACK, LANES), by_i)] * n_out,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, PACK, LANES), jnp.int32)] * n_out,
        interpret=interpret,
        name=SCAN_MULTI_NAME,
    )(*args, *cols3)
    return (out[0], None) if n_out == 1 else (out[0], out[1])


@partial(
    jax.jit,
    static_argnames=(
        "col_names", "has_boxes", "has_windows", "extent", "n_edges", "n_rints"
    ),
)
@jax.named_scope(SCAN_MULTI_NAME)
def _xla_block_scan_multi(
    cols3, bids, qids, boxes, wins, edges=None, spip=None, rasts=None, *,
    col_names, has_boxes, has_windows, extent, n_edges=0, n_rints=0,
):
    """XLA fallback for the fused multi-query scan: gather each slot's
    column block and params, vmap the single-block mask over slots. With
    ``n_edges``/``n_rints`` > 0 the per-slot edge/raster blocks
    (``edges[qids]``/``rasts[qids]``) and the ``spip`` selector add the
    polygon leg — the fori_loop variants keep the HLO small at large
    E/R, exactly like the single-query XLA kernel."""
    PACK = cols3[0].shape[1] // 32
    gathered = tuple(c[bids] for c in cols3)
    bq, wq = boxes[qids], wins[qids]
    skip = skip_inner_plane(has_boxes, extent)
    poly_leg = bool(n_edges or n_rints)

    def slot_masks(box, win, eb, rb, sp, *colblk):
        cols = dict(zip(col_names, colblk))
        w, i = _masks(cols, box, win, has_boxes, has_windows, extent)
        if poly_leg:
            wp, ip = _masks(
                cols, box, win, has_boxes, has_windows, extent,
                edges=eb if n_edges else None, n_edges=n_edges, pip_loop=True,
                rast=rb if n_rints else None, n_rints=n_rints,
            )
            w = jnp.where(sp > 0, wp, w)
            i = jnp.where(sp > 0, ip, i)
        return w, i

    # dummy per-slot operands so ONE vmapped body serves every shape
    eq = edges[qids] if n_edges else jnp.zeros((bids.shape[0], 1), jnp.float32)
    rq = rasts[qids] if n_rints else jnp.zeros((bids.shape[0], 1), jnp.float32)
    sq = spip if poly_leg else jnp.zeros(bids.shape[0], jnp.int32)

    if skip:

        def per_block_w(box, win, eb, rb, sp, *colblk):
            w, _ = slot_masks(box, win, eb, rb, sp, *colblk)
            return _pack_bits(w, PACK)

        return jax.vmap(per_block_w)(bq, wq, eq, rq, sq, *gathered), None

    def per_block(box, win, eb, rb, sp, *colblk):
        w, i = slot_masks(box, win, eb, rb, sp, *colblk)
        return _pack_bits(w, PACK), _pack_bits(i, PACK)

    return jax.vmap(per_block)(bq, wq, eq, rq, sq, *gathered)


def block_scan_multi(
    cols3, bids, qids, boxes, wins, *, col_names, has_boxes, has_windows,
    extent, edges=None, spip=None, n_edges=0, rasts=None, n_rints=0,
):
    """Fused multi-query scan (round 5): ONE kernel dispatch scans many
    queries' candidate blocks — slot i reads block ``bids[i]`` with query
    ``qids[i]``'s params from ``boxes``/``wins`` [Q, 8, 128] stacks. Output
    planes are per-slot exactly like :func:`block_scan`; each query's rows
    decode from its contiguous slot segment. Amortizes the per-dispatch
    overhead that serialized many-small-query workloads (the indexed
    spatial join's 256 per-polygon scans).

    PIP fusion (round 6): ``n_edges`` > 0 adds a [Q, n_edges, 128]
    ``edges`` stack (pack_edges blocks zero-padded to the chunk's
    FUSED_E_BUCKETS bucket) and a per-slot ``spip`` i32 selector — slots
    whose query carries a polygon run the exact device point-in-polygon
    tier, box-query slots keep the box test, all in the same dispatch.
    Past PALLAS_MAX_EDGES the chunk rides the XLA variant (the unrolled
    Pallas kernel gets too large), same as the single-query ladder.

    Raster fusion (round 7): ``n_rints`` > 0 adds a [Q, 1 + n_rints, 128]
    ``rasts`` stack (RasterApprox.pack_block blocks zero-padded to the
    chunk's FUSED_R_BUCKETS bucket) — slots whose query carries a raster
    classify rows by integer interval lookup first, running the exact PIP
    only on the boundary residue (in-kernel when edges ride along, else
    via host refinement of the uncertain rows). The ``spip`` selector
    covers both polygon tiers.

    Static compile key: (M bucket, Q stack height, col_names, flags,
    n_edges, n_rints). Production callers use the canonical fixed chunk
    shape — ``IndexTable.fused_slots`` x FUSED_CHUNK_Q (storage.table) —
    so ONE compiled variant per (columns, flags, E bucket, R bucket)
    serves every batch.
    """
    if use_pallas() and n_edges <= PALLAS_MAX_EDGES and n_rints <= PALLAS_MAX_RINTS:
        interpret = jax.default_backend() != "tpu"
        return _pallas_block_scan_multi(
            cols3, bids, qids, boxes, wins, edges, spip, rasts,
            col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent, interpret=interpret, n_edges=n_edges, n_rints=n_rints,
        )
    return _xla_block_scan_multi(
        cols3, bids, qids, boxes, wins, edges, spip, rasts,
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
        extent=extent, n_edges=n_edges, n_rints=n_rints,
    )


# --------------------------------------------------------------- decode


def _unpack_plane(plane: np.ndarray, n_real: int) -> np.ndarray:
    """[M, pack, 128] i32 plane -> [n_real, block] bool rows (inverts
    _pack_bits: bit b of word [blk, j, lane] = local row (j*32+b)*128+lane)."""
    pack = plane.shape[1]
    p = np.ascontiguousarray(plane[:n_real])
    bits = np.unpackbits(
        p.view(np.uint8).reshape(n_real, pack, LANES, 4), axis=-1, bitorder="little"
    )  # [m, pack, 128, 32]
    return bits.transpose(0, 1, 3, 2).reshape(n_real, pack * 32 * LANES)


def decode_bits(plane: np.ndarray, bids: np.ndarray, n_real: int) -> np.ndarray:
    """[M, pack, 128] i32 plane -> ascending global row ids (i64)."""
    if n_real == 0:
        return np.zeros(0, np.int64)
    block = plane.shape[1] * 32 * LANES

    from geomesa_tpu import native

    rows = native.bitmask_decode(plane, np.asarray(bids, np.int64), n_real, block)
    if rows is None:
        flat = _unpack_plane(plane, n_real)
        blk, local = np.nonzero(flat)
        rows = bids[:n_real][blk].astype(np.int64) * block + local
    return np.sort(rows) if not _bids_sorted(bids, n_real) else rows


def decode_bits_pair(wide_plane, inner_plane, bids, n_real):
    """(rows, certain) — rows ascending, certain[i] True when row i is in
    the inner plane (no host refinement needed). ``inner_plane=None``
    (extent scans, skip_inner_plane) decodes wide only with certain all
    False. Native C++ decode when available (~25x the numpy route on large
    pulls); exact numpy fallback."""
    if n_real == 0:
        return np.zeros(0, np.int64), np.zeros(0, bool)
    block = wide_plane.shape[1] * 32 * LANES
    if inner_plane is None:
        rows = decode_bits(wide_plane, bids, n_real)
        return rows, np.zeros(len(rows), bool)

    from geomesa_tpu import native

    nat = native.bitmask_decode_pair(
        wide_plane, inner_plane, np.asarray(bids, np.int64), n_real, block
    )
    if nat is not None:
        rows, certain = nat
        if not _bids_sorted(bids, n_real):
            order = np.argsort(rows, kind="stable")
            rows, certain = rows[order], certain[order]
        return rows, certain

    wb = _unpack_plane(wide_plane, n_real)
    ib = _unpack_plane(inner_plane, n_real)
    blk, local = np.nonzero(wb)
    rows = bids[:n_real][blk].astype(np.int64) * block + local
    certain = ib[blk, local].astype(bool)
    if not _bids_sorted(bids, n_real):
        order = np.argsort(rows, kind="stable")
        rows, certain = rows[order], certain[order]
    return rows, certain


def _bids_sorted(bids: np.ndarray, n_real: int) -> bool:
    b = bids[:n_real]
    return bool(np.all(b[1:] > b[:-1])) if len(b) > 1 else True


def bucket_of(n: int) -> int:
    """Static M bucket for an n-block candidate list: the smallest fixed
    bucket >= n, or the next power of two past the largest bucket (full
    scans — still one static shape per table)."""
    for m in M_BUCKETS:
        if n <= m:
            return m
    m = M_BUCKETS[-1]
    while m < n:
        m *= 2
    return m


def pad_bids(
    blocks: np.ndarray, n_blocks_table: int, pad: int = 0, bucket: int | None = None
) -> tuple[np.ndarray, int]:
    """Pad a sorted block-id list to a static M bucket. Returns
    (padded [M] i32, n_real).

    ``pad=0`` repeats block 0 (scan kernels: the decode ignores pad slots);
    ``pad=-1`` marks pads explicitly (aggregation kernels: the mask drops
    them, the Pallas index map clamps them to 0). ``bucket`` forces the
    bucket — the distributed table pads every device's list to the same M.
    """
    n = len(blocks)
    m = bucket if bucket is not None else bucket_of(n)
    out = np.full(m, pad, np.int32)
    out[:n] = blocks
    return out, n
