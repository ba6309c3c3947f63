"""Aggregation push-down over the block layout: density, bounds, counts.

Reference: the server-side aggregating scans — DensityScan renders matching
rows onto a pixel grid inside region servers (/root/reference/
geomesa-index-api/src/main/scala/org/locationtech/geomesa/index/iterators/
DensityScan.scala:29-100 over utils/geom/RenderingGrid + GridSnap), and
StatsScan folds stat sketches over rows (iterators/StatsScan.scala).

Same candidate-block contract as scan.block_kernels.block_scan: the host
prunes the sorted table to candidate blocks, pads the id list to a static
M bucket, and the device evaluates the shared wide predicate (``_masks``)
over whole blocks — no per-row gathers (the round-2 design this replaces
indexed ``cols[...][base]`` row-by-row, the access pattern measured at
~1000x below stream bandwidth; see PERF.md).

Two backends per kernel:
- XLA (CPU tests + portability): one first-axis gather of candidate
  blocks, then fused mask/reduce; block-granular gathers are contiguous
  64 KB+ DMAs, not row gathers.
- Pallas (TPU): scalar-prefetched block DMA; density accumulates the grid
  in VMEM via an MXU one-hot matmul histogram (no scatter — TPU has no
  fast vector scatter, but ``A^T @ B`` over one-hot pixel-coordinate
  planes IS the histogram), contracted per block over the window of the
  grid that the block's rows touch (the table is sorted by its curve, so
  they are neighbours on the map; a block spread wider takes the whole
  grid, one with no row in the tile takes nothing), bounds reduce
  per-block on the VPU.

Pad slots are -1 (``pad_bids(..., pad=-1)``): the XLA path masks them out,
the Pallas index map clamps them to block 0 and the kernels mask them (the
density kernel skips them before any vector work).
Sharded tables run these same kernels per shard under ``shard_map`` and
merge with ``psum`` (geomesa_tpu.parallel.dtable), the analogue of the
client-side reducer merging coprocessor partials.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from geomesa_tpu.scan import block_kernels as bk

# per-slot bounds stats lane layout: [count, xmin, xmax, ymin, ymax, 0...]
STAT_LANES = 8

#: device-op names (see block_kernels.SCAN_NAME): the Pallas kernels carry
#: them, the XLA twins' fusions show them as their ``op_name`` scope
POPS_NAME = "geomesa_pops"
DENSITY_NAME = "geomesa_density"
BOUNDS_NAME = "geomesa_bounds"


def _rep_xy(cols: dict, extent: bool):
    """Representative coordinates per row: the point, or the bbox centroid
    for extent geometries (the point-vs-shape split of the reference's
    DensityScan.getWeight; exact shape rendering stays on host)."""
    if extent:
        x = (cols["gxmin"] + cols["gxmax"]) * 0.5
        y = (cols["gymin"] + cols["gymax"]) * 0.5
        return x, y
    return cols["x"], cols["y"]


# ------------------------------------------------------------------ pops


def block_pops(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent):
    """[M] i32 wide-predicate hit count per candidate block slot (pads
    included — the host slices [:n_real]). One fused program: the scan
    kernel's wide plane popcounted and reduced on device, so a count-only
    query pulls M ints, not M bit planes."""
    kw = dict(
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows, extent=extent
    )
    if bk.use_pallas():
        return _pops_pallas(
            cols3, bids, boxes, wins,
            interpret=jax.default_backend() != "tpu", **kw,
        )
    return _pops_xla(cols3, bids, boxes, wins, **kw)


def _popcount_slots(plane):
    """[M, PACK, LANES] i32 bit plane -> [M] i32 set-bit counts."""
    u = lax.bitcast_convert_type(plane, jnp.uint32)
    return lax.population_count(u).sum(axis=(1, 2)).astype(jnp.int32)


@partial(
    jax.jit,
    static_argnames=("col_names", "has_boxes", "has_windows", "extent", "interpret"),
)
@jax.named_scope(POPS_NAME)
def _pops_pallas(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent, interpret):
    wide, _ = bk._pallas_block_scan(
        cols3, jnp.maximum(bids, 0), boxes, wins,
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
        extent=extent, interpret=interpret, name=POPS_NAME,
    )
    return _popcount_slots(wide)


@partial(jax.jit, static_argnames=("col_names", "has_boxes", "has_windows", "extent"))
@jax.named_scope(POPS_NAME)
def _pops_xla(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent):
    wide, _ = bk._xla_block_scan(
        cols3, jnp.maximum(bids, 0), boxes, wins,
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows, extent=extent,
    )
    return _popcount_slots(wide)


# --------------------------------------------------------------- density


def block_density(
    cols3, bids, boxes, wins, grid_bounds, *,
    col_names, has_boxes, has_windows, extent, width, height, counts=False,
):
    """[height, width] f32 density grid over ``grid_bounds`` (x0,y0,x1,y1).

    Each wide-predicate hit inside the grid envelope adds weight 1 to its
    pixel (reference GridSnap cell assignment; rows outside the envelope
    are dropped, not clamped — DensityScan only renders within bounds).
    bids: i32 [M], -1 = pad slot. grid_bounds: f32 [4] (rides the jit
    dispatch — the envelope is dynamic, only width/height are compiled in).

    ``counts=True`` returns ``(grid, counts)``: the Pallas kernel's i32[3]
    device array of slots by path (skipped, windowed, whole; see
    ``_make_density_kernel``), None from the XLA twin, which has one path.
    The same program runs either way."""
    kw = dict(
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows,
        extent=extent, width=width, height=height,
    )
    ch = _density_chunk(width, height, cols3[0].shape[1], len(col_names))
    if ch is not None and bk.use_pallas():
        grid, paths = _pallas_density(
            cols3, bids, boxes, wins, grid_bounds,
            interpret=jax.default_backend() != "tpu", chunk=ch, **kw,
        )
    else:
        grid, paths = _xla_density(cols3, bids, boxes, wins, grid_bounds, **kw), None
    return (grid, paths) if counts else grid


@partial(
    jax.jit,
    static_argnames=("col_names", "has_boxes", "has_windows", "extent", "width", "height"),
)
@jax.named_scope(DENSITY_NAME)
def _xla_density(
    cols3, bids, boxes, wins, grid_bounds, *,
    col_names, has_boxes, has_windows, extent, width, height,
):
    """XLA fallback: block-granular gather + scatter-add. Fine on CPU;
    on TPU the scatter serializes, which is why the Pallas matmul
    histogram exists."""
    gathered = {n: c[jnp.maximum(bids, 0)] for n, c in zip(col_names, cols3)}
    w, _ = bk._masks(gathered, boxes, wins, has_boxes, has_windows, extent)
    x, y = _rep_xy(gathered, extent)
    x0, y0 = grid_bounds[0], grid_bounds[1]
    x1, y1 = grid_bounds[2], grid_bounds[3]
    m = (
        w
        & (bids >= 0)[:, None, None]
        & (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
    )
    px = jnp.clip(((x - x0) / (x1 - x0) * width).astype(jnp.int32), 0, width - 1)
    py = jnp.clip(((y - y0) / (y1 - y0) * height).astype(jnp.int32), 0, height - 1)
    flat = (py * width + px).ravel()
    grid = jnp.zeros(height * width, jnp.float32).at[flat].add(
        m.ravel().astype(jnp.float32)
    )
    return grid.reshape(height, width)


# density matmul-histogram chunk: sublanes folded into the contraction dim
# per dot. 32 sublanes * 128 lanes = 4096-deep contractions keep the MXU
# busy (one dot per chunk instead of one per sublane).
_DENSITY_CHUNK = 32

# density window: the grid rows one contraction covers when a block's rows
# fall inside them and inside one 128-lane tile (see _make_density_kernel).
# Chosen on a v5e over 2^27 GPS points: 32 and 64 rows cost the same slot
# (the MXU loads 128 weight tiles a block whatever the height) and catch
# the same blocks to a point; 128 rows cost half as much again in planes.
_DENSITY_WINDOW_ROWS = 32


def _density_chunk(width, height, sub, n_cols) -> int | None:
    """Largest sublane chunk whose working set fits VMEM, or None when no
    chunk does (very large grids) — the caller then takes the XLA scatter
    path instead of failing Mosaic compilation."""
    from geomesa_tpu.conf import DENSITY_VMEM_BUDGET

    budget = DENSITY_VMEM_BUDGET.get()  # headroom under the ~16 MB VMEM
    hp = -(-height // 8) * 8
    wp = -(-width // bk.LANES) * bk.LANES
    fixed = 2 * hp * wp * 4 + n_cols * sub * bk.LANES * 4 + (1 << 20)  # acc+out, cols, slack
    ch = min(_DENSITY_CHUNK, sub)
    while ch >= 8:
        if fixed + (hp + wp) * ch * bk.LANES * 2 <= budget:
            return ch
        ch //= 2
    return None


def _make_density_kernel(col_names, has_boxes, has_windows, extent, width, height, hp, wp, sub, ch):
    """TPU has no fast vector scatter, but a histogram IS a matmul over
    one-hot planes: for each row r with pixel (py, px), grid = Ay^T-style
    contraction of Ay[h, r] = (py_r == h) against Ax[w, r] = (px_r == w)
    masked — both built with broadcasted_iota compares in VMEM, contracted
    on the MXU. The grid accumulates in VMEM across grid steps (init at
    step 0), padded to (8, 128)-aligned (hp, wp); the host slices to
    (height, width).

    The table is sorted by its curve, so a block's rows are neighbours on
    the map and most of those planes would be zeros. Per slot the kernel
    therefore takes the pixel extent of the block's masked rows and
    contracts over what they touch, no more:
    - nothing (a pad slot, known from the prefetched id; a block with no
      row inside box and envelope): no planes, no contraction;
    - a window of ``_DENSITY_WINDOW_ROWS`` grid rows starting at a multiple
      of 8, inside one 128-lane tile: planes relative to the window's
      origin, one [rows, 128] contraction, added to that part of the grid;
    - else the whole (hp, wp) grid.
    The pixels, the mask and the f32 sums are the same in every path, so
    the grid is the same array bit for bit; only multiplications by zero
    are left out. ``cnt_ref`` (SMEM, i32[3]) counts the slots by path:
    skipped, windowed, whole."""
    import jax.experimental.pallas as pl

    n = len(col_names)
    hb = min(_DENSITY_WINDOW_ROWS, hp)
    can_window = hb < hp or wp > bk.LANES  # else the window IS the grid

    def histogram(pix_y, pix_x, rows, lanes):
        """[rows, lanes] f32 counts of one block: pix_y -1 matches no iota
        row, so the mask rides Ay."""
        acc = jnp.zeros((rows, lanes), jnp.float32)
        for c in range(sub // ch):
            yy = pix_y[c * ch : (c + 1) * ch, :].reshape(1, ch * bk.LANES)
            xx = pix_x[c * ch : (c + 1) * ch, :].reshape(1, ch * bk.LANES)
            ay = (lax.broadcasted_iota(jnp.int32, (rows, ch * bk.LANES), 0) == yy).astype(
                jnp.bfloat16
            )
            ax = (lax.broadcasted_iota(jnp.int32, (lanes, ch * bk.LANES), 0) == xx).astype(
                jnp.bfloat16
            )
            acc += lax.dot_general(
                ay, ax, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
        return acc

    def kernel(bids_ref, boxes_ref, wins_ref, gb_ref, *refs):
        out_ref, cnt_ref = refs[n], refs[n + 1]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)
            for k in range(3):
                cnt_ref[k] = 0

        @pl.when(bids_ref[i] < 0)
        def _():
            cnt_ref[0] += 1

        @pl.when(bids_ref[i] >= 0)
        def _():
            cols = {name: refs[k][0] for k, name in enumerate(col_names)}
            w, _ = bk._masks(cols, boxes_ref, wins_ref, has_boxes, has_windows, extent)
            x, y = _rep_xy(cols, extent)
            x0, y0 = gb_ref[0, 0], gb_ref[0, 1]
            x1, y1 = gb_ref[0, 2], gb_ref[0, 3]
            m = w & (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
            px = jnp.clip(((x - x0) / (x1 - x0) * width).astype(jnp.int32), 0, width - 1)
            py = jnp.clip(((y - y0) / (y1 - y0) * height).astype(jnp.int32), 0, height - 1)
            # the extent of the masked rows' pixels, as scalars
            ylo = jnp.min(jnp.where(m, py, hp))
            yhi = jnp.max(jnp.where(m, py, -1))
            hit = yhi >= 0
            whole = hit
            if can_window:
                xlo = jnp.min(jnp.where(m, px, wp))
                xhi = jnp.max(jnp.where(m, px, -1))
                # window origin: a sublane tile (>> 3: 8 rows; past the grid's
                # last rows it slides up) and a lane tile (>> 7: bk.LANES)
                wy = jnp.minimum((ylo >> 3) << 3, hp - hb)
                tx = xlo >> 7
                fits = hit & (yhi < wy + hb) & ((xhi >> 7) == tx)
                whole = hit & jnp.logical_not(fits)

                @pl.when(fits)
                def _():
                    acc = histogram(
                        jnp.where(m, py - wy, -1), px - (tx << 7), hb, bk.LANES
                    )
                    rows = pl.ds(pl.multiple_of(wy, 8), hb)
                    for t in range(wp // bk.LANES):
                        @pl.when(tx == t)
                        def _():
                            out_ref[rows, t * bk.LANES : (t + 1) * bk.LANES] += acc

                    cnt_ref[1] += 1

            @pl.when(whole)
            def _():
                out_ref[...] += histogram(jnp.where(m, py, -1), px, hp, wp)
                cnt_ref[2] += 1

            @pl.when(jnp.logical_not(hit))
            def _():
                cnt_ref[0] += 1

    return kernel


@partial(
    jax.jit,
    static_argnames=(
        "col_names", "has_boxes", "has_windows", "extent", "width", "height",
        "interpret", "chunk",
    ),
)
def _pallas_density(
    cols3, bids, boxes, wins, grid_bounds, *,
    col_names, has_boxes, has_windows, extent, width, height, interpret, chunk,
):
    """-> ([height, width] f32 grid, i32[3] slots skipped, windowed, whole)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = bids.shape[0]
    SUB = cols3[0].shape[1]
    hp = -(-height // 8) * 8
    wp = -(-width // bk.LANES) * bk.LANES
    kernel = _make_density_kernel(
        col_names, has_boxes, has_windows, extent, width, height, hp, wp, SUB, chunk
    )
    gb = jnp.zeros((1, bk.LANES), jnp.float32).at[0, :4].set(grid_bounds)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((8, bk.LANES), lambda i, bids: (0, 0)),
            pl.BlockSpec((8, bk.LANES), lambda i, bids: (0, 0)),
            pl.BlockSpec((1, bk.LANES), lambda i, bids: (0, 0)),
        ]
        + [
            pl.BlockSpec((1, SUB, bk.LANES), lambda i, bids: (jnp.maximum(bids[i], 0), 0, 0))
            for _ in col_names
        ],
        out_specs=[
            pl.BlockSpec((hp, wp), lambda i, bids: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
    )
    grid, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((hp, wp), jnp.float32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        interpret=interpret,
        name=DENSITY_NAME,
    )(bids, boxes, wins, gb, *cols3)
    return grid[:height, :width], counts


# ---------------------------------------------------------------- bounds


def block_bounds(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent):
    """[M, STAT_LANES] f32 per-slot stats: lanes (count, xmin, xmax, ymin,
    ymax, 0, 0, 0) over wide-predicate hits of each candidate block. The
    host reduces over real slots — per-slot output needs no cross-step
    accumulation and pad slots are simply ignored. Counts are exact in f32
    (a block holds <= 2^24 rows)."""
    kw = dict(
        col_names=col_names, has_boxes=has_boxes, has_windows=has_windows, extent=extent
    )
    if bk.use_pallas():
        return _pallas_bounds(
            cols3, bids, boxes, wins,
            interpret=jax.default_backend() != "tpu", **kw,
        )
    return _xla_bounds(cols3, bids, boxes, wins, **kw)


def _bounds_stack(w, x, y):
    """Masked per-slot reductions -> [M, STAT_LANES]."""
    inf = jnp.float32(jnp.inf)
    cnt = w.sum(axis=(1, 2), dtype=jnp.float32)
    xmin = jnp.where(w, x, inf).min(axis=(1, 2))
    xmax = jnp.where(w, x, -inf).max(axis=(1, 2))
    ymin = jnp.where(w, y, inf).min(axis=(1, 2))
    ymax = jnp.where(w, y, -inf).max(axis=(1, 2))
    zero = jnp.zeros_like(cnt)
    return jnp.stack([cnt, xmin, xmax, ymin, ymax, zero, zero, zero], axis=1)


@partial(jax.jit, static_argnames=("col_names", "has_boxes", "has_windows", "extent"))
@jax.named_scope(BOUNDS_NAME)
def _xla_bounds(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent):
    gathered = {n: c[jnp.maximum(bids, 0)] for n, c in zip(col_names, cols3)}
    w, _ = bk._masks(gathered, boxes, wins, has_boxes, has_windows, extent)
    x, y = _rep_xy(gathered, extent)
    return _bounds_stack(w, x, y)


def _make_bounds_kernel(col_names, has_boxes, has_windows, extent):
    """Per-slot block DMA + VPU reductions into an (8, 128) output block
    (the Mosaic minimum tile; lanes 0-4 of row 0 carry the stats)."""
    import jax.experimental.pallas as pl  # noqa: F401  (symmetry with density)

    n = len(col_names)

    def kernel(bids_ref, boxes_ref, wins_ref, *refs):
        cols = {name: refs[k][0] for k, name in enumerate(col_names)}
        out_ref = refs[n]
        w, _ = bk._masks(cols, boxes_ref, wins_ref, has_boxes, has_windows, extent)
        x, y = _rep_xy(cols, extent)
        inf = jnp.float32(jnp.inf)
        vals = (
            w.sum(dtype=jnp.float32),
            jnp.where(w, x, inf).min(),
            jnp.where(w, x, -inf).max(),
            jnp.where(w, y, inf).min(),
            jnp.where(w, y, -inf).max(),
        )
        # Mosaic has no scatter: place the 5 scalars into row 0 via iota
        # selects instead of .at[].set
        row = lax.broadcasted_iota(jnp.int32, (8, bk.LANES), 0)
        lane = lax.broadcasted_iota(jnp.int32, (8, bk.LANES), 1)
        out = jnp.zeros((8, bk.LANES), jnp.float32)
        for j, v in enumerate(vals):
            out = jnp.where((row == 0) & (lane == j), v, out)
        out_ref[0] = out

    return kernel


@partial(
    jax.jit,
    static_argnames=("col_names", "has_boxes", "has_windows", "extent", "interpret"),
)
def _pallas_bounds(cols3, bids, boxes, wins, *, col_names, has_boxes, has_windows, extent, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = bids.shape[0]
    SUB = cols3[0].shape[1]
    kernel = _make_bounds_kernel(col_names, has_boxes, has_windows, extent)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[
            pl.BlockSpec((8, bk.LANES), lambda i, bids: (0, 0)),
            pl.BlockSpec((8, bk.LANES), lambda i, bids: (0, 0)),
        ]
        + [
            pl.BlockSpec((1, SUB, bk.LANES), lambda i, bids: (jnp.maximum(bids[i], 0), 0, 0))
            for _ in col_names
        ],
        out_specs=pl.BlockSpec((1, 8, bk.LANES), lambda i, bids: (i, 0, 0)),
    )
    stats = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, 8, bk.LANES), jnp.float32),
        interpret=interpret,
        name=BOUNDS_NAME,
    )(bids, boxes, wins, *cols3)
    return stats[:, 0, :STAT_LANES]


def reduce_bounds(stats, n_real: int):
    """Host-side fold of [M, STAT_LANES] per-slot stats (possibly
    concatenated across shards) -> (count, (xmin, ymin, xmax, ymax) | None)."""
    import numpy as np

    s = np.asarray(stats)[:n_real] if n_real is not None else np.asarray(stats)
    if len(s) == 0:
        return 0, None
    cnt = int(s[:, 0].sum())
    if cnt == 0:
        return 0, None
    return cnt, (
        float(s[:, 1].min()), float(s[:, 3].min()),
        float(s[:, 2].max()), float(s[:, 4].max()),
    )


# ---------------------------------------------------- tile-pyramid partials
# Host-side exact aggregation for the map-tile tier (geomesa_tpu.tiles;
# docs/tiles.md): counts are integers in f64 (exact to 2^53), and the
# bincount/block-sum pair is how a zoom-z pixel stays bit-identical to a
# from-scratch aggregation of the same rows no matter how the pyramid
# associates its partial sums.


def tile_partial(col, row, w: int, h: int):
    """Windowed density partial of one tile: per-pixel counts of rows
    already binned to LOCAL pixel indices (``0 <= col < w``,
    ``0 <= row < h``, row 0 = north). One ``bincount`` — no scatter
    races, deterministic on any backend."""
    import numpy as np

    flat = np.asarray(row, np.int64) * w + np.asarray(col, np.int64)
    return np.bincount(flat, minlength=h * w).reshape(h, w).astype(np.float64)


def block_sum(grid, k: int):
    """Exact ``k x k`` block-sum downsample of a 2-D f64 grid — the
    pyramid's parent recompose (4 children fold with k=2). Integer
    counts in f64 sum exactly in any association order."""
    import numpy as np

    g = np.asarray(grid, np.float64)
    hh, ww = g.shape
    return g.reshape(hh // k, k, ww // k, k).sum(axis=(1, 3))
