"""Aggregation kernels: XLA vs Pallas-interpret parity + no-predicate masks.

The Pallas TPU path cannot compile on CPU, but interpret mode runs the
same kernel logic (including the MXU one-hot matmul histogram and the
per-slot bounds blocks); parity with the XLA block-gather implementations
pins the contract.
"""

import numpy as np
import pytest

import jax

from geomesa_tpu.scan import aggregations as agg
from geomesa_tpu.scan import block_kernels as bk

SUB = 32  # 4096-row blocks
NB = 8
N = NB * SUB * bk.LANES


@pytest.fixture(scope="module")
def cols3():
    rng = np.random.default_rng(5)
    x = rng.uniform(-50, 50, N).astype(np.float32)
    y = rng.uniform(-50, 50, N).astype(np.float32)
    tb = rng.integers(100, 104, N).astype(np.int32)
    to = rng.integers(0, 1000, N).astype(np.int32)
    # sentinel-pad the tail like a real table
    x[-500:] = np.inf
    y[-500:] = np.inf
    tb[-500:] = -1
    shape = (NB, SUB, bk.LANES)
    return {
        "tbin": jax.numpy.asarray(tb.reshape(shape)),
        "toff": jax.numpy.asarray(to.reshape(shape)),
        "x": jax.numpy.asarray(x.reshape(shape)),
        "y": jax.numpy.asarray(y.reshape(shape)),
    }


NAMES = ("tbin", "toff", "x", "y")
BOXES = bk.pack_boxes(np.array([[-20.0, -15.0, 25.0, 30.0]]), None)
WINS = bk.pack_windows(np.array([[101, 102, 0, 700]]), None)


def _args(cols3):
    bids, _ = bk.pad_bids(np.array([0, 2, 3, 5, 7]), NB, pad=-1)
    return tuple(cols3[k] for k in NAMES), bids


def _density_pair(cols, bids, boxes, wins, gb, **kw):
    """(XLA twin's grid, Pallas kernel's grid, its i32[3] path counts)."""
    ref = agg._xla_density(cols, bids, boxes, wins, gb, **kw)
    got, paths = agg._pallas_density(
        cols, bids, boxes, wins, gb, interpret=True, chunk=SUB, **kw
    )
    return np.asarray(ref), np.asarray(got), [int(v) for v in np.asarray(paths)]


HB = agg._DENSITY_WINDOW_ROWS
G = 256  # the heat-map tile's grid; the envelope below makes a pixel one unit
# blocks of rows by the pixel ranges they fill, (x0, x1, y0, y1), and the path
# the kernel has to take for each on a 256 x 256 grid
PLACED = {
    "inside one window": ((10, 100, 41, 41 + HB - 1), "windowed"),
    "straddles column 127|128": ((120, 136, 8, 16), "whole"),
    "taller than the window": ((0, 100, 16, 16 + HB + 1), "whole"),
    "the grid's last rows": ((130, 250, G - 5, G), "windowed"),
    "two lane tiles, one row": ((0, G, 77, 78), "whole"),
    "outside the envelope": ((300, 400, 10, 20), "skipped"),
    "second lane tile": ((128, G, 0, HB), "windowed"),
    "crosses a window's end from an aligned start": ((200, 210, 96, 96 + HB + 7), "whole"),
}


@pytest.fixture(scope="module")
def placed():
    """One 4096-row block a PLACED entry, x and y uniform in its ranges."""
    rng = np.random.default_rng(17)
    shape = (len(PLACED), SUB, bk.LANES)
    x = np.empty(shape, np.float32)
    y = np.empty(shape, np.float32)
    for k, ((x0, x1, y0, y1), _) in enumerate(PLACED.values()):
        x[k] = rng.uniform(x0, x1, shape[1:])
        y[k] = rng.uniform(y0, y1, shape[1:])
    return jax.numpy.asarray(x), jax.numpy.asarray(y)


PLACED_KW = dict(col_names=("x", "y"), has_boxes=False, has_windows=False,
                 extent=False, width=G, height=G)
PLACED_GB = np.array([0, 0, G, G], np.float32)


class TestPallasInterpretParity:
    @pytest.mark.parametrize("width,height,has_windows", [(96, 48, True), (33, 17, False)])
    def test_density(self, cols3, width, height, has_windows):
        """Blocks spread over the whole envelope, grids smaller than a
        window: (96, 48) pads to one lane tile and more rows than a window,
        (33, 17) to less than one window, which then IS the grid."""
        cols, bids = _args(cols3)
        gb = np.array([-30, -30, 40, 40] if has_windows else [-50, -50, 50, 50], np.float32)
        kw = dict(col_names=NAMES, has_boxes=True, has_windows=has_windows,
                  extent=False, width=width, height=height)
        ref, got, paths = _density_pair(cols, bids, BOXES, WINS, gb, **kw)
        assert got.shape == (height, width)
        assert np.array_equal(ref, got) and ref.sum() > 0
        assert paths == [len(bids) - 5, 0, 5]  # the pads; five blocks all over the grid

    @pytest.mark.parametrize("name", list(PLACED))
    def test_density_of_a_block_by_the_pixels_its_rows_touch(self, placed, name):
        k = list(PLACED).index(name)
        bids = np.array([k, -1], np.int32)
        ref, got, paths = _density_pair(placed, bids, BOXES, WINS, PLACED_GB, **PLACED_KW)
        assert np.array_equal(ref, got)
        path = PLACED[name][1]
        assert ref.sum() == (0 if path == "skipped" else SUB * bk.LANES)
        assert paths == [1 + (path == "skipped"), int(path == "windowed"), int(path == "whole")]

    def test_density_paths_count_every_slot_once(self, placed):
        """All placed blocks in one call, twice over and in a shuffled
        order, into a padded bucket: the windows land on one resident grid,
        and the three counts sum to the slots, ``skipped`` the pads and the
        blocks with no row in the envelope."""
        order = np.random.default_rng(3).permutation(np.tile(np.arange(len(PLACED)), 2))
        bids, n_real = bk.pad_bids(order, len(PLACED), pad=-1)
        ref, got, paths = _density_pair(placed, bids, BOXES, WINS, PLACED_GB, **PLACED_KW)
        assert np.array_equal(ref, got) and ref.sum() == 14 * SUB * bk.LANES
        want = [p for _, p in PLACED.values()]
        assert sum(paths) == len(bids) == 32
        assert paths == [len(bids) - n_real + 2 * want.count("skipped"),
                         2 * want.count("windowed"), 2 * want.count("whole")]

    def test_density_of_pads_alone_is_an_empty_grid(self, placed):
        bids = np.full(32, -1, np.int32)
        ref, got, paths = _density_pair(placed, bids, BOXES, WINS, PLACED_GB, **PLACED_KW)
        assert np.array_equal(ref, got) and got.sum() == 0 and paths == [32, 0, 0]

    def test_density_under_a_box_keeps_the_rows_the_box_keeps(self, placed):
        """The extent is the MASKED rows': a box that cuts a tall block down
        to a window's rows moves it from the whole grid to the window."""
        k = list(PLACED).index("taller than the window")
        bids = np.array([k, -1], np.int32)
        boxes = bk.pack_boxes(np.array([[0.0, 16.0, 256.0, 16.0 + HB - 0.5]]), None)
        kw = PLACED_KW | dict(has_boxes=True)
        ref, got, paths = _density_pair(placed, bids, boxes, WINS, PLACED_GB, **kw)
        assert np.array_equal(ref, got) and 0 < ref.sum() < SUB * bk.LANES
        assert paths == [1, 1, 0]

    def test_density_of_extents_bins_their_centroids(self, placed):
        """``extent=True`` (xz2 / xz3 tables): boxes of a few pixels round
        the placed points, binned by centroid, through the same paths."""
        x, y = placed
        rng = np.random.default_rng(23)
        dx = jax.numpy.asarray(rng.uniform(0, 2, x.shape).astype(np.float32))
        dy = jax.numpy.asarray(rng.uniform(0, 2, x.shape).astype(np.float32))
        names = ("gxmax", "gxmin", "gymax", "gymin")
        cols = (x + dx, x - dx, y + dy, y - dy)
        bids, _ = bk.pad_bids(np.arange(len(PLACED)), len(PLACED), pad=-1)
        kw = PLACED_KW | dict(col_names=names, extent=True, has_boxes=True)
        boxes = bk.pack_boxes(np.array([[-10.0, -10.0, 500.0, 500.0]]), None)
        ref, got, paths = _density_pair(cols, bids, boxes, WINS, PLACED_GB, **kw)
        assert np.array_equal(ref, got) and ref.sum() > 6 * SUB * bk.LANES
        assert sum(paths) == len(bids) and paths[1] >= 2 and paths[2] >= 3

    def test_block_density_hands_the_counts_over_only_when_asked(self, placed):
        from geomesa_tpu import conf

        bids = np.array([0, 1, 5, -1], np.int32)
        args = (placed, bids, BOXES, WINS, PLACED_GB)
        grid = agg.block_density(*args, **PLACED_KW)  # the XLA twin on a CPU
        both = agg.block_density(*args, counts=True, **PLACED_KW)
        assert both[1] is None and np.array_equal(np.asarray(grid), np.asarray(both[0]))
        conf.PALLAS_MODE.set("1")
        try:
            alone = agg.block_density(*args, **PLACED_KW)
            with_counts, paths = agg.block_density(*args, counts=True, **PLACED_KW)
        finally:
            conf.PALLAS_MODE.clear()
        assert np.array_equal(np.asarray(alone), np.asarray(grid))
        assert np.array_equal(np.asarray(with_counts), np.asarray(grid))
        assert [int(v) for v in np.asarray(paths)] == [2, 1, 1]

    def test_bounds(self, cols3):
        cols, bids = _args(cols3)
        kw = dict(col_names=NAMES, has_boxes=True, has_windows=True, extent=False)
        ref = np.asarray(agg._xla_bounds(cols, bids, BOXES, WINS, **kw))
        got = np.asarray(agg._pallas_bounds(cols, bids, BOXES, WINS, interpret=True, **kw))
        assert np.allclose(ref, got)
        cnt, env = agg.reduce_bounds(got, 5)
        assert cnt > 0 and env is not None

    def test_scan_planes(self, cols3):
        cols, _ = _args(cols3)
        bids, _ = bk.pad_bids(np.array([1, 4, 6]), NB)
        kw = dict(col_names=NAMES, has_boxes=True, has_windows=True, extent=False)
        w_ref, i_ref = bk._xla_block_scan(cols, bids, BOXES, WINS, **kw)
        w_got, i_got = bk._pallas_block_scan(cols, bids, BOXES, WINS, interpret=True, **kw)
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_got))
        assert np.array_equal(np.asarray(i_ref), np.asarray(i_got))


class TestNoPredicateMask:
    def test_validity_mask_excludes_sentinels(self, cols3):
        cols, bids = _args(cols3)
        kw = dict(col_names=NAMES, has_boxes=False, has_windows=False, extent=False)
        stats = np.asarray(agg._xla_bounds(cols, bids, BOXES, WINS, **kw))
        cnt, env = agg.reduce_bounds(stats, 5)
        # block 7 holds the 500 sentinel rows: they must not count and must
        # not blow the envelope to +/-inf
        assert cnt > 0
        assert np.isfinite(env).all()

    def test_include_density_api(self):
        from geomesa_tpu import DataStore, FeatureCollection, FeatureType

        rng = np.random.default_rng(6)
        n = 3000
        sft = FeatureType.from_spec("d", "dtg:Date,*geom:Point:srid=4326")
        ds = DataStore()
        ds.create_schema(sft)
        t0 = np.datetime64("2024-01-01", "ms").astype(np.int64)
        fc = FeatureCollection.from_columns(
            sft, np.arange(n),
            {"dtg": t0 + rng.integers(0, 86400_000, n),
             "geom": (rng.uniform(-10, 10, n), rng.uniform(-10, 10, n))},
        )
        ds.write("d", fc, check_ids=False)
        grid = ds.density("d", envelope=(-10, -10, 10, 10), width=16, height=16)
        assert grid.sum() == n
