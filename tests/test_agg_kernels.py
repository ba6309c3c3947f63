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


class TestPallasInterpretParity:
    def test_density(self, cols3):
        cols, bids = _args(cols3)
        gb = np.array([-30, -30, 40, 40], np.float32)
        kw = dict(col_names=NAMES, has_boxes=True, has_windows=True,
                  extent=False, width=96, height=48)
        ref = agg._xla_density(cols, bids, BOXES, WINS, gb, **kw)
        got = agg._pallas_density(
            cols, bids, BOXES, WINS, gb, interpret=True, chunk=SUB, **kw
        )
        assert np.array_equal(np.asarray(ref), np.asarray(got))
        assert np.asarray(ref).sum() > 0

    def test_density_nonaligned_grid(self, cols3):
        cols, bids = _args(cols3)
        gb = np.array([-50, -50, 50, 50], np.float32)
        kw = dict(col_names=NAMES, has_boxes=True, has_windows=False,
                  extent=False, width=33, height=17)
        ref = agg._xla_density(cols, bids, BOXES, WINS, gb, **kw)
        got = agg._pallas_density(
            cols, bids, BOXES, WINS, gb, interpret=True, chunk=SUB, **kw
        )
        assert got.shape == (17, 33)
        assert np.array_equal(np.asarray(ref), np.asarray(got))

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
