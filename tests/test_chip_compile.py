"""Ahead-of-time compiles of the main path's kernels for a described v5e.

No chip is attached here: ``topologies.get_topology_desc`` describes a
v5e 2x2 host and ``jit(...).lower(shapes).compile()`` runs the TPU's own
compiler (Mosaic for the Pallas kernels) at real widths — what it refuses
here it refuses on the chip. A compile that passes is not a chip run
(``chip_smoke.py`` is that); these guard the kernels between chip runs.

The topology is described inside a module-scoped fixture, never at
import: only the xdist worker that is handed this file loads libtpu.
Keep every such test in THIS file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from geomesa_tpu.parallel import dtable
from geomesa_tpu.scan import aggregations as agg
from geomesa_tpu.scan import block_kernels as bk
from geomesa_tpu.storage.table import FUSED_CHUNK_Q, FUSED_CHUNK_SLOTS

SUB = bk.BLOCK // bk.LANES  # 128 sublanes: the default 16384-row block
N_BLOCKS = 8192  # 134M rows per column: past the smoke's 100M-row table
M_SMALL, M_LARGE = 256, bk.M_BUCKETS[-1]

Z3 = ("tbin", "toff", "x", "y")
TW = ("tw", "x", "y")
Z2 = ("x", "y")
XZ2 = ("gxmax", "gxmin", "gymax", "gymin")
_I32 = {"tbin", "toff", "tw"}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("shard",))


@pytest.fixture
def as_tpu(monkeypatch):
    """The dispatchers ask ``jax.default_backend()`` (use_pallas, the
    interpret= sites): answer as the chip would, for this test only."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cols(names, sh, lead=()):
    return tuple(
        _s(lead + (N_BLOCKS // (lead[0] if lead else 1), SUB, bk.LANES),
           jnp.int32 if n in _I32 else jnp.float32, sh)
        for n in names
    )


def _params(sh, lead=()):
    return (
        _s(lead + (8, bk.LANES), jnp.float32, sh),
        _s(lead + (8, bk.LANES), jnp.int32, sh),
    )


def _flags(names, has_windows):
    return dict(
        col_names=names, has_boxes=True, has_windows=has_windows,
        extent="gxmin" in names,
    )


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text()


SCAN_CASES = {
    "z3": (Z3, True, 0, 0),
    "packed-time": (TW, True, 0, 0),
    "z2": (Z2, False, 0, 0),
    "xz2-extent": (XZ2, False, 0, 0),
    "pip-e16": (Z3, True, 16, 0),
    "pip-e64": (Z2, False, 64, 0),
    "raster-r16": (Z2, False, 0, 16),
    "raster-r64": (Z3, True, 0, 64),
    "raster-r16-e64": (Z2, False, 64, 16),
}


@pytest.mark.parametrize("m", [M_SMALL, M_LARGE])
@pytest.mark.parametrize("case", sorted(SCAN_CASES))
def test_single_query_scan(one_chip, case, m):
    names, has_w, n_edges, n_rints = SCAN_CASES[case]
    edges = _s((n_edges, bk.LANES), jnp.float32, one_chip) if n_edges else None
    rast = _s((1 + n_rints, bk.LANES), jnp.float32, one_chip) if n_rints else None
    compiled = bk._pallas_block_scan.lower(
        _cols(names, one_chip), _s((m,), jnp.int32, one_chip),
        *_params(one_chip), edges, rast,
        interpret=False, n_edges=n_edges, n_rints=n_rints,
        **_flags(names, has_w),
    ).compile()
    _assert_mosaic(compiled)


FUSED_CASES = {
    "no-polygon": (Z3, True, 0, 0),
    "e16": (Z3, True, 16, 0),
    "e64": (Z2, False, 64, 0),
    "e64-r16": (Z3, True, 64, 16),
    "r16": (Z2, False, 0, 16),
}


@pytest.mark.parametrize("case", sorted(FUSED_CASES))
def test_fused_multi_query_scan(one_chip, case):
    """The canonical fused chunk (storage.table: FUSED_CHUNK_SLOTS slots x
    FUSED_CHUNK_Q queries). The polygon legs are what Mosaic refused
    before PR 21 (a vector select with boolean operands)."""
    names, has_w, n_edges, n_rints = FUSED_CASES[case]
    m, q = FUSED_CHUNK_SLOTS, FUSED_CHUNK_Q
    poly = bool(n_edges or n_rints)
    slot = _s((m,), jnp.int32, one_chip)
    compiled = bk._pallas_block_scan_multi.lower(
        _cols(names, one_chip), slot, slot,
        *_params(one_chip, lead=(q,)),
        _s((q, n_edges, bk.LANES), jnp.float32, one_chip) if n_edges else None,
        slot if poly else None,
        _s((q, 1 + n_rints, bk.LANES), jnp.float32, one_chip) if n_rints else None,
        interpret=False, n_edges=n_edges, n_rints=n_rints,
        **_flags(names, has_w),
    ).compile()
    _assert_mosaic(compiled)


#: (edge bucket, raster bucket) of the fused chunks a join of the cell
#: ``nyc-taxi.zone-join`` packs (PR 41; sql/join.py through
#: ``IndexTable.scan_submit_many``): census blocks ride the point-in-polygon
#: tier with no raster, neighborhoods the raster tier with no edges, a
#: block of over 16 edges the next fused edge bucket; under the device's
#: residue mode one chunk carries both stacks
JOIN_CHUNKS = {"blocks-e16": (16, 0), "blocks-e64": (64, 0), "nbhd-r16": (0, 16),
               "both-e16-r16": (16, 16)}


@pytest.mark.parametrize("case", sorted(JOIN_CHUNKS))
def test_fused_polygon_chunk_of_the_join_cell(one_chip, case):
    """The fused polygon chunk over the join cell's own z2 table: 2^24 rows
    are 1,024 blocks, so the chunk has 1,024 slots (``fused_slots`` clamps
    FUSED_CHUNK_SLOTS to the table's block bucket), FUSED_CHUNK_Q members,
    a per-slot polygon selector and the members' edge and raster stacks."""
    n_edges, n_rints = JOIN_CHUNKS[case]
    m = min(FUSED_CHUNK_SLOTS, bk.bucket_of((1 << 24) // bk.BLOCK))
    assert m == 1024
    slot = _s((m,), jnp.int32, one_chip)
    cols = tuple(_s((m, SUB, bk.LANES), jnp.float32, one_chip) for _ in Z2)
    compiled = bk._pallas_block_scan_multi.lower(
        cols, slot, slot, *_params(one_chip, lead=(FUSED_CHUNK_Q,)),
        _s((FUSED_CHUNK_Q, n_edges, bk.LANES), jnp.float32, one_chip) if n_edges else None,
        slot,
        _s((FUSED_CHUNK_Q, 1 + n_rints, bk.LANES), jnp.float32, one_chip) if n_rints else None,
        interpret=False, n_edges=n_edges, n_rints=n_rints, **_flags(Z2, False),
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("n_edges,n_rints", [(16, 0), (32, 0), (0, 16)])
def test_single_member_polygon_scan_of_the_join_cell(one_chip, n_edges, n_rints):
    """A join's small groups dispatch member by member on the single-query
    ladder: a census block at the 32-block floor with its 16- or 32-edge
    stack, a neighborhood or a borough with its raster."""
    m = bk.M_BUCKETS[0]
    compiled = bk._pallas_block_scan.lower(
        tuple(_s((1024, SUB, bk.LANES), jnp.float32, one_chip) for _ in Z2),
        _s((m,), jnp.int32, one_chip), *_params(one_chip),
        _s((n_edges, bk.LANES), jnp.float32, one_chip) if n_edges else None,
        _s((1 + n_rints, bk.LANES), jnp.float32, one_chip) if n_rints else None,
        interpret=False, n_edges=n_edges, n_rints=n_rints, **_flags(Z2, False),
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("names,has_w", [(Z3, True), (Z2, False)])
def test_pops_and_bounds(one_chip, names, has_w):
    args = (
        _cols(names, one_chip), _s((M_LARGE,), jnp.int32, one_chip),
        *_params(one_chip),
    )
    for fn in (agg._pops_pallas, agg._pallas_bounds):
        _assert_mosaic(
            fn.lower(*args, interpret=False, **_flags(names, has_w)).compile()
        )


@pytest.mark.parametrize("size", [256, 512])
def test_density(one_chip, size):
    ch = agg._density_chunk(size, size, SUB, len(Z3))
    assert ch is not None
    compiled = agg._pallas_density.lower(
        _cols(Z3, one_chip), _s((M_LARGE,), jnp.int32, one_chip),
        *_params(one_chip), _s((4,), jnp.float32, one_chip),
        width=size, height=size, interpret=False, chunk=ch,
        **_flags(Z3, True),
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("m", [bk.M_BUCKETS[0], M_LARGE, N_BLOCKS])
def test_heat_map_tile_over_a_z2_table(one_chip, m):
    """A WMS heat-map tile over a type with a z2 index alone: x and y, no
    window, 256 x 256, from the ladder's first bucket to the whole-table
    shape past it (every one of 8,192 blocks, 2^27 rows)."""
    ch = agg._density_chunk(256, 256, SUB, len(Z2))
    assert ch is not None
    compiled = agg._pallas_density.lower(
        _cols(Z2, one_chip), _s((m,), jnp.int32, one_chip),
        *_params(one_chip), _s((4,), jnp.float32, one_chip),
        width=256, height=256, interpret=False, chunk=ch,
        **_flags(Z2, False),
    ).compile()
    _assert_mosaic(compiled)
    assert bk.bucket_of(bk.M_BUCKETS[-1] + 1) == N_BLOCKS  # what pad_bids hands the kernel


def test_density_1024_takes_the_xla_path():
    assert agg._density_chunk(1024, 1024, SUB, len(Z3)) is None


FOOTPRINT_BLOCKS = 1 << 10  # osm-buildings-1chip: 2^24 footprints, four f32 bbox columns


@pytest.mark.parametrize("m", [m for m in bk.M_BUCKETS if m <= FOOTPRINT_BLOCKS])
def test_extent_scan_over_the_footprints_table(one_chip, m):
    """The XZ2 table of the ``osm-buildings.intersects`` cell (1,024
    blocks of 16,384 bboxes): the extent scan, boxes and no window, at
    every bucket ``IndexTable.warmup`` compiles there. The cell's mix stays
    in the first (at most 10 candidate blocks a request), its warm pass
    reaches the second; the rest are a wider viewport's."""
    cols = tuple(_s((FOOTPRINT_BLOCKS, SUB, bk.LANES), jnp.float32, one_chip) for _ in XZ2)
    compiled = bk._pallas_block_scan.lower(
        cols, _s((m,), jnp.int32, one_chip), *_params(one_chip), None, None,
        interpret=False, n_edges=0, n_rints=0, **_flags(XZ2, False),
    ).compile()
    _assert_mosaic(compiled)


AIS_BLOCKS = 1 << 9  # ais-reports-1chip: 2^23 position reports on z3 (and z2)


def _ais_cols(sh):
    return tuple(_s((AIS_BLOCKS, SUB, bk.LANES), jnp.int32 if n in _I32 else jnp.float32, sh)
                 for n in Z3)


@pytest.mark.parametrize("m", [m for m in bk.M_BUCKETS if m <= AIS_BLOCKS])
def test_tube_scan_over_the_ais_table(one_chip, m):
    """The z3 table of the ``ais.vessel-proximity`` cell (512 blocks of
    16,384 reports) as a ``tube_select`` of 256 slices plans it (PR 46):
    ONE single-query scan with boxes and a window, at every bucket of
    candidate blocks. The tube's 256 boxes are no compile key:
    ``pack_boxes`` always fills the kernel's eight slots (the last the
    union of slices 8 to 256) and the one window covers the whole track,
    so the parameter blocks are the shapes of any box-and-window query."""
    boxes = np.tile(np.array([[-125.0, 32.0, -117.0, 48.0]], np.float32), (256, 1))
    packed = bk.pack_boxes(boxes, None)
    assert packed.shape == (8, bk.LANES) and np.isfinite(packed[:, 0]).all()  # all eight slots
    compiled = bk._pallas_block_scan.lower(
        _ais_cols(one_chip), _s((m,), jnp.int32, one_chip), *_params(one_chip), None, None,
        interpret=False, n_edges=0, n_rints=0, **_flags(Z3, True),
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("members", [16, 32])
def test_fused_knn_round_over_the_ais_table(one_chip, members):
    """A ``knn_many`` round of 16 points (the cell's ``knn-many-16``) or of
    32 (its warm ladder's largest) over that table: the windows of a round
    go through ``submit_many`` into ONE fused chunk of the table's canonical
    shape (512 slots: ``fused_slots`` clamps FUSED_CHUNK_SLOTS to the table's
    block bucket; FUSED_CHUNK_Q queries) however many members fill it."""
    assert members <= FUSED_CHUNK_Q
    m, q = min(FUSED_CHUNK_SLOTS, bk.bucket_of(AIS_BLOCKS)), FUSED_CHUNK_Q
    assert m == 512
    slot = _s((m,), jnp.int32, one_chip)
    compiled = bk._pallas_block_scan_multi.lower(
        _ais_cols(one_chip), slot, slot, *_params(one_chip, lead=(q,)), None, None, None,
        interpret=False, n_edges=0, n_rints=0, **_flags(Z3, True),
    ).compile()
    _assert_mosaic(compiled)


TDRIVE_BLOCKS = 1 << 10  # tdrive-tracks-1chip: 2^24 taxi reports; attr_taxiId holds z3's columns
#: the predicates a filter that names taxis leaves the device (PR 49): a window
#: alone (``track-day``, ``fleet-32-day``, a ``tracks-many-32``'s members: the
#: scan projects the two time columns) or a box and a window (``taxi-box-hour``)
ATTR_SCANS = {"window": (("tbin", "toff"), False), "box-window": (Z3, True)}


def _tdrive_cols(names, sh):
    return tuple(_s((TDRIVE_BLOCKS, SUB, bk.LANES), jnp.int32 if n in _I32 else jnp.float32, sh)
                 for n in names)


@pytest.mark.parametrize("m", [bk.M_BUCKETS[0], 64, TDRIVE_BLOCKS])
@pytest.mark.parametrize("case", sorted(ATTR_SCANS))
def test_attribute_scan_over_the_tdrive_table(one_chip, case, m):
    """The attribute table of the ``tdrive.track-history`` cell (1,024 blocks
    of 16,384 reports sorted by the lexicode of ``taxiId``) as a filter that
    names taxis plans it: ONE single-query scan over the blocks the values'
    row spans touch: a taxi's one to three (the smallest bucket), a list of 32
    (the next), a list of 1,024 (every block). The value itself is no kernel
    argument: the host clips the block-granular hits to the row spans."""
    names, has_boxes = ATTR_SCANS[case]
    compiled = bk._pallas_block_scan.lower(
        _tdrive_cols(names, one_chip), _s((m,), jnp.int32, one_chip), *_params(one_chip),
        None, None, interpret=False, n_edges=0, n_rints=0,
        col_names=names, has_boxes=has_boxes, has_windows=True, extent=False,
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("case", sorted(ATTR_SCANS))
def test_fused_attribute_scan_over_the_tdrive_table(one_chip, case):
    """A ``query_many`` of 32 one-taxi one-day filters (the cell's
    ``tracks-many-32``) over that table: the members go through
    ``submit_many`` into ONE fused chunk of the table's canonical shape
    (1,024 slots, FUSED_CHUNK_Q queries), a window a member and no box; the
    box-and-window chunk is what a batch of ``taxi-box-hour`` filters would
    ride."""
    names, has_boxes = ATTR_SCANS[case]
    m, q = min(FUSED_CHUNK_SLOTS, bk.bucket_of(TDRIVE_BLOCKS)), FUSED_CHUNK_Q
    assert m == 1024
    slot = _s((m,), jnp.int32, one_chip)
    compiled = bk._pallas_block_scan_multi.lower(
        _tdrive_cols(names, one_chip), slot, slot, *_params(one_chip, lead=(q,)),
        None, None, None, interpret=False, n_edges=0, n_rints=0,
        col_names=names, has_boxes=has_boxes, has_windows=True, extent=False,
    ).compile()
    _assert_mosaic(compiled)


# ---- the mesh forms: jit(shard_map) over the described four chips


def _mesh_shardings(mesh):
    return NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())


@pytest.mark.parametrize("n_edges", [0, 16])
def test_dist_scan(mesh4, as_tpu, n_edges):
    sharded, repl = _mesh_shardings(mesh4)
    fn = dtable._dist_scan.__wrapped__(mesh4, Z3, True, True, False, n_edges, 0)
    extra = (_s((n_edges, bk.LANES), jnp.float32, repl),) if n_edges else ()
    compiled = fn.lower(
        _s((4, M_SMALL), jnp.int32, sharded), *_params(repl), *extra,
        *_cols(Z3, sharded, lead=(4,)),
    ).compile()
    _assert_mosaic(compiled)


@pytest.mark.parametrize("n_edges,n_rints", [(0, 0), (64, 0), (64, 16)])
def test_dist_scan_multi(mesh4, as_tpu, n_edges, n_rints):
    sharded, repl = _mesh_shardings(mesh4)
    q = FUSED_CHUNK_Q
    fn = dtable._dist_scan_multi.__wrapped__(
        mesh4, Z3, True, True, False, n_edges, n_rints
    )
    slot = _s((4, FUSED_CHUNK_SLOTS), jnp.int32, sharded)
    extra = ()
    if n_edges:
        extra += (_s((q, n_edges, bk.LANES), jnp.float32, repl),)
    if n_rints:
        extra += (_s((q, 1 + n_rints, bk.LANES), jnp.float32, repl),)
    compiled = fn.lower(
        slot, slot, slot, *_params(repl, lead=(q,)), *extra,
        *_cols(Z3, sharded, lead=(4,)),
    ).compile()
    _assert_mosaic(compiled)


def test_dist_density_psum(mesh4, as_tpu):
    sharded, repl = _mesh_shardings(mesh4)
    fn = dtable._dist_density.__wrapped__(mesh4, Z3, True, True, False, 256, 256)
    compiled = fn.lower(
        _s((4, M_SMALL), jnp.int32, sharded), *_params(repl),
        _s((4,), jnp.float32, repl), *_cols(Z3, sharded, lead=(4,)),
    ).compile()
    _assert_mosaic(compiled)
    assert "all-reduce" in compiled.as_text()
