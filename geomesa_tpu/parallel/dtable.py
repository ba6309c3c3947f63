"""DistributedIndexTable: one index sharded over a device mesh.

Layout: the sorted table's scan blocks are dealt round-robin across the
mesh axis (global block g -> device ``g % D``, local slot ``g // D``).
Round-robin is the ShardStrategy analogue (/root/reference/geomesa-index-
api/src/main/scala/org/locationtech/geomesa/index/api/ShardStrategy.scala:
21-80): consecutive z-runs interleave across chips, so any query's
candidate ranges fan out over the whole mesh instead of hot-spotting one
device.

Execution is the SAME block-bitmask engine as the single-chip table
(scan.block_kernels; the reference runs one push-down tier on every region
server, geomesa-hbase-rpc/.../coprocessor/GeoMesaCoprocessor.scala:28-79):
this class only overrides the device hooks of storage.table.IndexTable —
every device DMAs its own candidate blocks via the scalar-prefetched
Pallas kernel under ``shard_map`` and emits packed wide+inner bit planes
at a mesh-wide static M bucket. All shapes are static per (table, bucket,
predicate flags): zero query-time recompiles (the round-2 cap-retry loop
is gone), all query parameters ride the jit dispatch (no per-call
device_put), and ONE batched pull returns every device's planes, sized in
KB. Aggregations (pops/density/bounds) run the shared kernels per shard
and merge with ``psum`` or a host fold — the coprocessor-aggregation tier
collapsed into XLA collectives over ICI.

Under a caller's spans (docs/observability.md) the hooks mark what the
single-chip hooks mark, under the same names (``prune``, ``enqueue``,
``wait``, ``pull``, ``bits``; ``blocks``, ``slots``, ``groups``), and
name what only a mesh does: the segments ``deal`` (candidates split per
device, the [D, M] arrays filled) and ``merge`` (the devices' rows into
one ascending answer), the counters ``devices``, ``blocks_max`` (the
fullest device's real candidates) and ``splits`` (fused chunks cut in
two by skew). Untraced, each mark is one thread-local probe.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomesa_tpu.index.api import IndexKeySpace, ScanConfig, WriteKeys
from geomesa_tpu.obs.trace import add as _oadd
from geomesa_tpu.obs.trace import event as _oevent
from geomesa_tpu.obs.trace import tracer as _otracer
from geomesa_tpu.scan import aggregations
from geomesa_tpu.scan import block_kernels as bk
from geomesa_tpu.storage.table import FUSED_CHUNK_SLOTS, IndexTable, _await_device


def _count_deal(n_real, slots: int) -> None:
    """One dispatch's deal on the active span: real candidates over all
    devices (``n_real``: one count a device), kernel slots (D x M), the
    fullest device's candidates, D."""
    span = _otracer().current()
    if span is not None:
        span.add("blocks", int(sum(n_real)))
        span.add("slots", int(slots))
        span.add("blocks_max", int(max(n_real)))
        span.annotate(devices=len(n_real))


def _shard_map(body, mesh, in_specs, out_specs):
    """jax.shard_map with replication checking off: the scan bodies index
    shard-local blocks, which the checker cannot see through."""
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


@lru_cache(maxsize=256)
def _dist_scan(mesh, names, has_boxes, has_windows, extent, n_edges=0, n_rints=0):
    """jit(shard_map): per-device block-bitmask scan -> (wide, inner)
    planes [D, M, PACK, 128], sharded along the mesh axis so the host's one
    device_get is the only cross-host movement. ``n_edges`` > 0 runs the
    device point-in-polygon tier, ``n_rints`` > 0 the raster-interval
    tier (edge/raster blocks replicated to every device)."""
    axis = mesh.axis_names[0]

    skip = bk.skip_inner_plane(has_boxes, extent)

    def body(bids, boxes, wins, *rest):
        # with edges/rast, extra replicated args precede the sharded cols
        edges = rast = None
        if n_edges:
            edges, rest = rest[0], rest[1:]
        if n_rints:
            rast, rest = rest[0], rest[1:]
        cols = rest
        w, i = bk.block_scan(
            tuple(c[0] for c in cols), bids[0], boxes, wins,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent, edges=edges, n_edges=n_edges,
            rast=rast, n_rints=n_rints,
        )
        return w[None] if skip else (w[None], i[None])

    in_specs = (
        (P(axis), P(), P())
        + ((P(),) if n_edges else ())
        + ((P(),) if n_rints else ())
        + (P(axis),) * len(names)
    )
    return jax.jit(_shard_map(
        body, mesh, in_specs, P(axis) if skip else (P(axis), P(axis))
    ))


@lru_cache(maxsize=256)
def _dist_scan_multi(mesh, names, has_boxes, has_windows, extent, n_edges=0,
                     n_rints=0):
    """jit(shard_map): the FUSED multi-query scan on every device — one
    mesh-wide dispatch scans each device's [M] slot list (local block
    bids[d, i] under query qids[d, i]'s packed params) and emits
    (wide, inner) planes [D, M, PACK, 128] sharded along the mesh axis,
    so the host's one device_get is the only cross-host movement. The
    param stacks (boxes/wins [Q, 8, 128], optional edges [Q, E, 128] and
    rasters [Q, 1 + R, 128]) are replicated; ``spip`` [D, M] selects the
    polygon leg per slot. This is the mesh shape of bk.block_scan_multi:
    Q dispatches per batch become ONE, preserving the
    zero-recompile-after-warmup property (the compile key is the same
    static (slots, Q, columns, flags, E, R) tuple)."""
    axis = mesh.axis_names[0]

    skip = bk.skip_inner_plane(has_boxes, extent)
    poly_leg = bool(n_edges or n_rints)

    def body(bids, qids, spip, boxes, wins, *rest):
        edges = rasts = None
        if n_edges:
            edges, rest = rest[0], rest[1:]
        if n_rints:
            rasts, rest = rest[0], rest[1:]
        cols = rest
        w, i = bk.block_scan_multi(
            tuple(c[0] for c in cols), bids[0], qids[0], boxes, wins,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent, edges=edges, spip=spip[0] if poly_leg else None,
            n_edges=n_edges, rasts=rasts, n_rints=n_rints,
        )
        return w[None] if skip else (w[None], i[None])

    in_specs = (
        (P(axis), P(axis), P(axis), P(), P())
        + ((P(),) if n_edges else ())
        + ((P(),) if n_rints else ())
        + (P(axis),) * len(names)
    )
    return jax.jit(_shard_map(
        body, mesh, in_specs, P(axis) if skip else (P(axis), P(axis))
    ))


@lru_cache(maxsize=256)
def _dist_pops(mesh, names, has_boxes, has_windows, extent):
    """jit(shard_map): per-device per-block wide popcounts [D, M] i32 —
    count queries pull D*M ints, never planes."""
    axis = mesh.axis_names[0]

    def body(bids, boxes, wins, *cols):
        pops = aggregations.block_pops(
            tuple(c[0] for c in cols), jax.numpy.maximum(bids[0], 0), boxes, wins,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent,
        )
        return pops[None]

    in_specs = (P(axis), P(), P()) + (P(axis),) * len(names)
    return jax.jit(_shard_map(body, mesh, in_specs, P(axis)))


@lru_cache(maxsize=256)
def _dist_density(mesh, names, has_boxes, has_windows, extent, width, height):
    """jit(shard_map): per-device density grid, psum-merged over ICI."""
    axis = mesh.axis_names[0]

    def body(bids, boxes, wins, gb, *cols):
        grid = aggregations.block_density(
            tuple(c[0] for c in cols), bids[0], boxes, wins, gb,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent, width=width, height=height,
        )
        return lax.psum(grid, axis)

    in_specs = (P(axis), P(), P(), P()) + (P(axis),) * len(names)
    return jax.jit(_shard_map(body, mesh, in_specs, P()))


@lru_cache(maxsize=256)
def _dist_bounds(mesh, names, has_boxes, has_windows, extent):
    """jit(shard_map): per-device per-slot bounds stats [D, M, 8]."""
    axis = mesh.axis_names[0]

    def body(bids, boxes, wins, *cols):
        stats = aggregations.block_bounds(
            tuple(c[0] for c in cols), bids[0], boxes, wins,
            col_names=names, has_boxes=has_boxes, has_windows=has_windows,
            extent=extent,
        )
        return stats[None]

    in_specs = (P(axis), P(), P()) + (P(axis),) * len(names)
    return jax.jit(_shard_map(body, mesh, in_specs, P(axis)))


class DistributedIndexTable(IndexTable):
    """Sorted columnar index table sharded over a 1-D mesh. Shares the
    entire scan engine with IndexTable; only the layout and device hooks
    differ."""

    def __init__(
        self,
        keyspace: IndexKeySpace,
        keys: WriteKeys,
        mesh: Mesh,
        tile: int | None = None,
        sorted_state: "np.ndarray | None" = None,
    ):
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        self.axis = mesh.axis_names[0]
        super().__init__(keyspace, keys, tile=tile, sorted_state=sorted_state)

    # -- layout hooks ----------------------------------------------------
    def _round_blocks(self, n_blocks: int) -> int:
        D = self.n_devices
        return -(-n_blocks // D) * D

    def _place_cols(self, cols: dict, device=None) -> None:
        self.rows_uploaded = self.n_pad  # mesh tables always re-deal
        D = self.n_devices
        nb = self.n_blocks
        self.blocks_local = nb // D
        # deal[d, j] = global block j*D + d
        deal = np.arange(nb).reshape(self.blocks_local, D).T
        spec = NamedSharding(self.mesh, P(self.axis))
        self.cols3 = {}
        for k, v in cols.items():
            v4 = v.reshape(nb, self.sub, bk.LANES)[deal]  # [D, nb/D, SUB, L]
            self.cols3[k] = jax.device_put(v4, spec)

    # -- candidate split -------------------------------------------------
    def _split_blocks(self, blocks: np.ndarray, pad: int = 0):
        """Global candidate blocks -> ([D, M] i32 local block ids padded to
        one mesh-wide static bucket, per-device real counts [D]). Past the
        largest bucket every device scans all its local blocks (``full``
        on the caller's span, as ``IndexTable._full_or`` counts it). The
        caller's span gets the segment ``deal`` and the deal's counters;
        what follows is ``prune`` again, as on one chip."""
        _oevent("deal")
        D = self.n_devices
        per = [blocks[blocks % D == d] // D for d in range(D)]
        mx = max(len(p) for p in per)
        full = mx > bk.M_BUCKETS[-1]
        _oadd("full", int(full))
        if full:
            per = [np.arange(self.blocks_local, dtype=np.int64)] * D
            mx = self.blocks_local
        m = bk.bucket_of(mx)
        bids2 = np.full((D, m), pad, np.int32)
        n_real = np.zeros(D, np.int64)
        for d, p in enumerate(per):
            bids2[d, : len(p)] = p
            n_real[d] = len(p)
        _count_deal(n_real, bids2.size)
        _oevent("prune")
        return bids2, n_real

    def _merge_device_rows(self, parts):
        """[(rows, certain)] per device (each ascending) -> globally
        ascending (rows, certain). Segment ``merge`` of the caller's
        ``scan``; what follows (``_post_decode``) is ``bits`` again."""
        _oevent("merge")
        parts = [(r, c) for r, c in parts if len(r)]
        if not parts:
            out = np.zeros(0, np.int64), np.zeros(0, bool)
        else:
            rows = np.concatenate([r for r, _ in parts])
            cert = np.concatenate([c for _, c in parts])
            order = np.argsort(rows, kind="stable")
            out = rows[order], cert[order]
        _oevent("bits")
        return out

    # -- fused multi-query scan (round 6) --------------------------------
    @property
    def fused_slots(self) -> int:
        """PER-DEVICE slot bucket of the canonical fused shape: the
        single-chip clamp applied to the LOCAL block count (each device
        scans its own round-robin share, so a mesh table's fused dispatch
        is D lists of this size, not one global list)."""
        return min(FUSED_CHUNK_SLOTS, bk.bucket_of(max(1, self.blocks_local)))

    @property
    def fused_pack_capacity(self) -> int:
        """Chunk-packer capacity: candidates split round-robin across the
        mesh, so a chunk holds ~D x the per-device slot bucket."""
        return self.fused_slots * self.n_devices

    def _submit_fused_chunk(
        self, members, names, has_boxes, has_windows, finishes, deadline
    ):
        """Mesh fused dispatch (the shard_map shape of IndexTable's
        single-device `_submit_fused_chunk`): ONE `_dist_scan_multi` call
        scans every member's candidate blocks on their owning devices —
        member k's local blocks on device d form one contiguous slot
        segment [d, segs[k][d]] — and ONE batched pull returns every
        device's planes. Members decode lazily per (member, device)
        segment and merge like per-query distributed scans, so fused
        results are bit-identical to `_device_scan_submit` per query."""
        if self._fused_route_single(members, finishes, deadline):
            return
        raw = self._fused_raw_finishes(
            members, names, has_boxes, has_windows, deadline
        )
        if raw is None:
            # candidate skew overflowed one device's static slot bucket
            # (members' blocks clustered on one residue class): split the
            # chunk and recurse — bottoms out at the per-query route
            _oadd("splits", 1)
            half = len(members) // 2
            self._submit_fused_chunk(
                members[:half], names, has_boxes, has_windows, finishes, deadline
            )
            self._submit_fused_chunk(
                members[half:], names, has_boxes, has_windows, finishes, deadline
            )
            return

        def member_finish(k):
            j, config, blocks, spans = members[k]
            rows, certain = raw[k]()
            return self._post_decode(rows, certain, config, spans)

        for k, (j, *_rest) in enumerate(members):
            finishes[j] = lambda k=k: member_finish(k)

    def _fused_raw_finishes(
        self, members, names, has_boxes, has_windows, deadline
    ):
        """The dispatch half of the fused chunk, decoupled from routing:
        submit ONE `_dist_scan_multi` over every member's candidate
        blocks and return one raw finish per member — each yields this
        table's (rows, certain) in SORTED-ROW coordinates, before
        `_post_decode`. Returns None (nothing dispatched) when candidate
        skew overflows the static slot bucket, leaving the split/retry
        policy to the caller. The pod table drives this seam per host
        shard — one batched plane pull per host — and applies the global
        `_post_decode` itself after offsetting shard rows."""
        from geomesa_tpu.planning.errors import check_deadline

        _oevent("deal")
        D = self.n_devices
        slots = self.fused_slots
        # member-major per-device split: global block g -> device g % D,
        # local slot g // D (the round-robin deal, _place_cols)
        per = [
            [m[2][m[2] % D == d] // D for m in members] for d in range(D)
        ]
        counts = [sum(len(p) for p in row) for row in per]
        if max(counts) > slots:
            _oevent("prune")
            return None
        check_deadline(deadline, "device scan dispatch")
        _oevent("prune")  # the parameter stacks, as on one chip
        boxes, wins = self._fused_param_stacks(members)
        chunk_e, edges, pip = self._chunk_edge_stack(members)
        chunk_r, rasts, has_rast = self._chunk_raster_stack(members)
        poly_slot = pip | has_rast
        _oevent("deal")
        bids2 = np.zeros((D, slots), np.int32)
        qids2 = np.zeros((D, slots), np.int32)
        spip2 = np.zeros((D, slots), np.int32)
        segs: list[list] = [[(0, 0)] * D for _ in members]
        for d in range(D):
            pos = 0
            for q, loc in enumerate(per[d]):
                nb = len(loc)
                bids2[d, pos : pos + nb] = loc
                qids2[d, pos : pos + nb] = q
                if (chunk_e or chunk_r) and poly_slot[q]:
                    spip2[d, pos : pos + nb] = 1
                segs[q][d] = (pos, pos + nb)
                pos += nb
        self._record_scan(names, bids2.size)
        _count_deal(counts, bids2.size)
        _oadd("groups", 1)
        fn = _dist_scan_multi(
            self.mesh, names, has_boxes, has_windows, self.extent, chunk_e,
            chunk_r,
        )
        extra = (() if not chunk_e else (edges,)) + (
            () if not chunk_r else (rasts,)
        )
        _oevent("enqueue")
        out = fn(
            bids2, qids2, spip2, boxes, wins, *extra,
            *self._cols_args(names),
        )
        wide, inner = out if isinstance(out, tuple) else (out, None)
        group_pull = self._fused_pull(wide, inner, len(members))
        _oevent("prune")  # back from the enqueue: the next chunk's staging

        def raw_finish(k):
            wide_h, inner_h = group_pull()
            _oevent("bits")
            check_deadline(deadline, "bitmask decode")
            parts = []
            for d in range(D):
                s, e = segs[k][d]
                if e <= s:
                    continue
                gb = bids2[d, s:e].astype(np.int64) * D + d
                parts.append(bk.decode_bits_pair(
                    np.ascontiguousarray(wide_h[d, s:e]),
                    None if inner_h is None else np.ascontiguousarray(inner_h[d, s:e]),
                    gb, e - s,
                ))
            return self._merge_device_rows(parts)

        return [lambda k=k: raw_finish(k) for k in range(len(members))]

    # -- device hooks ----------------------------------------------------
    def _device_scan_submit(self, blocks: np.ndarray, config: ScanConfig):
        D = self.n_devices
        bids2, n_real = self._split_blocks(blocks)
        boxes, wins = self._params(config)
        kw = self._scan_kernel_kwargs(config, self._scan_cols(config))
        names = kw["col_names"]
        n_edges = kw.get("n_edges", 0)
        n_rints = kw.get("n_rints", 0)
        self._record_scan(names, bids2.size)
        fn = _dist_scan(
            self.mesh, names, kw["has_boxes"], kw["has_windows"], kw["extent"],
            n_edges, n_rints,
        )
        skip = bk.skip_inner_plane(kw["has_boxes"], kw["extent"])
        extra = (() if not n_edges else (kw["edges"],)) + (
            () if not n_rints else (kw["rast"],)
        )
        _oevent("enqueue")
        out = fn(bids2, boxes, wins, *extra, *self._cols_args(names))  # dispatched now
        # async device->host copies: see IndexTable._device_scan_submit
        for plane in out if isinstance(out, tuple) else (out,):
            if hasattr(plane, "copy_to_host_async"):
                plane.copy_to_host_async()

        def finish():
            _await_device(out)
            if skip:
                wide_h, inner_h = np.asarray(jax.device_get(out)), None
            else:
                wide_h, inner_h = jax.device_get(out)
                wide_h, inner_h = np.asarray(wide_h), np.asarray(inner_h)
            _oevent("bits")
            parts = []
            for d in range(D):
                nr = int(n_real[d])
                if nr == 0:
                    continue
                gb = bids2[d].astype(np.int64) * D + d  # local slot -> global
                parts.append(
                    bk.decode_bits_pair(
                        wide_h[d], None if inner_h is None else inner_h[d], gb, nr
                    )
                )
            return self._merge_device_rows(parts)

        return finish

    def _device_pops(self, blocks: np.ndarray, config: ScanConfig):
        D = self.n_devices
        bids2, n_real = self._split_blocks(blocks, pad=-1)
        boxes, wins = self._params(config)
        kw = self._kernel_kwargs(config)
        names = kw["col_names"]
        self._record_scan(names, bids2.size)
        fn = _dist_pops(self.mesh, names, kw["has_boxes"], kw["has_windows"], kw["extent"])
        _oevent("enqueue")
        out = fn(bids2, boxes, wins, *self._cols_args(names))
        _await_device(out)
        pops2 = np.asarray(jax.device_get(out))
        pops, gbids = [], []
        for d in range(D):
            nr = int(n_real[d])
            pops.append(pops2[d, :nr].astype(np.int64))
            gbids.append(bids2[d, :nr].astype(np.int64) * D + d)
        pops = np.concatenate(pops)
        gbids = np.concatenate(gbids)
        order = np.argsort(gbids)
        return pops[order], gbids[order]

    def _device_density_submit(self, blocks, config, grid_bounds, width, height):
        bids2, _ = self._split_blocks(blocks, pad=-1)
        boxes, wins = self._params(config)
        names = self._agg_cols(config)
        kw = self._kernel_kwargs(config, names)
        self._record_scan(names, bids2.size)
        fn = _dist_density(
            self.mesh, names, kw["has_boxes"], kw["has_windows"], kw["extent"],
            width, height,
        )
        _oevent("enqueue")
        grid = fn(bids2, boxes, wins, grid_bounds, *self._cols_args(names))
        if hasattr(grid, "copy_to_host_async"):
            grid.copy_to_host_async()

        def finish():
            _await_device(grid)
            return np.asarray(jax.device_get(grid))

        return finish

    def _device_bounds(self, blocks, config):
        bids2, n_real = self._split_blocks(blocks, pad=-1)
        boxes, wins = self._params(config)
        names = self._agg_cols(config)
        kw = self._kernel_kwargs(config, names)
        self._record_scan(names, bids2.size)
        fn = _dist_bounds(self.mesh, names, kw["has_boxes"], kw["has_windows"], kw["extent"])
        _oevent("enqueue")
        out = fn(bids2, boxes, wins, *self._cols_args(names))
        _await_device(out)
        stats = np.asarray(jax.device_get(out))
        # fold only real slots from each device
        parts = [stats[d, : int(n_real[d])] for d in range(self.n_devices)]
        return aggregations.reduce_bounds(np.concatenate(parts), None)
