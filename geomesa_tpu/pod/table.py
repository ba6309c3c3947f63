"""PodIndexTable: one index sharded HOST-MAJOR over a host group.

Layout — the block deal is the pod tier's whole argument. The mesh
table (``parallel/dtable.py``) deals blocks round-robin over one flat
device axis, so every query's candidates fan out over every device — the
right call inside one host, where the merge is ICI-cheap. Across hosts
it is exactly wrong: every host touches every query, every host holds
key arrays for the whole table, and ingest re-deals the world. The pod
table instead cuts the globally sorted block sequence into H CONTIGUOUS
runs (the reference's tablet split points, not its in-tablet shards):
host h owns global blocks ``[h*bph, (h+1)*bph)`` and builds ONE per-host
``DistributedIndexTable`` over its own device slice from its slice of
the already-sorted columns (``sorted_state`` identity — no re-sort, and
per-host device memory is ~1/H of the table). A selective query's
candidate blocks then land on FEW hosts; non-owning hosts do zero work.

Execution — the coordinator keeps the global ``SortedKeys`` (ranges,
spans, ``perm``) so planning is bit-identical to the single-process
table, and the device seam routes each candidate-block run to its
owning host's shard: dispatch every owning host first (the per-host
calls are async), then merge on finish. Shard results arrive in
shard-sorted coordinates; adding the host's row base turns them into
global sorted positions, and because cuts are contiguous and ascending
the per-host parts CONCATENATE into globally sorted order — no re-sort
at the coordinator. The fused multi-query path rides the same seam
(``DistributedIndexTable._fused_raw_finishes``): one fused dispatch and
one batched plane pull PER OWNING HOST per chunk, decode at the
coordinator, global ``_post_decode`` — zero XLA recompiles after warmup
and bit-identical results to the flat-mesh table (the differential
tests pin it on both drivers).
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.fault import fault_point
from geomesa_tpu.index.api import IndexKeySpace, ScanConfig, WriteKeys
from geomesa_tpu.parallel.dtable import DistributedIndexTable
from geomesa_tpu.pod.hostgroup import HostGroup
from geomesa_tpu.scan import block_kernels as bk
from geomesa_tpu.storage.table import IndexTable

#: sentinel key values for the rows padding a short host cut (the cut
#: slices sentinel-padded device columns, so only the HOST arrays need
#: explicit pads; values keep the (bin, z) order non-decreasing)
_PAD_BIN = np.int32(np.iinfo(np.int32).max)
_PAD_Z = np.uint64(0xFFFFFFFFFFFFFFFF)


class PodIndexTable(IndexTable):
    """Sorted columnar index cut into per-host contiguous shards, each a
    ``DistributedIndexTable`` on its host's own shard mesh."""

    def __init__(
        self,
        keyspace: IndexKeySpace,
        keys: WriteKeys,
        group: HostGroup,
        tile: int | None = None,
        sorted_state: "np.ndarray | None" = None,
    ):
        self.group = group
        self.hosts = group.hosts
        super().__init__(keyspace, keys, tile=tile, sorted_state=sorted_state)

    # -- layout hooks ----------------------------------------------------
    def _round_blocks(self, n_blocks: int) -> int:
        # multiple of H*dph: the cut is H equal contiguous runs AND each
        # run is a whole number of per-device rounds on its shard mesh,
        # so every global block id (full scans included) maps to a real
        # shard block — the flat-mesh table over the same devices rounds
        # to the same H*dph, which keeps candidate sets identical
        unit = self.hosts * self.group.devices_per_host
        return -(-n_blocks // unit) * unit

    def _place_cols(self, cols: dict, device=None) -> None:
        """Cut the padded sorted columns into H contiguous host runs and
        build one per-host shard table from each — the only device
        placement the pod table does is its shards'."""
        self.rows_uploaded = self.n_pad
        H = self.hosts
        self.blocks_per_host = self.n_blocks // H
        rows_ph = self.blocks_per_host * self.block
        self.rows_per_host = rows_ph
        self.cols3 = {}  # per-host shards own the device arrays
        self._col_bytes = {k: int(v.dtype.itemsize) for k, v in cols.items()}
        self.shards: list[DistributedIndexTable] = []
        for h in range(H):
            r0 = h * rows_ph
            n_h = max(0, min(self.n - r0, rows_ph))  # real rows in the cut
            bins = np.full(rows_ph, _PAD_BIN, np.int32)
            zs = np.full(rows_ph, _PAD_Z, np.uint64)
            bins[:n_h] = self.bins[r0 : r0 + n_h]
            zs[:n_h] = self.zs[r0 : r0 + n_h]
            sub = None
            if self.subkeys is not None:
                sub = np.zeros(
                    (rows_ph, self.subkeys.shape[1]), self.subkeys.dtype
                )
                sub[:n_h] = self.subkeys[r0 : r0 + n_h]
            shard_keys = WriteKeys(
                bins=bins,
                zs=zs,
                # the pod-level pad already wrote never-matching
                # sentinels past row n, so a short cut's tail rows are
                # sentinels by construction
                device_cols={k: v[r0 : r0 + rows_ph] for k, v in cols.items()},
                sub=sub,
            )
            shard = DistributedIndexTable(
                self.keyspace,
                shard_keys,
                self.group.mesh(h),
                tile=self.block,
                # the cut slices the globally sorted columns: identity
                # order, no per-shard re-sort
                sorted_state=np.arange(rows_ph, dtype=np.int64),
            )
            self.shards.append(shard)

    # -- accounting (no coordinator-resident device columns) -------------
    def _record_scan(self, names: tuple, n_blocks: int) -> None:
        self.last_scan_cols = names
        self.last_scan_bytes = sum(
            self._col_bytes[k] for k in names
        ) * n_blocks * self.block

    @property
    def nbytes_device(self) -> int:
        return sum(sh.nbytes_device for sh in self.shards)

    def warmup(self) -> int:
        """Per-shard warmup: the pod table has no kernels of its own —
        every variant it can hit is a shard variant on that host's mesh."""
        return sum(sh.warmup() for sh in self.shards)

    # -- ownership routing -----------------------------------------------
    def _host_blocks(self, blocks: np.ndarray):
        """Ascending global candidate blocks -> [(h, local_blocks)] over
        OWNING hosts only (the contiguous cut makes this two
        searchsorted calls per host; non-owning hosts never appear)."""
        bph = self.blocks_per_host
        out = []
        for h in range(self.hosts):
            s = int(np.searchsorted(blocks, h * bph))
            e = int(np.searchsorted(blocks, (h + 1) * bph))
            if e > s:
                out.append((h, blocks[s:e] - h * bph))
        return out

    def _merge_host_rows(self, parts):
        """[(h, shard_rows, certain)] in ascending host order -> global
        (rows, certain): shard rows + the host's row base are global
        sorted positions, and contiguous ascending cuts concatenate
        already sorted."""
        fault_point("pod.join")
        parts = [
            (h, r, c) for h, r, c in parts if len(r)
        ]
        if not parts:
            return np.zeros(0, np.int64), np.zeros(0, bool)
        rows = np.concatenate([
            r.astype(np.int64) + h * self.rows_per_host for h, r, _ in parts
        ])
        cert = np.concatenate([c for _, _, c in parts])
        return rows, cert

    # -- device hooks ------------------------------------------------------
    def _device_scan_submit(self, blocks: np.ndarray, config: ScanConfig):
        per_host = self._host_blocks(blocks)
        names = self._scan_cols(config)
        self._record_scan(names, int(sum(len(loc) for _, loc in per_host)))
        pending = []
        for h, loc in per_host:
            fault_point("pod.dispatch")
            # dispatch every owning host before finishing any: the
            # shard calls are async, so H hosts scan concurrently
            pending.append((h, self.shards[h]._device_scan_submit(loc, config)))

        def finish():
            return self._merge_host_rows(
                [(h, *fin()) for h, fin in pending]
            )

        return finish

    def _device_pops(self, blocks: np.ndarray, config: ScanConfig):
        per_host = self._host_blocks(blocks)
        pops_parts: list = []
        gbid_parts: list = []
        for h, loc in per_host:
            fault_point("pod.dispatch")
            pops, gbids = self.shards[h]._device_pops(loc, config)
            pops_parts.append(pops)
            gbid_parts.append(gbids + h * self.blocks_per_host)
        fault_point("pod.join")
        if not pops_parts:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        # per-shard results are gbid-sorted; ascending host cuts keep the
        # concatenation globally sorted
        return np.concatenate(pops_parts), np.concatenate(gbid_parts)

    def _device_density_submit(self, blocks, config, grid_bounds, width, height):
        per_host = self._host_blocks(blocks)
        finishes = []
        for h, loc in per_host:
            fault_point("pod.dispatch")
            finishes.append(
                self.shards[h]._device_density_submit(
                    loc, config, grid_bounds, width, height
                )
            )

        def finish():
            fault_point("pod.join")
            grid = np.zeros((height, width), np.float32)
            for fin in finishes:
                grid = grid + fin()
            return grid

        return finish

    def _device_bounds(self, blocks, config):
        per_host = self._host_blocks(blocks)
        total, env = 0, None
        for h, loc in per_host:
            fault_point("pod.dispatch")
            cnt, e = self.shards[h]._device_bounds(loc, config)
            total += cnt
            if e is not None:
                env = e if env is None else (
                    min(env[0], e[0]), min(env[1], e[1]),
                    max(env[2], e[2]), max(env[3], e[3]),
                )
        fault_point("pod.join")
        return total, env

    # -- fused multi-query scan (cross-host leg) -------------------------
    @property
    def fused_slots(self) -> int:
        return min(sh.fused_slots for sh in self.shards)

    @property
    def fused_pack_capacity(self) -> int:
        return sum(sh.fused_pack_capacity for sh in self.shards)

    def _submit_fused_chunk(
        self, members, names, has_boxes, has_windows, finishes, deadline
    ):
        """Cross-host fused dispatch: route each member's candidate
        blocks to owning hosts, pre-check every host's per-device slot
        budget (a skewed chunk splits BEFORE any host dispatches — no
        wasted legs), then drive each owning host's
        ``_fused_raw_finishes`` — one fused kernel call and one batched
        plane pull per host per chunk. Members decode per host at the
        coordinator; the global ``_post_decode`` runs once per member,
        so results stay bit-identical to the flat-mesh fused path."""
        if self._fused_route_single(members, finishes, deadline):
            return
        host_members: dict[int, list] = {}
        for k, m in enumerate(members):
            for h, loc in self._host_blocks(m[2]):
                host_members.setdefault(h, []).append((k, loc))
        for h, mem in host_members.items():
            sh = self.shards[h]
            counts = np.zeros(sh.n_devices, np.int64)
            for _, loc in mem:
                counts += np.bincount(
                    loc % sh.n_devices, minlength=sh.n_devices
                )
            if counts.max() > sh.fused_slots:
                self._split_fused_chunk(
                    members, names, has_boxes, has_windows, finishes, deadline
                )
                return
        host_raw: list = []
        for h in sorted(host_members):
            fault_point("pod.dispatch")
            mem = host_members[h]
            sub_members = [
                (i, members[k][1], loc, None)
                for i, (k, loc) in enumerate(mem)
            ]
            raw = self.shards[h]._fused_raw_finishes(
                sub_members, names, has_boxes, has_windows, deadline
            )
            if raw is None:  # defensive: the pre-check mirrors this test
                self._split_fused_chunk(
                    members, names, has_boxes, has_windows, finishes, deadline
                )
                return
            host_raw.append(
                (h, {k: raw[i] for i, (k, _) in enumerate(mem)})
            )

        def member_finish(k):
            j, config, blocks, spans = members[k]
            parts = []
            for h, raws in host_raw:
                fn = raws.get(k)
                if fn is not None:
                    parts.append((h, *fn()))
            rows, certain = self._merge_host_rows(parts)
            return self._post_decode(rows, certain, config, spans)

        for k, (j, *_rest) in enumerate(members):
            finishes[j] = lambda k=k: member_finish(k)

    def _split_fused_chunk(
        self, members, names, has_boxes, has_windows, finishes, deadline
    ):
        """Half-split recursion on slot overflow (the dtable policy,
        hoisted so the pre-check and the defensive path share it);
        bottoms out at the per-query route."""
        half = len(members) // 2
        self._submit_fused_chunk(
            members[:half], names, has_boxes, has_windows, finishes, deadline
        )
        self._submit_fused_chunk(
            members[half:], names, has_boxes, has_windows, finishes, deadline
        )
