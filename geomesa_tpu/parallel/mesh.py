"""Device mesh helpers for the distributed scan path.

The reference spreads hot ranges over tablet servers with a 1-byte shard
prefix (/root/reference/geomesa-index-api/src/main/scala/org/locationtech/
geomesa/index/api/ShardStrategy.scala:21-80) and fans scans out over
server-side RPC. The TPU equivalent is a 1-D ``jax.sharding.Mesh`` over the
chips of a slice: table tiles are dealt round-robin across the mesh axis so
any z-range's rows land on every device, scans run under ``shard_map``, and
partial results merge with XLA collectives over ICI (psum / all_gather)
instead of coprocessor RPC.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

SHARD_AXIS = "shard"


def make_mesh(n_devices: int | None = None, axis: str = SHARD_AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices."""
    devs = jax.devices()
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(f"asked for {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


def make_multihost_mesh(
    hosts: int | None = None,
    devices_per_host: int | None = None,
    axis: str = SHARD_AXIS,
) -> Mesh:
    """A 1-D scan mesh over a multi-host slice, devices ordered HOST-MAJOR.

    Multi-host layout guidance (SURVEY §2.6 distributed comm backend):
    the scan path needs only a 1-D axis — each device scans its own
    HBM-resident blocks, and the only cross-device traffic is the
    collective merge (psum for aggregations) plus the host pull of each
    device's packed planes. Host-major ordering keeps every contiguous
    ``devices_per_host`` run of the axis inside one host, so the XLA
    collective schedule does its ring/tree phase over ICI within hosts
    and crosses DCN once per host group — the same hierarchy the
    reference gets from per-regionserver aggregation + client-side merge
    (GeoMesaCoprocessor), with DCN in place of the client RPC fan-in.

    Under ``jax.distributed`` each process contributes its local devices
    (jax.devices() is already globally host-major); single-process runs
    (tests, the virtual CPU mesh) reshape the local devices the same way
    so the layout is testable without a pod.
    """
    devs = jax.devices()
    if hosts is None:
        hosts = jax.process_count()
    if devices_per_host is None:
        if len(devs) % hosts:
            raise ValueError(
                f"{len(devs)} devices do not divide over {hosts} hosts"
            )
        devices_per_host = len(devs) // hosts
    return Mesh(
        np.array(_host_major(devs, hosts, devices_per_host)), (axis,)
    )


def host_major_slices(devs, hosts: int, devices_per_host: int) -> list:
    """Per-host device slices, host-major: ``out[h]`` is host h's
    ``devices_per_host`` devices. Devices group by ``process_index``
    (real multi-process pods); single-process runs (tests, the virtual
    CPU mesh) slice the one process's devices into synthetic host
    groups, which preserves the layout semantics without a pod. This is
    the shared layout authority: ``make_multihost_mesh`` concatenates
    the slices into one flat scan axis, and the pod host-group tier
    (geomesa_tpu.pod) builds one PER-HOST shard mesh from each slice —
    both see the same device-to-host assignment."""
    by_host: dict = {}
    for d in devs:
        by_host.setdefault(getattr(d, "process_index", 0), []).append(d)
    if len(by_host) >= hosts > 1:
        out = []
        for h in sorted(by_host)[:hosts]:
            hd = by_host[h]
            if len(hd) < devices_per_host:
                raise ValueError(
                    f"host {h} has {len(hd)} devices, need {devices_per_host}"
                )
            out.append(hd[:devices_per_host])
        return out
    n = hosts * devices_per_host
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return [
        list(devs[h * devices_per_host : (h + 1) * devices_per_host])
        for h in range(hosts)
    ]


def _host_major(devs, hosts: int, devices_per_host: int) -> list:
    """Flat host-major device order (see ``host_major_slices``)."""
    return [d for hd in host_major_slices(devs, hosts, devices_per_host) for d in hd]


def shard_spec(mesh: Mesh) -> NamedSharding:
    """Sharding for [D, ...] arrays split along the mesh axis."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
