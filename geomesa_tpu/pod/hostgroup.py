"""Host groups: which devices belong to which host, behind two drivers.

A :class:`HostGroup` is the pod tier's layout authority — H hosts, each
contributing a fixed slice of devices, every slice backing one per-host
1-D shard mesh (the same ``parallel.mesh.host_major_slices`` order the
flat ``make_multihost_mesh`` axis uses, so the two views agree on which
host owns which device). Two interchangeable drivers produce the
slices:

- ``distributed`` — a real ``jax.distributed`` multi-process world: one
  host per process, each process's local devices form its slice. Only
  available when the backend supports multi-process collectives;
  :func:`probe_capability` shells out to
  ``scripts/probe_multiprocess.py --json`` for the machine-readable
  supported/UNSUPPORTED verdict, and :func:`make_host_group` raises
  :class:`PodUnsupported` (tests skip, not fail) when the verdict says
  no or the process wasn't launched under ``jax.distributed``.
- ``sim`` — deterministic in-process simulation: the one process's
  devices (the ``--xla_force_host_platform_device_count`` virtual CPU
  mesh on CI) slice host-major into H synthetic hosts. Every pod code
  path — per-host shard builds, cross-host fused dispatch, per-host
  WAL/standing shards — runs identically, so the full matrix pins on
  the CPU CI host.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from geomesa_tpu import conf
from geomesa_tpu.parallel.mesh import SHARD_AXIS, host_major_slices


class PodUnsupported(RuntimeError):
    """The requested host-group driver cannot run in this environment
    (carries the capability-probe reason); tests skip on it, not fail."""


#: memoized capability verdict — the probe spawns two jax.distributed
#: worker processes (~seconds), so one verdict serves the whole process
_PROBE_MEMO: dict = {}


def _probe_script() -> str:
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "scripts",
        "probe_multiprocess.py",
    )


def probe_capability(refresh: bool = False) -> dict:
    """The machine-readable multi-process collective verdict:
    ``{"supported": bool, "verdict": "supported"|"UNSUPPORTED"|"error",
    "reason": str}`` from ``scripts/probe_multiprocess.py --json``
    (memoized — the probe launches real subprocesses). The distributed
    driver keys off ``supported``; tests key off ``verdict`` to skip on
    UNSUPPORTED backends instead of failing."""
    if not refresh and "verdict" in _PROBE_MEMO:
        return _PROBE_MEMO["verdict"]
    script = _probe_script()
    if not os.path.exists(script):
        v = {"supported": False, "verdict": "error",
             "reason": f"probe script missing: {script}"}
    else:
        try:
            out = subprocess.run(
                [sys.executable, script, "--json"],
                capture_output=True, text=True, timeout=240,
            )
            lines = [
                ln for ln in out.stdout.splitlines() if ln.strip().startswith("{")
            ]
            v = (
                json.loads(lines[-1])
                if lines
                else {"supported": False, "verdict": "error",
                      "reason": f"no verdict line (rc={out.returncode})"}
            )
        except Exception as e:
            v = {"supported": False, "verdict": "error",
                 "reason": f"probe run failed: {e}"}
    _PROBE_MEMO["verdict"] = v
    return v


class HostGroup:
    """H hosts and their device slices; per-host shard meshes on demand.

    Construct through :func:`make_host_group` (driver/knob resolution)
    — the constructor itself only records a settled layout.
    """

    def __init__(self, driver: str, slices: list):
        if not slices or not slices[0]:
            raise ValueError("a host group needs >= 1 host with >= 1 device")
        widths = {len(s) for s in slices}
        if len(widths) != 1:
            raise ValueError(f"ragged host slices: {sorted(widths)}")
        self.driver = driver
        self.hosts = len(slices)
        self.devices_per_host = len(slices[0])
        self.device_slices = tuple(tuple(s) for s in slices)
        self._meshes: dict = {}
        self._flat_mesh = None

    # -- meshes ----------------------------------------------------------
    def mesh(self, h: int):
        """Host h's 1-D shard mesh over its own device slice (cached):
        the mesh each per-host ``DistributedIndexTable`` shard runs on."""
        from jax.sharding import Mesh

        if h not in self._meshes:
            self._meshes[h] = Mesh(
                np.array(self.device_slices[h]), (SHARD_AXIS,)
            )
        return self._meshes[h]

    def flat_mesh(self):
        """ONE host-major mesh over every device in the group — the
        single-process `DistributedIndexTable` view of the same devices
        (the differential baseline the pod table pins bit-identity
        against)."""
        from jax.sharding import Mesh

        if self._flat_mesh is None:
            flat = [d for s in self.device_slices for d in s]
            self._flat_mesh = Mesh(np.array(flat), (SHARD_AXIS,))
        return self._flat_mesh

    def __repr__(self):  # pragma: no cover - debugging aid
        return (
            f"HostGroup(driver={self.driver!r}, hosts={self.hosts}, "
            f"devices_per_host={self.devices_per_host})"
        )


def make_host_group(
    hosts: "int | None" = None,
    devices_per_host: "int | None" = None,
    driver: "str | None" = None,
) -> HostGroup:
    """Resolve a host group from arguments and the ``geomesa.pod.*``
    knobs. ``driver`` is ``"distributed"``, ``"sim"`` or ``"auto"``
    (default: the ``geomesa.pod.driver`` knob): auto picks distributed
    only when this process is part of a multi-process jax world.
    Raises :class:`PodUnsupported` when the distributed driver is
    requested but cannot run here — callers (tests) skip on it."""
    import jax

    driver = (driver or conf.POD_DRIVER.get() or "auto").lower()
    if driver not in ("auto", "sim", "distributed"):
        raise ValueError(f"unknown pod driver {driver!r}")
    procs = jax.process_count()
    if driver == "auto":
        driver = "distributed" if procs > 1 else "sim"

    if driver == "distributed":
        if procs <= 1:
            verdict = probe_capability()
            if verdict.get("supported"):
                raise PodUnsupported(
                    "backend supports multi-process collectives but this "
                    "process was not launched under jax.distributed "
                    "(launch one process per host, then driver=distributed)"
                )
            raise PodUnsupported(
                f"multi-process collectives unavailable: "
                f"{verdict.get('reason', 'probe verdict missing')}"
            )
        hosts = int(hosts or conf.POD_HOSTS.get() or procs)
        if hosts != procs:
            raise ValueError(
                f"distributed driver: hosts={hosts} != process_count={procs}"
            )
        local = jax.local_devices()
        dph = int(devices_per_host or conf.POD_DEVICES_PER_HOST.get() or len(local))
        slices = host_major_slices(jax.devices(), hosts, dph)
    else:
        devs = jax.devices()
        hosts = int(hosts or conf.POD_HOSTS.get() or 0)
        if hosts <= 0:
            raise ValueError(
                "sim driver needs an explicit host count "
                "(hosts= or the geomesa.pod.hosts knob)"
            )
        dph = int(devices_per_host or conf.POD_DEVICES_PER_HOST.get() or 0)
        if dph <= 0:
            if len(devs) < hosts:
                raise PodUnsupported(
                    f"sim driver: {len(devs)} devices cannot back "
                    f"{hosts} one-device hosts"
                )
            dph = len(devs) // hosts
        slices = host_major_slices(devs, hosts, dph)

    return HostGroup(driver, slices)
