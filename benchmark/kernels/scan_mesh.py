"""Kernel family ``scan_mesh``: the block-scan kernels of ``kernels/scan.py``
under ``jit(shard_map)`` on a mesh store (``parallel/dtable.py``). No new
kernel: every device runs the same block scan over its own quarter of the
candidate blocks. Under ``shard_map`` ``bk.block_scan`` is a Python call at
trace time only, so the ``scan`` family's wrappers record nothing true on a
mesh; this family records the mesh table's own two scan dispatches instead.

``install(rec, family)`` wraps the methods ``kernels/scan_mesh.json`` lists.
Each ``_device_scan_submit`` and each ``_fused_raw_finishes`` that
dispatched becomes a record {kind, t, devices, slots, blocks, block_rows,
n_cols} under ``rec.calls["scan_mesh"]`` and a host span
``bench:kernel.<kind>``. ``blocks`` is the real candidates over ALL devices
(the per-query count from ``_split_blocks`` on the same thread: past the
largest bucket every device scans all it holds), ``slots`` D x M. On a
store that is no mesh none of the methods is ever called.

``roofline(calls, peaks)`` is ``kernels/scan.py``'s arithmetic over those
records. ``least_s`` is PER CHIP: all devices' bytes and operations at one
chip's peaks, over the number of devices, because the device time it is
divided by (``harness/xplane.py:reduce``'s ``ops``) is a name's time summed
over the device planes over their number. (All devices' bytes at one chip's
peak against the time summed over the planes is the same quotient.)
"""

from __future__ import annotations

import importlib
import threading
import time

from kernels import scan


def install(rec, family) -> None:
    calls = rec.calls.setdefault(family["family"], [])
    tls = threading.local()

    def dealt(fn):
        def wrapped(self, blocks, *a, **kw):
            out = fn(self, blocks, *a, **kw)
            tls.dealt = (int(out[1].sum()), int(out[0].size))
            return out

        return wrapped

    def record(self, kind, t, blocks, slots, names):
        calls.append({"kind": kind, "t": t, "devices": int(self.n_devices), "slots": slots,
                      "blocks": blocks, "block_rows": int(self.block), "n_cols": len(names)})

    def per_query(fn):
        def wrapped(self, blocks, config, *a, **kw):
            t, tls.dealt = time.monotonic(), None
            with rec.annotation("bench:kernel.mesh_scan"):
                out = fn(self, blocks, config, *a, **kw)
            if tls.dealt is not None:
                record(self, "mesh_scan", t, *tls.dealt, self.last_scan_cols)
            return out

        return wrapped

    def fused(fn):
        def wrapped(self, members, names, *a, **kw):
            t = time.monotonic()
            with rec.annotation("bench:kernel.mesh_scan_multi"):
                out = fn(self, members, names, *a, **kw)
            if out is not None:  # None: skew overflowed a bucket, nothing dispatched
                record(self, "mesh_scan_multi", t, sum(len(m[2]) for m in members),
                       int(self.fused_slots) * int(self.n_devices), names)
            return out

        return wrapped

    mod, cls, attr = family["deal"]
    rec.patch(getattr(importlib.import_module(mod), cls), attr, dealt)
    for (mod, cls, attr), make in zip(family["entry_points"], (per_query, fused)):
        rec.patch(getattr(importlib.import_module(mod), cls), attr, make)


def roofline(calls, peaks) -> dict:
    """``calls``: this family's records. "bytes", "flops" and "bound" over
    all devices; "least_s" per chip (see the module's docstring)."""
    whole = scan.roofline(calls, peaks)
    least = sum(scan.roofline([c], peaks)["least_s"] / c["devices"] for c in calls)
    return dict(whole, least_s=least)
