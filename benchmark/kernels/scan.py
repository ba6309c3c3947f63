"""Kernel family ``scan`` (the block-scan kernels): how its calls are
recorded and what they have to move and compute.

``install(rec, family)`` wraps the entry points ``kernels/scan.json``
lists. Each call to one becomes a record {kind, t, slots, blocks,
block_rows, n_cols} under ``rec.calls["scan"]`` and a host span
``bench:kernel.<kind>`` in the profiler's trace; ``blocks`` is the
unpadded candidate count, taken from the ``padding`` function's result on
the same thread. Each call to the fused entry point is a record {kind:
"submit_many", members}.

``roofline(calls, peaks)`` returns {"bytes", "flops", "least_s", "bound"}:
the least time the chip could take is the larger of bytes over peak
bytes/s and operations over peak FLOP/s.
"""

from __future__ import annotations

import importlib
import threading
import time

#: the block scan compares each row against a box (4 compares), a window
#: (2) and combines them, for the wide and the inner plane: counted as 16
#: operations a row, generously; the family is bound by bytes either way
SCAN_OPS_PER_ROW = 16


def install(rec, family) -> None:
    calls = rec.calls.setdefault(family["family"], [])
    tls = threading.local()

    def padded(fn):
        def wrapped(blocks, *a, **kw):
            out = fn(blocks, *a, **kw)
            tls.n_real = out[1]
            return out

        return wrapped

    def kernel(kind):
        def make(fn):
            def wrapped(cols3, bids, *a, **kw):
                cols = list(cols3.values()) if isinstance(cols3, dict) else list(cols3)
                shape = cols[0].shape
                calls.append({
                    "kind": kind, "t": time.monotonic(), "slots": len(bids),
                    "blocks": getattr(tls, "n_real", len(bids)),
                    "block_rows": int(shape[-2]) * int(shape[-1]),
                    "n_cols": len(kw.get("col_names", cols)),
                })
                with rec.annotation(f"bench:kernel.{kind}"):
                    return fn(cols3, bids, *a, **kw)

            return wrapped

        return make

    def fused(fn):
        def wrapped(*a, **kw):
            calls.append({"kind": "submit_many", "t": time.monotonic(),
                          "members": len(a[1]) if len(a) > 1 else len(kw.get("configs", ()))})
            return fn(*a, **kw)

        return wrapped

    mod, attr = family["padding"]
    rec.patch(importlib.import_module(mod), attr, padded)
    for mod, attr in family["entry_points"]:
        rec.patch(importlib.import_module(mod), attr, kernel(attr))
    mod, cls, attr = family["fused_entry_point"]
    rec.patch(getattr(importlib.import_module(mod), cls), attr, fused)


def scan_bytes(blocks: int, block_rows: int, n_cols: int, n_planes: int = 2) -> int:
    """Bytes one scan has to stream: every candidate block's ``n_cols``
    4-byte columns once, and ``n_planes`` result bit planes (one bit a
    row each) written once. Padding slots of the M-bucket ladder are the
    program's choice, not the algorithm's need, and are not counted."""
    rows = int(blocks) * int(block_rows)
    return rows * 4 * int(n_cols) + rows * int(n_planes) // 8


def roofline(calls, peaks) -> dict:
    """``calls``: this family's records; those of a kernel call have
    ``blocks`` (candidate blocks, unpadded), ``block_rows``, ``n_cols``."""
    calls = [c for c in calls if "blocks" in c]
    nbytes = sum(scan_bytes(c["blocks"], c["block_rows"], c["n_cols"]) for c in calls)
    flops = sum(c["blocks"] * c["block_rows"] * SCAN_OPS_PER_ROW for c in calls)
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    t_flops = flops / float(peaks["flops_per_s"])
    return {"bytes": nbytes, "flops": flops, "least_s": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
