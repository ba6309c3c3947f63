"""Kernel family ``density`` (the heat-map histogram, ``geomesa_density``):
how its calls are recorded and what the question they answer has to move
and compute.

``install(rec, family)`` wraps the entry point ``kernels/density.json``
lists. Each call becomes a record {kind, t, slots, blocks, block_rows,
n_cols, width, height} under ``rec.calls["density"]`` and a host span
``bench:kernel.block_density`` in the profiler's trace; ``blocks`` is the
unpadded candidate count, taken from the ``padding`` function's result on
the same thread (``kernels/scan.py`` wraps the same function for its own
family: each keeps its own thread-local, and neither changes the result).
On a mesh store ``block_density`` is a Python call at trace time only (it
runs under ``jit(shard_map)``): a record there says nothing true, and the
family's share is listed for one-chip cells alone.

``roofline(calls, peaks)`` reads the work the QUESTION needs, whatever
implements it: every candidate row's columns streamed once and the grid
written once; a row compared against the box and the envelope, two pixel
coordinates and one add, counted as 16 operations (``kernels/scan.py``'s
convention). So the family is bound by bytes. The program's kernel builds
one-hot planes and contracts them on the MXU, 2 x width x height
operations a row: its choice, not the question's need, and not counted.
"""

from __future__ import annotations

import importlib
import threading
import time

DENSITY_OPS_PER_ROW = 16


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
                    "width": int(kw.get("width", 0)), "height": int(kw.get("height", 0)),
                })
                with rec.annotation(f"bench:kernel.{kind}"):
                    return fn(cols3, bids, *a, **kw)

            return wrapped

        return make

    mod, attr = family["padding"]
    rec.patch(importlib.import_module(mod), attr, padded)
    for mod, attr in family["entry_points"]:
        rec.patch(importlib.import_module(mod), attr, kernel(attr))


def density_bytes(blocks: int, block_rows: int, n_cols: int, width: int, height: int) -> int:
    """Bytes one heat map has to move: every candidate block's ``n_cols``
    4-byte columns once, and the f32 grid written once. Padding slots and
    the whole-table shape past the bucket ladder are the program's choice
    and are not counted: ``blocks`` is what the padding function was handed
    (past the ladder that IS every block, and the kernel then reads them)."""
    return int(blocks) * int(block_rows) * 4 * int(n_cols) + int(width) * int(height) * 4


def roofline(calls, peaks) -> dict:
    """``calls``: this family's records. {"bytes", "flops", "rows",
    "least_s", "bound"}."""
    calls = [c for c in calls if "blocks" in c]
    rows = sum(c["blocks"] * c["block_rows"] for c in calls)
    nbytes = sum(density_bytes(c["blocks"], c["block_rows"], c["n_cols"], c["width"],
                               c["height"]) for c in calls)
    flops = rows * DENSITY_OPS_PER_ROW
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    t_flops = flops / float(peaks["flops_per_s"])
    return {"bytes": nbytes, "flops": flops, "rows": rows, "least_s": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops"}
