"""Kernels: of the density kernel's slots that held a row of the tile, the
share it contracted over a window of the grid and not over all of it:
100 x ``windowed`` over ``windowed`` + ``whole``, summed over the ``agg``
spans under ``density`` roots (PR 34: the kernel counts its slots by path,
``skipped`` being the pads and the blocks with no row inside box and
envelope). A program whose ``agg`` spans count neither (before PR 34; the
XLA twin; a mesh store) gives None."""
from layer_metrics._segments import spans


def read(view):
    got = [s["attrs"] for s in spans(view, "agg", roots=("density",))
           if "windowed" in s["attrs"] and "whole" in s["attrs"]]
    worked = sum(a["windowed"] + a["whole"] for a in got)
    return 100.0 * sum(a["windowed"] for a in got) / worked if worked else None
