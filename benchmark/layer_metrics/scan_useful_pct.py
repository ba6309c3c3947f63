"""Kernels: 100 x candidate blocks over kernel slots, summed over the
``dispatch`` spans that count them (any root): what share of a kernel's
grid scans a block some query asked for, the rest being the padding of
its bucket."""
from layer_metrics._segments import spans


def read(view):
    blocks = slots = 0
    for s in spans(view, "dispatch"):
        if "slots" in s["attrs"]:
            blocks += s["attrs"].get("blocks", 0)
            slots += s["attrs"]["slots"]
    return 100.0 * blocks / slots if slots else None
