"""Tables / native tier: 100 x candidate blocks over kernel slots, summed
over the ``dispatch`` spans under ``density`` roots: what share of the
density kernel's grid reads a block the tile asked for, the rest being the
padding of its bucket and, past the ladder, the whole-table shape's other
blocks (``blocks`` counts what the kernel was handed as real: past the
ladder every block of the table)."""
from layer_metrics._density import dispatches


def read(view):
    got = [s["attrs"] for s in dispatches(view, "slots")]
    slots = sum(a["slots"] for a in got)
    return 100.0 * sum(a.get("blocks", 0) for a in got) / slots if slots else None
