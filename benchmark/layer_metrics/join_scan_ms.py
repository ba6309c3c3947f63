"""Tables / native tier: per ``join`` root the summed wall of the table's
own spans under it, ``dispatch`` (candidate spans and blocks, the stacks,
the fused call) and every member's ``scan`` (wait, pull, decode of the
bits); the median over the window's roots, milliseconds. A root whose
members all took the host's route holds a ``dispatch`` of no member."""
from layer_metrics._join import per_root_ms


def read(view):
    return per_root_ms(view, "dispatch", "scan")
