"""Tables / native tier: of the pairs the window's joins answered, the
share the assembly copied out of a member's array into the answer's
(PR 52: a lone member's rows ARE the answer and none moves; several
members are copied into their slices of arrays allocated once): 100 x
``moved`` over ``pairs``, summed over the ``join.assemble`` spans that
carry ``moved``. None where no such span carries it (a program before
PR 52, which copies every pair and counts none)."""
from layer_metrics._join import children


def read(view):
    got = [s["attrs"] for s in children(view, "join.assemble") if "moved" in s["attrs"]]
    pairs = sum(a["pairs"] for a in got)
    return 100.0 * sum(a["moved"] for a in got) / pairs if pairs else None
