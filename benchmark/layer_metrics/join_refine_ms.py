"""Tables / native tier: per ``join`` root the summed wall of its
``join.refine`` spans (the uncertain rows' exact host check, a member) and
its ``join.assemble`` (the members' sorts and the concatenation of the
pairs); the median over the window's roots, milliseconds."""
from layer_metrics._join import per_root_ms


def read(view):
    return per_root_ms(view, "join.refine", "join.assemble")
