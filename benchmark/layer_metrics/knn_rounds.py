"""Planner: windows planned and scanned a query point, pooled over the
window's ``knn`` roots (``windows`` over ``members``): 1.0 where every start
radius held ``k`` rows at the first round; each miss is another plan, another
fused dispatch and another gather at sixteen times the radius."""
from layer_metrics._process import pooled


def read(view):
    return pooled(view, "knn", "windows", "members")
