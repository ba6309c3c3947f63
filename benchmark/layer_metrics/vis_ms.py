"""Planner: median whole ``vis`` span, the row-level security stage of
an answer (``np.unique`` over the answer's label strings, one evaluation a
distinct label, and the copy of every column of the rows kept), over the
requests that have one (a ``query_many``'s members each)."""
from harness.stats import median
from layer_metrics._vis import vis_spans


def read(view):
    got = [s["dur_s"] * 1e3 for s in vis_spans(view)]
    return median(got) if got else None
