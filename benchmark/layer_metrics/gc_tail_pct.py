"""Host runtime: of the slowest 5% of the window's roots by wall (served:
the ``http`` roots; embedded: ``query``, ``query_many``, ``count`` and
``density``, pooled), the share that waited for the collector at least a
tenth of their wall (``gc_wait_s``, which ``Tracer.end`` stamps on a
root: the seconds of collections, on any thread, that overlap it). Says
whether the tail is the collector's."""
import math

from layer_metrics._segments import spans
from layer_metrics._stalls import totals
from layer_metrics.span_coverage_pct import EMBEDDED


def read(view):
    if totals(*view["perf_window"]) is None:
        return None
    roots = [s for s in spans(view) if s["parent"] is None]
    pool = [s for s in roots if s["name"] == "http"]
    if not pool:
        pool = [s for s in roots if s["name"] in EMBEDDED]
    if not pool:
        return None
    pool.sort(key=lambda s: -s["dur_s"])
    tail = pool[: max(math.ceil(0.05 * len(pool)), 1)]
    hit = [s for s in tail if s["attrs"].get("gc_wait_s", 0.0) >= 0.1 * s["dur_s"]]
    return 100.0 * len(hit) / len(tail)
