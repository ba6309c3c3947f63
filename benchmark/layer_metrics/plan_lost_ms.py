"""Planner: median, a request, of the ``plan.probe`` + ``plan.decompose`` time
of the indexes that were costed and LOST: every index that can serve a filter
is decomposed before any is costed, so a one-range attribute plan pays for a
few hundred z-ranges it never scans. Over the ``plan`` spans that costed more
than one index a member."""
from harness.stats import median
from layer_metrics._attr import ROOTS, plans
from layer_metrics._segments import spans


def read(view):
    won = {s["id"]: s["attrs"]["index"] for s in plans(view)
           if s["attrs"].get("costed", 0) > s["attrs"].get("members", 1)}
    if not won:
        return None
    sums = dict.fromkeys(won, 0.0)
    for s in spans(view, roots=ROOTS):
        if s["name"] in ("plan.probe", "plan.decompose") and s["parent"] in won \
                and s["attrs"].get("index") != won[s["parent"]]:
            sums[s["parent"]] += s["dur_s"] * 1e3
    return median(list(sums.values()))
