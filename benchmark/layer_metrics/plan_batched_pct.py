"""Planner: of the members of the ``plan`` spans directly under
``query_many`` roots (one span a batch, ``QueryPlanner.plan_many``), the
share that went through the batched stages as arrays (``batched`` over
``members``, summed over the window's roots); the rest were planned one
by one inside the same span. A ``plan`` under any other root does not
count. A program whose ``plan`` spans count neither gives None."""
from layer_metrics._segments import spans


def read(view):
    roots = {s["id"] for s in spans(view, "query_many") if s["parent"] is None}
    got = [(a["batched"], a["members"])
           for a in (s["attrs"] for s in spans(view, "plan", roots=("query_many",))
                     if s["parent"] in roots)
           if "batched" in a and "members" in a]
    members = sum(m for _, m in got)
    return 100.0 * sum(b for b, _ in got) / members if members else None
