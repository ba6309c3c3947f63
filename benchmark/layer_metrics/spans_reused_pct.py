"""Tables / native tier: of the ``dispatch`` spans that count
``spans_reused`` (any root), the share in which every member found its
candidate row spans where the plan's ``cost()`` had left them
(``spans_reused`` = ``members``, 1 for a single query's dispatch), and
computed none there. A program that does not count them gives None."""
from layer_metrics._segments import spans


def read(view):
    got = [a["spans_reused"] >= a.get("members", 1)
           for a in (s["attrs"] for s in spans(view, "dispatch")) if "spans_reused" in a]
    return 100.0 * sum(got) / len(got) if got else None
