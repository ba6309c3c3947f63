"""Planner: median WHOLE duration of the ``plan.decompose`` spans under
``query`` roots: one index's covering ranges for a fresh filter (a plan
decomposes once an index it costs: z3 and z2 for a bbox + DURING).
``plan_ms`` is the ``plan`` span's self time and leaves this child out."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "plan.decompose", whole=True)
