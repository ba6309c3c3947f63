"""Planner: median self time of the program's ``plan`` spans (its
``plan.probe`` and ``plan.decompose`` children taken out)."""
from layer_metrics._spans import median_ms


def read(view):
    return median_ms(view, "plan")
