"""Planner: median whole ``plan`` span of the requests whose plan chose an
attribute index (``index`` = ``attr_<attribute>``; a ``query_many`` whose
members all did is one sample): the decider's own work and, inside it, every
index's decomposition, the losers' too."""
from harness.stats import median
from layer_metrics._attr import attr_plans


def read(view):
    got = [s["dur_s"] * 1e3 for s in attr_plans(view)]
    return median(got) if got else None
