"""Shared by the span readers: the median of one span name's self time
(or whole duration) in milliseconds, over the roots of the window."""

from harness.stats import median


def median_ms(view, name, roots=("query",), whole=False):
    key = "dur_s" if whole else "self_s"
    got = [s[key] * 1e3 for s in view["spans"] if s["name"] == name and s["root"] in roots]
    return median(got) if got else None
