"""Tables: median self time of the ``scan`` spans over an attribute table
(``index`` = ``attr_<attribute>``): the wait for the kernel over the blocks a
value's row spans touch, the pull, the bit decode and the clip; a pure range
scan (no box, no window) runs no kernel and is the span's few microseconds."""
from harness.stats import median
from layer_metrics._attr import attr_scans


def read(view):
    got = [s["self_s"] * 1e3 for s in attr_scans(view)]
    return median(got) if got else None
