"""Tables / native tier: per ``tube`` root the summed wall of its query's
``dispatch`` and ``scan`` spans (one z3 scan of the blocks the track's ranges
leave, the wait for the device and the pull); the median over the window's
roots, milliseconds."""
from layer_metrics._process import tube_ms


def read(view):
    return tube_ms(view, inner=("dispatch", "scan"))
