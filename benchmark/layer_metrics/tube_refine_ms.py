"""Tables / native tier: per ``tube`` root the summed wall of its query's
``decode`` span (the gather of every row the device's mask passed and the
host's evaluation of the whole ``Or`` over them) and of its own
``tube.refine`` (interpolation, haversine, mask); the median over the window's
roots, milliseconds."""
from layer_metrics._process import tube_ms


def read(view):
    return tube_ms(view, inner=("decode",), own=("tube.refine",))
