"""Tables / native tier: of the rows the device's mask passed for the
window's tubes (eight box slots, the last the union of slices 8 to 256, times
ONE window over the whole track) and the host gathered, the share that is the
answer: 100 x ``kept`` over ``candidates``, pooled over the ``tube`` roots."""
from layer_metrics._process import pooled


def read(view):
    return pooled(view, "tube", "kept", "candidates", scale=100.0)
