"""Tables / native tier: per ``knn`` root the summed wall of its rounds'
``dispatch`` spans (the staging of a round's windows into the fused chunk) and
of its ``scan`` spans (the wait for the device and the pull; of a fused group
one member pays both); the median over the window's roots, milliseconds."""
from layer_metrics._process import knn_ms


def read(view):
    staged = knn_ms(view, ("dispatch",), under="knn.round")
    return None if staged is None else staged + knn_ms(view, ("scan",))
