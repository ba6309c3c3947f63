"""Entry points: median of the whole ``encode`` spans of the Arrow
answers (``http`` roots with ``fmt`` = ``arrow``, a fifth of the served
cells' reads): the table built, the IPC stream written, the chunks sent.
``encode_ms`` pools them with the GeoJSON answers, whose median it is."""
from harness.stats import median
from layer_metrics._arrow import encodes


def read(view):
    got = [s["dur_s"] * 1e3 for s in encodes(view)]
    return median(got) if got else None
