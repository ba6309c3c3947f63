"""Entry points: of the window's ``count`` and ``density`` roots that carry
``vis_fallback`` (auths set, a label field), the share with 1: visibility
alone took the aggregation's device path away and the request became a row
query with a host tally. A ``density`` whose filter the scan mask decides
reads 1; an exact ``count`` of a box and a window has no device path with or
without auths and reads 0. A label mask on the device brings the 1s to 0."""
from layer_metrics._vis import roots


def read(view):
    got = [s["attrs"]["vis_fallback"] for s in roots(view)
           if s["name"] in ("count", "density") and "vis_fallback" in s["attrs"]]
    return 100.0 * sum(1 for v in got if v) / len(got) if got else None
