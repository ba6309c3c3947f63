"""Device: of the first device plane's idle time inside the traced
window, the share under a ``geomesa:gc.`` host event: a collection of
generation 1 or 2, which the program's collector hook puts on the
profiler's clock from its start to its stop. ``idle_named_pct``'s
arithmetic over another prefix; None where the trace holds no such
event (the parent of PR 35 emits none)."""
import os

from harness import xplane
from harness.cells import OUT_DIR
from layer_metrics.idle_named_pct import share

PREFIX = "geomesa:gc."


def load(path):
    """(first device plane's op intervals, ``geomesa:gc.`` host intervals,
    the ``bench:window`` interval or None), nanoseconds:
    ``idle_named_pct.load`` over this file's prefix (that one reads its
    own module's)."""
    from jax.profiler import ProfileData

    device, named, window = {}, [], None
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if xplane.DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name in xplane.OP_LINES] or lines
            device[plane.name] = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                                  for ln in ops for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        named.append((float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
                    elif ev.name == xplane.WINDOW and window is None:
                        window = (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
    busy = device[sorted(device)[0]] if device else None
    return busy, named, window


def read(view):
    if not view["device"]:
        return None
    try:
        path = xplane.newest_xplane(os.path.join(OUT_DIR, view["workload"], "trace"))
    except FileNotFoundError:
        return None
    return share(*load(path))
