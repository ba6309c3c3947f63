"""Device: of the device's idle time inside the traced window, the share
during which at least one of the program's own ``geomesa:`` host events
(a span or a segment of a retained trace, on the profiler's clock) was
open. 100 means every idle moment can be laid to a named span of the
program; what is missing is host time the program's spans do not cover.

Reads the run's ``.xplane.pb`` itself (``harness/xplane.py`` keeps only
``bench:`` host events); None where the trace has no device plane or no
``geomesa:`` event (the parent of PR 24 has none)."""
import os

from harness import xplane
from harness.cells import OUT_DIR

PREFIX = "geomesa:"


def load(path):
    """(first device plane's op intervals, ``geomesa:`` host intervals,
    the ``bench:window`` interval or None), nanoseconds."""
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


def share(busy, named, window):
    """100 x (idle time under a named interval) / (idle time), inside
    ``window`` (the extent of the device ops where there is none)."""
    if not busy or not named:
        return None
    lo, hi = window or (min(s for s, _ in busy), max(e for _, e in busy))
    idle = xplane.gaps(xplane.union(xplane._clip(busy, lo, hi)), lo, hi)
    cover = xplane.union(xplane._clip(named, lo, hi))
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    both, k = 0.0, 0
    for s, e in idle:  # both lists are sorted and disjoint
        while k < len(cover) and cover[k][1] <= s:
            k += 1
        j = k
        while j < len(cover) and cover[j][0] < e:
            both += min(e, cover[j][1]) - max(s, cover[j][0])
            j += 1
    return 100.0 * both / total


def read(view):
    if not view["device"]:
        return None
    try:
        path = xplane.newest_xplane(os.path.join(OUT_DIR, view["workload"], "trace"))
    except FileNotFoundError:
        return None
    return share(*load(path))
