"""Host runtime: the CPU seconds of every thread role of the program but
the probe's own (``obs.trace.lock_cpu``: handlers, dispatcher, flush pool,
log, replicas, ops plane, callers) inside the window, over the window's
seconds. 1.0 is an interpreter that never idles; above it, native calls
ran beside it."""
from layer_metrics._lock import python_cpu_s


def read(view):
    got = python_cpu_s(view)
    if got is None:
        return None
    roles, window_s = got
    return sum(roles.values()) / window_s
