"""Entry points: 100 x (1 - CPU time / wall time) summed over the
``plan``, ``decode`` and ``encode`` spans, the ones that read their
thread's CPU clock (``cpu_s``): phases that never sleep by design, so
what their threads did not run they waited, for the interpreter lock
first of all."""
from layer_metrics._segments import spans


def read(view):
    wall = cpu = 0.0
    for s in spans(view):
        if s["name"] in ("plan", "decode", "encode") and "cpu_s" in s["attrs"]:
            wall += s["dur_s"]
            cpu += s["attrs"]["cpu_s"]
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None
