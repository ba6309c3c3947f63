"""Host runtime: 100 x the seconds inside collections of the interpreter's
garbage collector, all generations, that ended inside the window, over
the window's seconds. A collection holds the interpreter lock: every
thread of the process stands still for it."""
from layer_metrics._stalls import totals


def read(view):
    t_lo, t_hi = view["perf_window"]
    tot = totals(t_lo, t_hi)
    if tot is None or t_hi <= t_lo:
        return None
    return 100.0 * sum(g["s"] for g in tot["gc"].values()) / (t_hi - t_lo)
