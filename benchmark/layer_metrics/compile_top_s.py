"""Host runtime: the dearest single program of set-up by JAX's
``fun_name``, its three phases (trace, lower, backend) summed over its
compiles, seconds; 0 where nothing compiled before the window."""
from layer_metrics._stalls import set_up_programs


def read(view):
    programs = set_up_programs(view)
    if programs is None:
        return None
    return max((p["trace"] + p["lower"] + p["backend"] for p in programs.values()),
               default=0.0)
