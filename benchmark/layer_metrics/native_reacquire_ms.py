"""Host runtime: ``reacquire_s`` summed over every span of the window's
retained traces, over the native calls that wrote one (``native_n``), in
milliseconds: the mean wait to get the interpreter lock back after a
native call, read between the library's stamp of its return and
``perf_counter`` in Python. The second and independent reading of what
``lock_handoff_ms`` reads."""
from layer_metrics._lock import span_sum


def read(view):
    waited, calls = span_sum(view, "reacquire_s"), span_sum(view, "native_n")
    return None if waited is None or not calls else 1e3 * waited / calls
