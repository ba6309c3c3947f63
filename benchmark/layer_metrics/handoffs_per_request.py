"""Host runtime: ``handoffs`` (the places where the program itself lets
the interpreter lock go on a request's path: native calls, device waits,
socket writes and reads, the wait on a future, pyarrow's calls, an fsync)
summed over every span of the window's retained traces, over the
operations that ended in the window as the client counted them."""
from layer_metrics._lock import operations, span_sum


def read(view):
    total, n = span_sum(view, "handoffs"), operations(view)
    return None if total is None or not n else total / n
