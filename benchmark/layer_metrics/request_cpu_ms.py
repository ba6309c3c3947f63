"""Host runtime: ``python_cpu_cores``' CPU seconds, in milliseconds, over
the operations that ended in the window as the CLIENT counted them (its
latency samples: a ``query_many`` is one operation, as is an acknowledged
batch): what a request costs under the interpreter lock, whose reciprocal
bounds the operations a second once ``python_cpu_cores`` is at 1."""
from layer_metrics._lock import operations, python_cpu_s


def read(view):
    got, n = python_cpu_s(view), operations(view)
    if got is None or not n:
        return None
    roles, _ = got
    return 1e3 * sum(roles.values()) / n
