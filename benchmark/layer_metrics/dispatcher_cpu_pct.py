"""Host runtime: 100 x the CPU seconds of the role ``dispatcher`` (the
serving scheduler's one thread) over those of every role but the probe's,
inside the window: the share of the interpreter's work that cannot be
spread over the handler threads."""
from layer_metrics._lock import python_cpu_s


def read(view):
    got = python_cpu_s(view)
    if got is None:
        return None
    roles, _ = got
    total = sum(roles.values())
    return 100.0 * roles["dispatcher"] / total if "dispatcher" in roles and total > 0 else None
