"""Shared by the readers of the program's record of its interpreter lock
(``geomesa_tpu/obs/trace.py``: the hand-off probe's samples and the CPU
ledger by thread role, PR 51) and of what the same PR counts on the spans
(``handoffs``; ``native_s``, ``reacquire_s``, ``native_n`` where the native
tier stamped a call). A program without the record (the parent of PR 51)
gives every reader here nothing to read: None."""

from layer_metrics._segments import spans


def probe(view):
    """``obs.trace.lock_probe`` cut to the samples taken inside the
    window; None without the record or without a sample."""
    from geomesa_tpu.obs import trace

    fn = getattr(trace, "lock_probe", None)
    got = None if fn is None else fn(*view["perf_window"])
    return got if got and got["n"] else None


def python_cpu_s(view):
    """({role: CPU seconds inside the window} less the probe's own role,
    the window's seconds); None without the record or with fewer than two
    ledger samples inside the window."""
    from geomesa_tpu.obs import trace

    fn = getattr(trace, "lock_cpu", None)
    t_lo, t_hi = view["perf_window"]
    if fn is None or t_hi <= t_lo:
        return None
    got = fn(t_lo, t_hi)
    if got["samples"] < 2:
        return None
    return {r: s for r, s in got["cpu_s"].items() if r != "probe"}, t_hi - t_lo


def operations(view):
    """The operations that ended in the window, as the client counted
    them: its latency samples (a ``query_many`` is one, as is an
    acknowledged batch), the same number ``query_p95_ms`` is taken over."""
    return len(view["client"]["query_ms"])


def span_sum(view, attr):
    """``attr`` summed over every span of the window's retained traces,
    each span once; None where no span carries it."""
    got = [s["attrs"][attr] for s in spans(view) if attr in s["attrs"]]
    return sum(got) if got else None
