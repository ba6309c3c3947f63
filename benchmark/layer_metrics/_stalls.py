"""Shared by the readers of the program's record of runtime stalls
(``geomesa_tpu/obs/trace.py``: the collector's pauses and the compiler's
phases, PR 35). A program without the record (the parent of PR 35) gives
every reader here nothing to read: None."""


def totals(t_lo, t_hi):
    """``obs.trace.stall_totals`` cut to what ended inside [t_lo, t_hi)
    on ``time.perf_counter`` (None: no bound on that side)."""
    from geomesa_tpu.obs import trace

    fn = getattr(trace, "stall_totals", None)
    return None if fn is None else fn(t_lo, t_hi)


def set_up_programs(view):
    """{fun_name: {"trace", "lower", "backend": seconds, "calls",
    "hits"}} of the phases that ended before the window began."""
    tot = totals(None, view["perf_window"][0])
    return None if tot is None else tot["compile"]
