"""Kernels: candidate rows the density kernel was handed (unpadded blocks
x rows a block, of the calls dispatched while the profiler ran) over the
family's device seconds in the trace. None where the trace shows no device
op under the family's name."""
from harness import xplane


def read(view):
    d, fam = view["device"], view["kernels"].get("density")
    if not d or fam is None or view["trace_t"] is None:
        return None
    seconds = xplane.family_seconds(d["ops"], fam["trace_name_pattern"])
    if seconds <= 0:
        return None
    t0, t1 = view["trace_t"]
    calls = [c for c in view["kernel_calls"].get("density", []) if t0 <= c["t"] < t1]
    return sum(c["blocks"] * c["block_rows"] for c in calls) / seconds
