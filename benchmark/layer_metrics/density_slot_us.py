"""Kernels: the density family's device seconds in the trace over the
kernel slots of the calls dispatched while the profiler ran, in
microseconds: what a block slot costs whatever it holds (padding and the
whole-table shape's other blocks included). The one-hot histogram is
2 x width x height x block rows operations a slot on the MXU, so at a fixed
grid this is the kernel's speed; ``density_roofline`` says how far that is
from what the question needs. None where the trace shows no device op
under the family's name."""
from harness import xplane


def read(view):
    d, fam = view["device"], view["kernels"].get("density")
    if not d or fam is None or view["trace_t"] is None:
        return None
    seconds = xplane.family_seconds(d["ops"], fam["trace_name_pattern"])
    t0, t1 = view["trace_t"]
    slots = sum(c["slots"] for c in view["kernel_calls"].get("density", []) if t0 <= c["t"] < t1)
    return 1e6 * seconds / slots if seconds > 0 and slots else None
