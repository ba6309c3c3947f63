"""Tables: of the rows the kernel's blocks hit for scans of an attribute
table, the share inside the value's row spans: 100 x ``clip_kept`` over
``clip_in``, pooled over the window's ``scan`` spans that count both. The
kernel masks whole blocks by box and window; a taxi's rows are a tenth of a
block, so the rest are its neighbours' in the same box or window."""
from layer_metrics._attr import attr_scans


def read(view):
    got = [s["attrs"] for s in attr_scans(view) if "clip_in" in s["attrs"]]
    hit = sum(a["clip_in"] for a in got)
    return 100.0 * sum(a.get("clip_kept", 0) for a in got) / hit if hit else None
