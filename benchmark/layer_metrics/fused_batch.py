"""Scheduler: queries per fused dispatch, members handed to
``scan_submit_many`` over the ``block_scan_multi`` dispatches they made
(the scan family's call records)."""


def read(view):
    calls = view["kernel_calls"].get("scan", [])
    members = sum(c["members"] for c in calls if c["kind"] == "submit_many")
    dispatches = sum(1 for c in calls if c["kind"] == "block_scan_multi")
    return members / dispatches if dispatches else None
