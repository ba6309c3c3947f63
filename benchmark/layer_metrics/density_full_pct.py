"""Tables / native tier: of the ``dispatch`` spans under ``density`` roots
that count ``full``, the share that took the whole-table shape (more
candidate blocks than the ladder's last bucket, so every block is
scanned). A program that does not count it (before PR 33) gives None."""
from layer_metrics._density import dispatches


def read(view):
    got = [s["attrs"]["full"] >= 1 for s in dispatches(view, "full")]
    return 100.0 * sum(got) / len(got) if got else None
