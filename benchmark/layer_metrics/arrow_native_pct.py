"""Entry points: of the Arrow answers' ``encode`` spans that carry
``arrow_native``, the share with 1: no Python object under the IPC writer
and every column of the table out of the native tier's one call, not of
pyarrow an array at a time (0: a column only pyarrow can read, no native
library). A program that does not count them gives None."""
from layer_metrics._arrow import encodes


def read(view):
    got = [s["attrs"]["arrow_native"] >= 1 for s in encodes(view) if "arrow_native" in s["attrs"]]
    return 100.0 * sum(got) / len(got) if got else None
