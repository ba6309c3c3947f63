"""Tables / native tier: of the ``decode`` spans that count
``gather_native`` (any root), the share whose candidates' rows came out
of ``FeatureCollection.take``'s one native call for all columns (1), not
of NumPy's indexing column by column (0: answers under the module's
floor of rows x bytes a row). A program that does not count them gives
None."""
from layer_metrics._segments import spans


def read(view):
    got = [a["gather_native"] >= 1
           for a in (s["attrs"] for s in spans(view, "decode")) if "gather_native" in a]
    return 100.0 * sum(got) / len(got) if got else None
