"""Entry points: of the ``encode`` spans that carry ``native`` (GeoJSON
answers of ``http`` roots), the share whose every page came out of the
exporter's one native call a page (1), not of ``json.dumps`` over a dict
a feature (0: a column only the interpreter can read, a NaN, no native
library). Arrow answers carry no ``native`` and are not counted. A
program that does not count them gives None."""
from layer_metrics._segments import spans


def read(view):
    got = [a["native"] >= 1
           for a in (s["attrs"] for s in spans(view, "encode", roots=("http",))) if "native" in a]
    return 100.0 * sum(got) / len(got) if got else None
