"""Planner: of the window's ``vis`` spans that say how they decided
(``coded``), 100 x those that looked label codes up by the candidates'
ordinals, before the gather (``coded`` 1: ``security.mask_ordinals``), over
all of them (``coded`` 0: ``security.mask_collection`` read an answer's
label strings). The engagement counter of PR 54's mechanism: 100 where every
route of the mix has ordinals. None where no span carries ``coded`` (PR 53's
program, a store without auths)."""
from layer_metrics._vis import vis_spans


def read(view):
    got = [s["attrs"]["coded"] for s in vis_spans(view) if "coded" in s["attrs"]]
    return 100.0 * sum(1 for v in got if v) / len(got) if got else None
