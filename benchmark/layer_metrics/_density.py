"""Shared by the readers of the ``density`` root's spans (PR 33): the
``dispatch`` spans under ``density`` roots that count kernel slots (the
device path; a request answered on the host nests a row query's
``dispatch``, which counts slots too and is read all the same: it is what
the request scanned). A program without the attribute a reader needs gives
it nothing to read: None."""

from layer_metrics._segments import spans


def dispatches(view, needs):
    return [s for s in spans(view, "dispatch", roots=("density",)) if needs in s["attrs"]]
