"""Shared by the readers of the Arrow answers' ``encode`` spans: an
``encode`` does not say what it encoded, its ``http`` root does (``fmt``),
so the two are joined by the span's ``parent``. A program whose roots
carry no ``fmt`` gives the readers nothing to read: None."""

from layer_metrics._segments import spans


def encodes(view):
    """The ``encode`` spans directly under ``http`` roots whose ``fmt`` is
    ``arrow``, each once."""
    roots = {s["id"] for s in spans(view, "http", roots=("http",))
             if s["parent"] is None and s["attrs"].get("fmt") == "arrow"}
    return [s for s in spans(view, "encode", roots=("http",)) if s["parent"] in roots]
