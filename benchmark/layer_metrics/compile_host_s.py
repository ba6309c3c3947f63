"""Host runtime: seconds of the ``trace`` and ``lower`` phases (a function
to a jaxpr, the jaxpr to MLIR; for a Pallas kernel Mosaic's lowering) of
every program whose phase ended before the window began: the part of
compiling that is Python under the interpreter lock, which ``compile_s``
(the backend's seconds) does not hold and a warm cache does not save."""
from layer_metrics._stalls import set_up_programs


def read(view):
    programs = set_up_programs(view)
    if programs is None:
        return None
    return sum(p["trace"] + p["lower"] for p in programs.values())
