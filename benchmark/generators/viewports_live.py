"""``viewports``' map sessions over a store that is appended to while it
is read: the same requests from the same parameters and stream, under the
op ``query_live``, whose comparison knows which appended rows an answer
must hold and which it may (``ops/query_live.py``)."""

from generators import viewports


def generate(params, rng, n, ctx):
    out = viewports.generate(params, rng, n, ctx)
    for req in out:
        req["op"] = "query_live"
    return out
