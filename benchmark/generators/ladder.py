"""A warm-up ladder: every class of ``classes`` at widths that grow by
``ratio`` from ``width_from`` to ``width_to`` degrees, crossed with every
window of ``hours`` (classes without a time predicate ignore it), boxes
centred on the data's cluster centres in turn. A kernel variant is keyed
by its candidate-block bucket (powers of two), and a box's blocks grow
with its area: with ``ratio`` under the square root of two no bucket
between the smallest and the largest rung is skipped, so every variant the
classes can reach is compiled in set-up, not in the window. ``n`` is
ignored: the ladder has as many requests as it has rungs.
"""

from generators.notebook import _ngon, _ring_box


def generate(params, rng, n, ctx):
    t0, span = int(ctx["t0"]), int(ctx["span_ms"])
    out, k = [], 0
    widths, w = [], float(params["width_from"])
    while w <= float(params["width_to"]):
        widths.append(round(w, 3))
        w *= float(params["ratio"])
    for klass in params["classes"]:
        timed = klass in ("z3", "count", "density")
        for w in widths:
            for h in (params["hours"] if timed else [0]):
                cx, cy = float(ctx["cx"][k % len(ctx["cx"])]), float(ctx["cy"][k % len(ctx["cy"])])
                k += 1
                w = float(w)
                x0 = min(max(cx - w / 2, -180.0), 180.0 - w)
                y0 = min(max(cy - w / 4, -90.0), 90.0 - w / 2)
                box = [x0, y0, x0 + w, y0 + w / 2]
                req = {"klass": klass, "box": box}
                if timed:
                    dur = int(h) * 3_600_000 if h else span
                    req["win"] = [t0 + span - dur, t0 + span]
                if klass in ("pip", "raster"):
                    ring = _ngon(box, 6 if klass == "pip" else 24)
                    req.update(box=_ring_box(ring), ring=ring)
                req["op"] = {"count": "count", "density": "density"}.get(klass, "query")
                if klass == "density":
                    req["grid"] = int(params["grid"])
                out.append(req)
    return out
