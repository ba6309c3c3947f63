"""A warm-up pass over slabs of time: every class of ``classes`` over every
box of ``boxes`` (degrees, as given) crossed with every window of ``days``
([start, length] in days from the data's first instant, cut at its last).
Nothing is drawn: ``rng`` and ``n`` are ignored.

Why beside ``ladder``: an aggregation kernel's variant is keyed by the
bucket its candidate-block count pads into, and a box's candidate blocks
do not grow with its area (a 40-degree box across the equator and the
prime meridian reaches 47 blocks of 128 where a 73-degree box elsewhere
reaches 26), so a ladder of widths can skip a bucket that the window's
traffic then compiles. Under a box that is nearly the world the candidate
blocks of a z3 table are those of the window's share of time, and the
data's times are uniform for every seed: 4 to 7 days reach 45 to 57 blocks
of 128, 10 to 16 days 97 to 128 (PERF.md, Findings). So these rungs reach
every bucket under every seed.
"""

DAY_MS = 86_400_000


def generate(params, rng, n, ctx):
    t0, span = int(ctx["t0"]), int(ctx["span_ms"])
    out = []
    for klass in params["classes"]:
        for box in params["boxes"]:
            for start, length in params["days"]:
                lo = t0 + int(float(start) * DAY_MS)
                hi = min(lo + int(float(length) * DAY_MS), t0 + span)
                req = {"op": {"count": "count", "density": "density"}.get(klass, "query"),
                       "klass": klass, "box": [float(v) for v in box], "win": [lo, hi]}
                if klass == "density":
                    req["grid"] = int(params["grid"])
                out.append(req)
    return out
