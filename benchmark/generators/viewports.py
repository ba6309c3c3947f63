"""Map sessions: ``bbox AND dtg DURING`` viewports around the data's
cluster centres, as a WFS/GeoServer dashboard sends them while it pans.

Parameters (the traffic file's ``params``): ``widths_deg`` and ``hours``
(dealt equally often, so every seed asks for the same sizes),
``height_ratio``, ``zipf_s`` (popularity of the 64 centres by rank),
``jitter_deg`` (N(0, jitter) about the centre), ``recent_share`` (share of
windows that end at the newest event, "the last 24 h"; the rest start
anywhere in the span), ``arrow_share`` (the rest is GeoJSON).
"""

import numpy as np

from harness.data import balanced


def generate(params, rng, n, ctx):
    cx, cy = np.asarray(ctx["cx"]), np.asarray(ctx["cy"])
    t0, span = int(ctx["t0"]), int(ctx["span_ms"])
    ranks = np.arange(1, len(cx) + 1, dtype=np.float64) ** -float(params["zipf_s"])
    which = rng.choice(len(cx), n, p=ranks / ranks.sum())
    widths = balanced(rng, params["widths_deg"], n)
    hours = balanced(rng, params["hours"], n)
    n_arrow = int(round(n * float(params["arrow_share"])))
    fmts = balanced(rng, ["arrow"] * n_arrow + ["geojson"] * (n - n_arrow), n)
    n_recent = int(round(n * float(params["recent_share"])))
    recent = balanced(rng, [True] * n_recent + [False] * (n - n_recent), n)
    jit = rng.normal(0.0, float(params["jitter_deg"]), (n, 2))
    starts = rng.random(n)
    out = []
    for i in range(n):
        w = float(widths[i])
        h = w * float(params["height_ratio"])
        x0 = float(np.clip(cx[which[i]] + jit[i, 0] - w / 2, -180.0, 180.0 - w))
        y0 = float(np.clip(cy[which[i]] + jit[i, 1] - h / 2, -90.0, 90.0 - h))
        dur = int(hours[i]) * 3_600_000
        if recent[i]:
            hi = t0 + span
        else:
            hi = (t0 + dur + int(starts[i] * (span - dur))) // 1000 * 1000
        out.append({"op": "query", "klass": "viewport", "fmt": str(fmts[i]),
                    "box": [x0, y0, x0 + w, y0 + h], "win": [hi - dur, hi]})
    return out
