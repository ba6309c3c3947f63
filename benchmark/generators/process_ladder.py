"""A warm-up ladder for the process cell: every size of window, of fused
round and of corridor the mix can reach, asked once in set-up under any
seed. Nothing is drawn but the places: the rungs are the parameters'.

A kNN round plans one window a pending point at four times its radius and
sends them through ``QueryPlanner.submit_many``: a window's scan variant is
keyed by its bucket of candidate blocks, a fused chunk by its members'
flags alone (``ds.warmup`` compiles both ladders, this asks them with the
data under them). A tube is ONE z3 scan whose boxes always fill the
kernel's eight slots (the box count is no compile key), keyed again by its
block bucket, which grows with the buffer and the track's length. So:

  ``knn.radii_m``     ``knn`` at the heaviest port and at one point at sea
                      with ``estimated_distance_m`` fixed at each rung (the
                      window is four times as wide), at the first, the middle
                      and the last instant of the span
  ``knn_many.points`` ``knn_many`` of that many points along the first lane
                      (the store's own start radius, as the mix asks)
  ``tube.bins`` x ``tube.buffers_m``
                      ``tube`` along the first voyages' 24 h, cut by
                      ``max_bins`` to each number of slices, at each buffer
"""

import numpy as np

from generators.vessel_proximity import (COMMON, MIN_TRACK_ROWS, along, at_sea,
                                         knn_many_request, knn_request, tube_request)


def generate(params, rng, n, ctx):
    out = []
    ports = ctx["ports"]
    common = {k: params[k] for k in COMMON}
    ws = int(common["window_s"]) * 1000
    t0, span = int(ctx["t0"]), int(ctx["span_ms"])
    times = [t0 + ws, t0 + span // 2 // 1000 * 1000, t0 + span - ws]
    places = [(ports["x"][0], ports["y"][0]), at_sea(rng, ctx, float(params["min_port_km"]))]
    for r in params["knn"]["radii_m"]:
        for place in places:
            for t_ms in times:
                out.append(knn_request("warm-knn", place, t_ms,
                                       dict(common, estimated_distance_m=float(r))))
    for m in params["knn_many"]["points"]:
        pts = along(ctx["lanes"][0], (np.arange(int(m)) + 0.5) / int(m))
        out.append(knn_many_request("warm-knn-many", pts, times[1],
                                    dict(common, k=params["knn_many"]["k"])))
    voyages = np.flatnonzero(np.asarray(ctx["voyages"]["rows_left"]) >= MIN_TRACK_ROWS)
    spec = {"hours": 24, "every": 1}
    turn = 0
    for buffer_m in params["tube"]["buffers_m"]:
        for bins in params["tube"]["bins"]:
            out.append(tube_request("warm-tube", ctx, int(voyages[turn % len(voyages)]),
                                    dict(spec, buffer_m=buffer_m), max_bins=int(bins)))
            turn += 1
    return out
