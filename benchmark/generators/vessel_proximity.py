"""A coast guard's or a port authority's analyst over an AIS archive: the
vessels nearest an incident's position at its time (``knn``, ``knn_many``)
and the vessels that travelled with or met a given vessel along its voyage
(``tube``). Every request carries a time predicate.

Requests come in rounds, as ``generators/notebook.py``'s: a round holds the
classes in the counts ``round`` gives, dealt into a seeded order with
``harness.data.balanced``, so every round of every seed asks the same
multiset. A class is ``classes[name]``; its ``kind`` says how it is drawn:

  ``knn-port``   ``knn`` at a point N(0, ``offset_sigmas`` x the berths' sigma)
                 on each axis from a port drawn Zipf by the data's weights
  ``knn-sea``    ``knn`` at a point of a lane at least ``min_port_km`` from
                 every port (a lane and a place along it, uniform, drawn again
                 until it is that far out)
  ``knn-many``   ``knn_many`` of ``points`` points spaced evenly along one
                 lane, from port to port, at one time
  ``tube``       ``tube`` along one vessel's own reports from its departure
                 from a port: ``hours`` of them, every ``every``-th (up to
                 ``hours`` x 60 / ``every`` waypoints; fewer where the data
                 ends), ``buffer_m``; the voyage drawn uniform over those that
                 begin inside the span, so its time is uniform over it

The kNN classes share ``k`` (a class may give its own), ``window_s``
(``DURING`` T - window_s .. T + window_s, T uniform over the table's span on
a whole second) and ``max_distance_m``; none gives an estimated distance:
the start radius is the store's own.
"""

import numpy as np

from datagen.ais import flat_km
from harness.data import balanced

COMMON = ("k", "window_s", "max_distance_m")
#: a voyage is asked as a track only where its vessel reports this long after it leaves
MIN_TRACK_ROWS = 120


def knn_request(klass, point, t_ms, spec) -> dict:
    w = int(spec["window_s"]) * 1000
    return {"op": "knn", "klass": klass, "point": [float(point[0]), float(point[1])],
            "k": int(spec["k"]), "win": [int(t_ms) - w, int(t_ms) + w],
            "estimated_distance_m": spec.get("estimated_distance_m"),
            "max_distance_m": float(spec["max_distance_m"])}


def knn_many_request(klass, points, t_ms, spec) -> dict:
    req = knn_request(klass, points[0], t_ms, spec)
    del req["point"]
    return dict(req, op="knn_many", points=[[float(x), float(y)] for x, y in points])


def tube_request(klass, ctx, voyage: int, spec, **more) -> dict:
    """The track of ``voyage``'s vessel from its departure: its own reports."""
    v, rep = ctx["voyages"], ctx["reports"]
    lo = int(v["row"][voyage])
    n = min(int(spec["hours"]) * 60, int(v["rows_left"][voyage]))
    rows = np.arange(lo, lo + n, int(spec["every"]))
    return {"op": "tube", "klass": klass, "buffer_m": float(spec["buffer_m"]),
            "track_xy": np.stack([rep["x"][rows], rep["y"][rows]], 1),
            "track_t": np.asarray(rep["t"][rows], np.int64), **more}


def along(lane, fractions) -> np.ndarray:
    """Points at ``fractions`` of a lane's length from its first end."""
    lane = np.asarray(lane, np.float64)
    seg = flat_km(lane[:-1, 0], lane[:-1, 1], lane[1:, 0], lane[1:, 1])
    cum = np.concatenate([[0.0], np.cumsum(seg)])
    s = np.asarray(fractions, np.float64) * cum[-1]
    return np.stack([np.interp(s, cum, lane[:, 0]), np.interp(s, cum, lane[:, 1])], 1)


def at_sea(rng, ctx, min_port_km: float):
    """A point of some lane at least ``min_port_km`` from every port."""
    px, py = np.asarray(ctx["ports"]["x"]), np.asarray(ctx["ports"]["y"])
    for _ in range(10_000):
        lane = ctx["lanes"][int(rng.integers(0, len(ctx["lanes"])))]
        x, y = along(lane, [rng.uniform(0.0, 1.0)])[0]
        if flat_km(x, y, px, py).min() >= min_port_km:
            return x, y
    raise ValueError(f"no lane of this coast runs {min_port_km} km from every port")


def generate(params, rng, n, ctx):
    per_round = dict(params["round"])
    classes = [k for k, count in per_round.items() for _ in range(count)]
    n_rounds = -(-n // len(classes))
    ports = ctx["ports"]
    w = np.asarray(ports["w"])
    sigma = float(ports["sigma_deg"])
    span_s = int(ctx["span_ms"]) // 1000
    voyages = np.flatnonzero(np.asarray(ctx["voyages"]["rows_left"]) >= MIN_TRACK_ROWS)
    out = []
    for _ in range(n_rounds):
        for klass in balanced(rng, classes, len(classes)):
            klass = str(klass)
            spec = {k: params[k] for k in COMMON} | params["classes"][klass]
            kind = spec["kind"]
            if kind == "tube":
                out.append(tube_request(klass, ctx, int(rng.choice(voyages)), spec))
                continue
            ws = int(spec["window_s"])
            t_ms = int(ctx["t0"]) + int(rng.integers(ws, span_s - ws)) * 1000
            if kind == "knn-port":
                p = int(rng.choice(len(w), p=w / w.sum()))
                off = rng.normal(0.0, float(spec["offset_sigmas"]) * sigma, 2)
                out.append(knn_request(klass, (ports["x"][p] + off[0], ports["y"][p] + off[1]),
                                       t_ms, spec))
            elif kind == "knn-sea":
                out.append(knn_request(klass, at_sea(rng, ctx, float(spec["min_port_km"])),
                                       t_ms, spec))
            elif kind == "knn-many":
                m = int(spec["points"])
                lane = ctx["lanes"][int(rng.integers(0, len(ctx["lanes"])))]
                out.append(knn_many_request(klass, along(lane, (np.arange(m) + 0.5) / m),
                                            t_ms, spec))
            else:
                raise ValueError(f"class {klass!r}: unknown kind {kind!r}")
    return out[:n]
