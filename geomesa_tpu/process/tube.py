"""Tube select: spatio-temporal corridor search along a track.

Reference: TubeSelectProcess + TubeBuilder (/root/reference/
geomesa-process/src/main/scala/org/locationtech/geomesa/process/tube/
TubeSelectProcess.scala:36, TubeBuilder.scala) — bins an input track into
time slices, buffers each slice's geometry, and queries features that fall
inside the moving buffer both spatially and temporally. The TPU redesign
bins the track the same way (``bin_ms`` slices, interpolating positions),
issues one indexed query of its (bbox And interval) slices, carried as the
two arrays of a ``filter.predicates.Slices`` (which the planner answers,
past sixteen slices, as a union of time-ordered groups dispatched fused),
and refines with a vectorized distance test against each row's own
time-matched tube center.
"""

from __future__ import annotations

import numpy as np

from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.predicates import And, BBox, During, Filter, Include, Slices
from geomesa_tpu.obs.trace import span as _ospan
from geomesa_tpu.obs.trace import tracer as _otracer
from geomesa_tpu.process.knn import _meters_to_degrees_each, haversine_m


def tube_select(
    store,
    type_name: str,
    track_xy: "np.ndarray | list",
    track_times_ms: "np.ndarray | list",
    buffer_m: float,
    bin_ms: int | None = None,
    filter: Filter = Include(),
    max_bins: int = 256,
) -> FeatureCollection:
    """Features within ``buffer_m`` of the track position at their own time.

    ``track_xy``: [n, 2] lon/lat waypoints; ``track_times_ms``: [n] epoch
    millis, ascending. ``bin_ms`` defaults to the track duration / number
    of waypoints (the reference's default binning).

    Traced (docs/processes.md): ONE root ``tube`` a call (``waypoints``,
    ``bins``, ``buffer_m``, ``rows`` the query returned, ``kept`` within
    ``buffer_m``; ``groups`` / ``boxes`` / ``windows`` / ``ranges`` /
    ``candidates`` / ``arrays`` from the query's own plan and trace) with
    the children ``tube.bins`` and ``tube.refine``; the query is
    ``store.query``'s own ``query`` root, linked by ``tube_trace`` (and
    ``query_trace`` here).

    Past sixteen slices the planner answers the one query as a union of
    time-ordered groups of slices (``filter.dnf.time_slices``), each group
    a scan of its own boxes over its own stretch of the track: the rows
    then come group by group, not in table order.
    """
    xy = np.asarray(track_xy, dtype=np.float64).reshape(-1, 2)
    ts = np.asarray(track_times_ms, dtype=np.int64)
    if len(xy) != len(ts) or len(xy) < 2:
        raise ValueError("track needs >= 2 (point, time) pairs")
    if not (np.diff(ts) >= 0).all():
        raise ValueError("track times must be ascending")
    sft = store.get_schema(type_name)
    if sft.dtg_field is None:
        raise ValueError("tube select requires a time attribute")
    geom, dtg = sft.geom_field, sft.dtg_field

    # capture=False: the slow log takes the ``query`` root inside, as it did
    # before this root was here; unsampled, this one is never built
    with _otracer().trace(
        "tube", capture=False, type=type_name, waypoints=len(xy), buffer_m=float(buffer_m)
    ) as trace:
        with _ospan("tube.bins", cpu=True):
            tube = _slices(geom, dtg, xy, ts, buffer_m, bin_ms, max_bins)
            f = tube if isinstance(filter, Include) else And((tube, filter))
        if trace is None:
            out = store.query(type_name, f)
        else:
            bins = len(tube) if isinstance(tube, Slices) else 1
            out = _query_counted(store, type_name, f, trace.root.annotate(bins=bins))
        if len(out):
            # refine: distance from each hit to the track position at the hit's time
            with _ospan("tube.refine", cpu=True, rows=len(out)):
                hx, hy = out.representative_xy()
                ht = np.asarray(out.columns[dtg], dtype=np.int64)
                px = np.interp(ht, ts, xy[:, 0])
                py = np.interp(ht, ts, xy[:, 1])
                d = haversine_m(hx, hy, px, py)
                out = out.mask(d <= buffer_m)
        if trace is not None:
            trace.root.annotate(kept=len(out))
        return out


def _query_counted(store, type_name: str, f: Filter, root) -> FeatureCollection:
    """The corridor's one query under a live ``tube`` root: ``store.query``
    as ever, which opens its own ``query`` root (linked both ways by the
    tracer: ``tube_trace`` there, ``query_trace`` here) and leaves its
    trace and its plan on the explainer it is handed. From them the root's
    ``rows``, and where that inner root was built too (sampled 1 in N by
    its own name's count) ``groups`` (the branches of a time-sliced union,
    0 for one scan), ``boxes`` / ``windows`` / ``ranges`` (the chosen plan's
    config, summed over a union's branches), ``candidates`` (its
    ``decode`` spans': the rows the device's mask passed) and ``arrays``
    (1 where its ``plan`` span counts ``slice_rows``: the slices reached
    the indexes as the carrier's two arrays, not as objects; else 0)."""
    from geomesa_tpu.planning.explain import ExplainNull

    exp = ExplainNull()
    out = store.query(type_name, f, explain=exp)
    root.annotate(rows=len(out))
    inner, plan = getattr(exp, "trace", None), getattr(exp, "plan", None)
    if inner is not None:
        root.annotate(
            candidates=sum(
                (s.attrs or {}).get("candidates", 0) for s in inner.spans if s.name == "decode"
            ),
            # the planner's own count: slices that reached the indexes as array rows
            arrays=int(any(
                (s.attrs or {}).get("slice_rows", 0) for s in inner.spans if s.name == "plan"
            )),
        )
    if plan is not None:
        # one scan, or past sixteen slices the union's branches (a group each)
        branches = plan.union if plan.union is not None else [plan]
        cfgs = [p.config for p in branches if p.config is not None]
        root.annotate(
            groups=len(branches) if plan.union is not None else 0,
            boxes=sum(0 if c.boxes is None else len(c.boxes) for c in cfgs),
            windows=sum(0 if c.windows is None else len(c.windows) for c in cfgs),
            ranges=sum(int(c.n_ranges) for c in cfgs),
        )
    return out


def _slices(geom, dtg, xy, ts, buffer_m, bin_ms, max_bins) -> Filter:
    """The track as box-and-interval slices, one a time bin, its box the
    bin's part of the track widened by ``buffer_m``: all bins computed at
    once and handed over as the two arrays of a ``Slices`` carrier (one
    bin: the ``And(BBox, During)`` it is)."""
    span = int(ts[-1] - ts[0])
    if bin_ms is None:
        bin_ms = max(1, span // max(1, len(xy)))
    n_bins = min(max_bins, max(1, -(-span // bin_ms)))
    bin_ms = -(-span // n_bins)

    # interpolated tube center per bin midpoint
    edges = ts[0] + bin_ms * np.arange(n_bins + 1)
    mids = edges[:-1] + bin_ms // 2
    cx = np.interp(mids, ts, xy[:, 0])
    cy = np.interp(mids, ts, xy[:, 1])

    # DURING is [lo, hi): the last slice ends one past the track's last
    # instant whatever the bins' width (where the span is a whole
    # multiple of the bins, ts[0] + n_bins * bin_ms IS ts[-1], and a min
    # with ts[-1] + 1 left the rows of that instant out)
    lo, hi = edges[:-1], np.minimum(edges[1:], ts[-1] + 1)
    hi[-1] = ts[-1] + 1
    # widen by the intra-bin track movement so interpolation error cannot
    # exclude a true hit: a bin's box holds its centre and the waypoints
    # from the one before its window to the one after. The runs overlap,
    # so their extremes come from reduceat over (start, stop) pairs, every
    # other result; one more element keeps a stop at the track's end valid
    first = np.maximum(np.searchsorted(ts, lo) - 1, 0)
    stop = np.maximum(np.minimum(np.searchsorted(ts, hi) + 1, len(ts)), first + 1)
    runs = np.stack([first, stop], axis=1).ravel()
    x, y = np.append(xy[:, 0], xy[-1, 0]), np.append(xy[:, 1], xy[-1, 1])
    x0 = np.minimum(cx, np.minimum.reduceat(x, runs)[::2])
    x1 = np.maximum(cx, np.maximum.reduceat(x, runs)[::2])
    y0 = np.minimum(cy, np.minimum.reduceat(y, runs)[::2])
    y1 = np.maximum(cy, np.maximum.reduceat(y, runs)[::2])
    # the buffer's reach in degrees at the slice's most poleward point,
    # where a metre is the most longitude
    deg = _meters_to_degrees_each(buffer_m, np.maximum(np.abs(y0), np.abs(y1)))
    boxes = np.stack(
        [x0 - deg, np.maximum(y0 - deg, -90.0), x1 + deg, np.minimum(y1 + deg, 90.0)],
        axis=1,
    )
    # where the span is shorter than its bins' millisecond steps, the bins
    # past the last instant hold no time at all: nothing to ask
    live = lo < hi
    boxes, windows = boxes[live], np.stack([lo, hi], axis=1)[live]
    if len(boxes) == 1:
        (box,), ((t0, t1),) = boxes.tolist(), windows.tolist()
        return And((BBox(geom, *box), During(dtg, t0, t1)))
    return Slices(geom, dtg, boxes, windows)


def standing_tube(
    lam,
    sub_id: str,
    track_xy: "np.ndarray | list",
    track_times_ms: "np.ndarray | list",
    buffer_m: float,
    attrs: "dict | None" = None,
):
    """:func:`tube_select`, STANDING (docs/standing.md): register the
    corridor as a persistent subscription on a
    :class:`~geomesa_tpu.streaming.LambdaStore` — every arriving batch
    routes through the inverted SubscriptionIndex and events within
    ``buffer_m`` of the interpolated track position AT THE EVENT'S OWN
    TIME deliver alerts (events without a usable time never match, the
    TubeSelectProcess refinement). Returns the registered
    :class:`~geomesa_tpu.streaming.Subscription`."""
    from geomesa_tpu.streaming.standing import Subscription

    xy = np.asarray(track_xy, np.float64).reshape(-1, 2)
    ts = np.asarray(track_times_ms, np.int64)
    if len(xy) != len(ts) or len(xy) < 2:
        raise ValueError("track needs >= 2 (point, time) pairs")
    if not (np.diff(ts) >= 0).all():
        raise ValueError("track times must be ascending")
    sub = Subscription(
        str(sub_id), "tube", track_xy=xy, track_times_ms=ts,
        buffer_m=float(buffer_m), attrs=dict(attrs or {}),
    )
    lam.subscribe(sub)
    return sub
