"""A warm-up ladder for the track-history cell: every size of ``IN`` list,
with and without the predicates the device masks, asked once in set-up
under any seed. Nothing is drawn but the taxis: the rungs are the
parameters'.

A filter that names taxis plans one row span a taxi on the attribute table;
with a window or a box beside it the table's kernel scans the blocks those
spans touch, and its variant is keyed by the bucket that number of blocks
pads into and by which predicates it has (``ds.warmup`` compiles the ladder,
this asks it with the data under it: a taxi in a block of its own, a heavy
taxi across several). Without either no kernel runs and the host takes the
spans' rows. Past a few hundred taxis a box and a window plan on a z index,
whose candidates the host then holds to the ``IN``. So:

  ``ids``      for each count: the ``IN`` alone, with the week's first day,
               and with that day's first hour and a box round the heaviest
               hot spot (the first taxis are the heaviest, so the spans
               that cross blocks are in every rung)
  ``many``     ``query_many`` of that many one-taxi one-day filters (the
               fused chunk)
  ``areas``    [width, height, hours] of a box round the heaviest hot spot
               beside the largest ``IN``: the z tables' buckets
"""

from generators.track_history import HOUR_MS, box_at, request, taxis


def generate(params, rng, n, ctx):
    out = []
    t0 = int(ctx["t0"])
    day, hour = [t0, t0 + 24 * HOUR_MS], [t0 + 8 * HOUR_MS, t0 + 9 * HOUR_MS]
    heavy = [str(v) for v in ctx["heavy"]]
    largest = []
    for count in params["ids"]:
        count = min(int(count), int(ctx["fleet"]))
        ids = heavy[:count] + [x for x in taxis(rng, ctx, count) if x not in heavy]
        ids = largest = ids[:count]
        out.append(request("warm-ids", ids))
        out.append(request("warm-ids-day", ids, day))
        out.append(request("warm-ids-box-hour", ids, hour, box_at(ctx, 0, params["box_deg"])))
    for count in params["many"]:
        out.append({"op": "query_attr", "klass": "warm-many", "members": [
            request("warm-many", [x], day) for x in taxis(rng, ctx, count)]})
    for width, height, hours in params["areas"]:
        out.append(request("warm-area", largest, [t0 + 8 * HOUR_MS, t0 + (8 + int(hours)) * HOUR_MS],
                           box_at(ctx, 0, [width, height])))
    return out
