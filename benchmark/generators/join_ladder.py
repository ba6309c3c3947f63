"""A warm-up ladder for the join cell: every kernel variant a join of the
mix can reach, under any seed, asked once in set-up. Nothing is drawn:
``rng`` and ``n`` are ignored.

A join's members go to the device grouped by kernel variant
(``IndexTable.scan_submit_many``): a group of over 8 members, or one whose
candidate blocks fill an eighth of a chunk, is ONE fused chunk, whose
(edge bucket, raster bucket) shapes ``ds.warmup`` compiles; a smaller
group dispatches member by member on the single-query ladder, whose
variant is keyed by the member's own block bucket and by its polygon's
edge or raster bucket, which ``ds.warmup`` does not reach. So, for each
layer of ``layers``:

  ``singles: "all"``   every polygon alone (a variant is a property of the
                       polygon and the rows under it, both fixed by the
                       data): ``boroughs``
  ``singles: "spots"`` every polygon of the 3 x 3 patch round each hot spot
                       alone: ``neighborhoods``, whose 2 x 2 patches the
                       mix draws round a point 0.25 sigma from a hot spot,
                       a fortieth of a cell: 40-42 of the 195 polygons, of
                       which a mix of 8,000 reaches 28-32
  ``singles: n``       the polygon at each of the n heaviest hot spots
                       alone, and the layer's ``over_16_edges`` polygons
                       alone (the next edge bucket): ``blocks``, all of
                       about one size, far too many to ask each
  ``patches: [...]``   square patches of those sides round the heaviest
                       hot spots in turn: the fused chunks, and the mixes
                       of fused and single groups a patch splits into
"""

from generators.zone_joins import join_request, patch_round


def generate(params, rng, n, ctx):
    out, spot = [], 0
    for layer, spec in params["layers"].items():
        about = ctx["layers"][layer]
        klass = "warm-" + layer
        singles = spec.get("singles", 0)
        if singles == "all":
            alone = list(range(about["polygons"]))
        elif singles == "spots":
            alone = sorted({k for x, y in zip(ctx["cx"], ctx["cy"])
                            for k in patch_round(ctx, layer, x, y, 3, 3)})
        else:
            alone = [patch_round(ctx, layer, ctx["cx"][s], ctx["cy"][s], 1, 1)[0]
                     for s in range(int(singles))] + list(about["over_16_edges"])
        out += [join_request(klass, layer, [k], params["predicate"]) for k in alone]
        for side in spec.get("patches", []):
            for _ in range(int(spec.get("spots_a_patch", 1))):
                s = spot % len(ctx["cx"])
                spot += 1
                out.append(join_request(klass, layer, patch_round(
                    ctx, layer, ctx["cx"][s], ctx["cy"][s], int(side), int(side)),
                    params["predicate"]))
    return out
