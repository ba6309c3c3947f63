"""A warm-up pass over the tile pyramid of ``generators/heatmap_tiles.py``:
every tile of the zooms ``whole_levels``, then the tile round each of the
``centres`` heaviest city centres at every zoom of ``centre_levels``.
Nothing is drawn: ``rng`` and ``n`` are ignored.

Why beside ``ladder`` and ``slabs``: both give every aggregation a time
window, and a window on a type with a Z2 index alone leaves the device
path. A density kernel's variant is keyed by the bucket its candidate-block
count pads into (32 ... 4,096, and the whole table past that). The tiles
of a few whole levels hold from a thousandth of the rows to half of them,
the tiles round the cities from a few blocks to a city's all, so the
passes reach every bucket under every seed (PERF.md, Findings, PR 33).
"""

from generators.heatmap_tiles import tile_at, tile_request


def generate(params, rng, n, ctx):
    grid = int(params["grid"])
    out = []
    for z in params["whole_levels"]:
        out += [tile_request(z, i, j, grid) for j in range(1 << z) for i in range(2 << z)]
    for z in params["centre_levels"]:
        seen = set()
        for x, y in list(zip(ctx["cx"], ctx["cy"]))[: int(params["centres"])]:
            ij = tile_at(z, float(x), float(y))
            if ij not in seen:
                seen.add(ij)
                out.append(tile_request(z, *ij, grid))
    return out
