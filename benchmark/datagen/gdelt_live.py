"""``gdelt``'s rows, and the events a feed appends to the store after the
load (configuration ``gdelt-lambda-1chip``).

The first ``n`` rows are ``datagen/gdelt.py``'s under the same seed,
letter for letter: the checkpointed store a Lambda deployment starts from.
Appended rows continue the ids: batch ``b`` holds ``first_id`` =
``n`` + ``b`` x ``rows`` and the ``rows`` ids after it. Their points come
from the same cluster mixture (the centres are the seed's), their
attributes from the same draws (``gdelt.RULES``; the actor names from the same
vocabulary in a fixed order, see ``_names``), and their times are
uniform over the last ``data.live_hours`` of the preloaded span: fresh
events, so a reader's window that ends at the newest event meets them.

A batch is a function of (seed, writer, batch number) alone
(``batch_columns``): a writer's process makes it to send it, the parent
makes it again to check what was read back, and nothing but the key
crosses the process boundary. Imports NumPy alone.
"""

from __future__ import annotations

import json
from functools import lru_cache

import numpy as np

from datagen import gdelt
from harness.data import cluster_centres, gdelt_points, sub_rng

HOUR_MS = 3_600_000


@lru_cache(None)
def _names() -> np.ndarray:
    """``gdelt._names()``'s vocabulary in an order every process agrees on
    (by length, then by letter): that one sorts a set by length alone, so
    its order follows the process's string hashing, which is no matter
    while one process makes all the rows and is one here, where a writer's
    process and the parent both make a batch."""
    return np.array(sorted(gdelt._names().tolist(), key=lambda s: (len(s), s)), dtype="<U24")


#: ``gdelt.RULES`` with the actor names drawn from the ordered vocabulary
RULES = dict(gdelt.RULES, **{actor + "Name": lambda rng, n, lo: gdelt._skewed(rng, _names(), n)
                             for actor in ("actor1", "actor2")})


def batch_spec(ctx: dict, writer: int, k: int, writers: int, rows: int) -> dict:
    """The key of writer ``writer``'s batch ``k``: all ``batch_columns``
    needs, small enough to ride in a request. Batches take their ids in
    the order of the writers' common schedule: ``k`` x ``writers`` +
    ``writer``."""
    b = int(k) * int(writers) + int(writer)
    t_hi = int(ctx["t0"]) + int(ctx["span_ms"])
    return {"seed": int(ctx["seed"]), "writer": int(writer), "k": int(k), "batch": b,
            "rows": int(rows), "first_id": int(ctx["n_rows"]) + b * int(rows),
            "t_lo": t_hi - int(ctx["live_hours"]) * HOUR_MS, "t_hi": t_hi,
            "attrs": list(ctx["attrs"]), "dtg": ctx["dtg"], "geom": ctx["geom"]}


def batch_columns(spec: dict) -> dict:
    """{"ids", "x", "y", "t", "attrs": {name: column}} of one batch."""
    n, seed = spec["rows"], spec["seed"]
    rng = np.random.default_rng([seed, 1, spec["writer"], spec["k"]])
    cx, cy = cluster_centres(sub_rng(seed, 0))
    x, y = gdelt_points(n, rng, cx, cy)
    t = spec["t_lo"] + rng.integers(0, spec["t_hi"] - spec["t_lo"], n)
    attrs = {a: RULES[a](rng, n, spec["first_id"]) for a in spec["attrs"]}
    return {"ids": np.arange(spec["first_id"], spec["first_id"] + n, dtype=np.int64),
            "x": x, "y": y, "t": t.astype(np.int64), "attrs": attrs}


def batch_rows(spec: dict) -> list:
    """The batch as the rows every witness is brought to (``Columns.row``)."""
    c = batch_columns(spec)
    names = list(c["attrs"])
    lists = [c["attrs"][a].tolist() for a in names]
    out = []
    for i, (x, y, t) in enumerate(zip(c["x"].tolist(), c["y"].tolist(), c["t"].tolist())):
        row = {spec["dtg"]: t, spec["geom"]: [x, y]}
        row.update((a, col[i]) for a, col in zip(names, lists))
        out.append(row)
    return out


def geojson_body(spec: dict) -> bytes:
    """The batch as a feed posts it: a GeoJSON FeatureCollection, every
    attribute a property, the date as ISO text, the feature id as text."""
    dtg, geom = spec["dtg"], spec["geom"]
    feats = []
    for i, row in zip(range(spec["first_id"], spec["first_id"] + spec["rows"]),
                      batch_rows(spec)):
        xy = row.pop(geom)
        row[dtg] = f"{np.datetime64(row[dtg], 'ms')}Z"
        feats.append({"type": "Feature", "id": str(i),
                      "geometry": {"type": "Point", "coordinates": xy}, "properties": row})
    return json.dumps({"type": "FeatureCollection", "features": feats}).encode()


class Columns(gdelt.Columns):
    """``gdelt.Columns`` (the preloaded rows: ``len`` is theirs alone) that
    also knows the appended batches by their keys."""

    def __init__(self, config: dict, n: int, seed: int):
        super().__init__(config, n, seed)
        self.live_hours = int(config["data"]["live_hours"])
        self._batches: dict = {}

    def context(self) -> dict:
        return super().context() | {"attrs": list(self.attrs), "dtg": self.dtg,
                                    "geom": self.geom, "live_hours": self.live_hours}

    def batch(self, spec: dict) -> dict:
        """``batch_columns(spec)``, made once."""
        got = self._batches.get(spec["batch"])
        if got is None:
            got = self._batches[spec["batch"]] = batch_columns(spec)
        return got

    def appended_row(self, spec: dict, i: int) -> dict:
        """Row ``i`` (a feature id) of the batch ``spec``, as ``row`` gives
        a preloaded one."""
        c = self.batch(spec)
        j = int(i) - spec["first_id"]
        out = {self.dtg: int(c["t"][j]), self.geom: [float(c["x"][j]), float(c["y"][j])]}
        out.update({a: col[j].item() for a, col in c["attrs"].items()})
        return out


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
