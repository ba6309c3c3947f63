"""``datagen/gdelt.py``'s rows, value for value under the same seed, and one
attribute more: ``visibility``, the feature-level label the schema's
``geomesa.vis.field`` names (GeoMesa manual, "Data Security": an Accumulo
visibility expression a feature).

**Labels follow WHERE a row lies**, as a source's releasability does. The
twelve expressions ``EXPRESSIONS`` over the six tokens ``TOKENS`` use every
production of the grammar (a token, ``|``, ``&``, ``&`` over ``|``,
parentheses on either side, the empty label); the first ``N_VISIBLE`` are
the ones auths ``{user, ops}`` satisfy.

- the uniform half of the table (``harness.data.gdelt_points``' background)
  draws its labels from the fixed mix ``UNIFORM_MIX``;
- each of the 64 clusters draws from a mix of its own: a Dirichlet draw
  centred on ``UNIFORM_MIX`` with concentration ``DIRICHLET_ALPHA`` a label
  on average (``DIRICHLET_ALPHA`` x 12 in all: most of a cluster's rows
  carry two or three labels), from the configuration's ``data.label_seed``
  and NOT from the run's seed: cluster k is as open or as closed under every
  ``--seed``, wherever that seed puts its centre (``datagen/ais.py`` has why
  a deployment's geography is the configuration's).

Which of its group's labels a ROW carries is drawn from the run's seed (a
stream of its own, ``[seed, LABEL_STREAM, chunk]``), so the columns
``datagen/gdelt.py`` makes are untouched. Which group a row belongs to is
not among the columns ``gdelt_points`` returns: ``groups`` replays that
function's first draws of the chunk's stream (two uniforms, the coin, the
cluster number) and tests/test_secured_cell.py holds the replay to the
columns themselves (a clustered row lies within its cluster's reach).

``Columns.label_code`` is each row's index into ``EXPRESSIONS`` and
``Columns.group`` 0 for the background, 1 + k for cluster k: for the tests
and the configuration's stated shares, never read by the reference, which
parses the strings (``harness/reference_secured.py``). ``Columns.auths`` is
the configuration's.

The module imports ``harness.check_secured`` for one reason: it brings the
comparison's ``vis_leaks`` into ``harness.check.LIMITS``, and a run's tally
is made from ``LIMITS`` after the data set's module is loaded and before any
request's op is (``datagen/tdrive.py`` does the same for its op).
"""

from __future__ import annotations

import numpy as np

import harness.check_secured  # noqa: F401  (vis_leaks in check.LIMITS before the run's tally)
from datagen import gdelt
from harness.data import N_CLUSTERS

TOKENS = ("user", "ops", "intel", "admin", "partner", "legal")
EXPRESSIONS = ("", "user", "ops", "user|ops", "user&ops", "(ops|intel)&user",
               "user&(ops|partner)", "intel", "ops&intel", "admin", "admin&intel",
               "partner|admin")
N_VISIBLE = 7  # the first seven: what {user, ops} may read
#: the background's mix, and the centre of every cluster's: 0.60 visible to
#: {user, ops}, a quarter of it public
UNIFORM_MIX = (0.25, 0.12, 0.08, 0.05, 0.04, 0.03, 0.03, 0.10, 0.06, 0.10, 0.06, 0.08)
DIRICHLET_ALPHA = 0.3
LABEL_STREAM = 53
VIS_ATTRIBUTE = "visibility"


def label_mixes(label_seed: int) -> np.ndarray:
    """[1 + clusters, labels]: row 0 the background's mix, row 1 + k
    cluster k's. The deployment's: from ``label_seed`` alone."""
    centre = np.asarray(UNIFORM_MIX, np.float64)
    rng = np.random.default_rng([int(label_seed), LABEL_STREAM])
    own = rng.dirichlet(DIRICHLET_ALPHA * len(centre) * centre, N_CLUSTERS)
    return np.vstack([centre, own])


def groups(seed: int, chunk: int, n: int) -> np.ndarray:
    """Which of ``gdelt_points``' populations each row of a chunk was drawn
    from: 0 the background, 1 + k cluster k. The first draws of the chunk's
    stream, as ``harness.data.gdelt_points`` makes them."""
    rng = np.random.default_rng([int(seed), 0, int(chunk)])
    rng.uniform(-180, 180, n)
    rng.uniform(-90, 90, n)
    clustered = np.flatnonzero(rng.integers(0, 2, n, dtype=np.int8))
    out = np.zeros(n, np.int16)
    out[clustered] = 1 + rng.integers(0, N_CLUSTERS, len(clustered))
    return out


def schema_without_labels(spec: str) -> str:
    """The spec less the label attribute and the user data: what
    ``datagen/gdelt.py`` has rules for."""
    attrs = [a for a in spec.split(";")[0].split(",") if a.split(":")[0] != VIS_ATTRIBUTE]
    return ",".join(attrs)


class Columns(gdelt.Columns):
    """``datagen.gdelt.Columns`` with ``attrs["visibility"]``."""

    def __init__(self, config: dict, n: int, seed: int):
        super().__init__(dict(config, schema=schema_without_labels(config["schema"])), n, seed)
        self.schema, self.dtg, self.geom = gdelt.parse_schema(config["schema"])
        if VIS_ATTRIBUTE not in dict(self.schema):
            raise KeyError(f"datagen/gdelt_secured.py labels the attribute {VIS_ATTRIBUTE!r}")
        self.auths = tuple(config["auths"])
        cdf = np.cumsum(label_mixes(config["data"]["label_seed"]), axis=1)
        cdf[:, -1] = 1.0
        # one ascending table for every group: group g's steps lie in (g, g + 1]
        steps = (cdf + np.arange(len(cdf))[:, None]).ravel()
        self.group = np.empty(n, np.int16)
        self.label_code = np.empty(n, np.int8)
        for c, lo in enumerate(range(0, n, gdelt.CHUNK_ROWS)):
            hi = min(lo + gdelt.CHUNK_ROWS, n)
            g = self.group[lo:hi] = groups(seed, c, hi - lo)
            u = np.random.default_rng([int(seed), LABEL_STREAM, c]).random(hi - lo)
            self.label_code[lo:hi] = np.searchsorted(steps, u + g, "right") - g * cdf.shape[1]
        self.attrs[VIS_ATTRIBUTE] = np.asarray(EXPRESSIONS)[self.label_code]

    def shares(self) -> dict:
        """The shares the configuration states, of THIS table."""
        seen = self.label_code < N_VISIBLE
        by_cluster = [float(seen[self.group == 1 + k].mean()) for k in range(N_CLUSTERS)
                      if (self.group == 1 + k).any()]
        return {"visible": float(seen.mean()), "public": float((self.label_code == 0).mean()),
                "background_visible": float(seen[self.group == 0].mean()),
                "cluster_visible_min": min(by_cluster), "cluster_visible_max": max(by_cluster),
                "distinct_labels": int(len(np.unique(self.label_code)))}


def make(config: dict, n: int, seed: int) -> Columns:
    return Columns(config, n, seed)
