"""The seeded pieces that data sets and generators share.

Copies of bench.py's ``gdelt_points``, ``box_queries`` and ``time_windows``
(bench.py may be deleted, ROADMAP D1, so nothing here imports it). Two
departures, both so that one seed gives the same amount of work as another:
the cluster centres are drawn once and passed in; and ``balanced`` deals
the box and window sizes from a fixed multiset instead of drawing each
one. A data set is a module of its own under ``datagen/``, named by the
configuration.
"""

from __future__ import annotations

import numpy as np

DAY_MS = 86_400_000
N_CLUSTERS = 64


def sub_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose (data, traffic, warm-up,
    check sample ...); ``seed`` may pass 2**31."""
    return np.random.default_rng([int(seed), int(stream)])


def cluster_centres(rng):
    return rng.uniform(-160, 160, N_CLUSTERS), rng.uniform(-55, 65, N_CLUSTERS)


def gdelt_points(n, rng, cx, cy):
    """World-wide events clustered around population centres: a uniform
    background, and for half the rows (a fair coin each, so that both
    kinds arrive all through the span) a Gaussian cluster, sigma 3 x 2
    degrees."""
    x = rng.uniform(-180, 180, n)
    y = rng.uniform(-90, 90, n)
    clustered = np.flatnonzero(rng.integers(0, 2, n, dtype=np.int8))
    which = rng.integers(0, len(cx), len(clustered))
    x[clustered] = np.clip(cx[which] + rng.normal(0, 3.0, len(clustered)), -180, 180)
    y[clustered] = np.clip(cy[which] + rng.normal(0, 2.0, len(clustered)), -90, 90)
    return x, y


def balanced(rng, choices, n):
    """n values that use every choice equally often (to within one), in a
    seeded order: every seed asks for the same sizes."""
    reps = -(-n // len(choices))
    out = np.tile(np.asarray(choices), reps)[:n]
    rng.shuffle(out)
    return out


def box_queries(rng, n_queries, widths=(1.0, 2.0, 5.0, 10.0, 20.0, 40.0)):
    """Selectivity mix: city-scale through continent-scale boxes, height
    half the width, corner uniform over the world."""
    out = []
    for w in balanced(rng, widths, n_queries):
        w = float(w)
        h = w / 2
        qx = rng.uniform(-175, 175 - w)
        qy = rng.uniform(-85, 85 - h)
        out.append((qx, qy, qx + w, qy + h))
    return out


def time_windows(rng, n_queries, t0, span_ms, hours=(6, 24, 72, 168, 24 * 14)):
    """Windows of the given lengths, start uniform over the span, bounds
    on whole seconds (the device keeps time offsets in seconds)."""
    out = []
    for h in balanced(rng, hours, n_queries):
        dur_ms = int(h) * 3_600_000
        start = int(t0 + rng.integers(0, span_ms - dur_ms)) // 1000 * 1000
        out.append((start, start + dur_ms))
    return out
