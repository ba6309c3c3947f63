"""The measured-cost gate primitives (geomesa_tpu.utils.costgate).

The three measured-cost gates (tile compose gate, adaptive join gate,
standing match gate) share utils/costgate.py; each differential test
replays the PRE-migration arithmetic inline as a reference
implementation and asserts the migrated gate produces the identical
DECISION sequence over seeded inputs (decisions, not internal floats:
the tile gate's old nudge-form EWMA is algebraically equal to the
canonical blend but may differ in the last ulp).
"""

import os

import numpy as np
import pytest

from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.utils.costgate import (
    CostEwma,
    ProbeGate,
    ewma_step,
)


# -- shared primitives + the gate differentials ---------------------------


def test_ewma_blend_matches_legacy_nudge_form():
    # the tile gate's old `prev + a*(s-prev)` and the canonical
    # `(1-a)*prev + a*s` are the same function; pin the equivalence the
    # migration leaned on
    rng = np.random.default_rng(3)
    blend, nudge = None, None
    for s in rng.uniform(1e-4, 2.0, 500):
        blend = ewma_step(blend, s)
        nudge = s if nudge is None else nudge + 0.25 * (s - nudge)
        assert blend == pytest.approx(nudge, rel=1e-12)


def test_probe_gate_explore_then_reprobe():
    g = ProbeGate(explore_min=3, reprobe_every=4)
    assert g.exploring
    for _ in range(3):
        g.note_trial()
    assert not g.exploring
    # every 4th blocked attempt re-probes, resetting the streak
    assert [g.block() for _ in range(9)] == [
        False, False, False, True, False, False, False, True, False
    ]


def test_cost_ewma_drops_non_positive_samples():
    e = CostEwma()
    assert e.value is None and e.value_or(7.5) == 7.5
    assert e.update_cost(1.0, 0) is None      # zero units: no signal
    assert e.update_cost(0.0, 10) is None     # zero seconds: no signal
    assert e.update_cost(2.0, 4) == 0.5       # first sample seeds
    assert e.value_or(7.5) == 0.5


class _LegacyTilesGate:
    """The pre-migration cache/tiles.py gate verbatim: nudge-form EWMAs,
    _compose_n explore counter, _gated re-probe counter."""

    _EXPLORE_MIN, _REPROBE_EVERY, _A = 6, 8, 0.25

    def __init__(self):
        self._scan = {}
        self._comp = {}
        self._n = {}
        self._gated = {}

    def note_scan(self, t, s):
        prev = self._scan.get(t)
        self._scan[t] = s if prev is None else prev + self._A * (s - prev)

    def note_compose(self, t, s):
        prev = self._comp.get(t)
        self._comp[t] = s if prev is None else prev + self._A * (s - prev)
        self._n[t] = self._n.get(t, 0) + 1

    def worth_composing(self, t):
        if self._n.get(t, 0) < self._EXPLORE_MIN:
            return True
        scan, comp = self._scan.get(t), self._comp.get(t)
        if scan is None or comp is None or comp <= scan:
            return True
        g = self._gated.get(t, 0) + 1
        if g >= self._REPROBE_EVERY:
            self._gated[t] = 0
            return True
        self._gated[t] = g
        return False


def test_tiles_gate_differential():
    from geomesa_tpu.cache.generations import GenerationTracker
    from geomesa_tpu.cache.tiles import TileAggregateCache, TileCacheConf

    cache = TileAggregateCache(
        TileCacheConf(), GenerationTracker(), metrics=MetricsRegistry()
    )
    legacy = _LegacyTilesGate()
    rng = np.random.default_rng(11)
    got, want = [], []
    for _ in range(400):
        t = ("a", "b")[rng.integers(0, 2)]
        op = rng.integers(0, 3)
        if op == 0:
            s = float(rng.uniform(0.2, 1.0))
            cache.note_scan(t, s)
            legacy.note_scan(t, s)
        elif op == 1:
            # composes sometimes costlier than scans so the gate trips
            s = float(rng.uniform(0.2, 2.0))
            cache._note_compose(t, s)
            legacy.note_compose(t, s)
        else:
            got.append((t, cache.worth_composing(t)))
            want.append((t, legacy.worth_composing(t)))
    assert got == want
    assert {d for _, d in got} == {True, False}  # both branches exercised


class _LegacyJoinGate:
    """The pre-migration sql/join.py _AdaptiveGate verbatim."""

    _A = 0.25

    def __init__(self):
        self._pip = None
        self._cls = None

    def update(self, kind, seconds, units):
        if units <= 0 or seconds <= 0:
            return
        per = seconds / units
        if kind == "pip_s":
            self._pip = (
                per if self._pip is None
                else (1.0 - self._A) * self._pip + self._A * per
            )
        else:
            self._cls = (
                per if self._cls is None
                else (1.0 - self._A) * self._cls + self._A * per
            )

    def pick(self, n_cand, n_edges, boundary_frac):
        pip = self._pip if self._pip is not None else 4e-9
        cls = self._cls if self._cls is not None else 2e-8
        plain = n_cand * n_edges * pip
        rast = n_cand * cls + boundary_frac * n_cand * n_edges * pip
        return "raster" if rast < plain else "exact"


def test_join_gate_differential():
    from geomesa_tpu.sql.join import _AdaptiveGate

    gate, legacy = _AdaptiveGate(), _LegacyJoinGate()
    rng = np.random.default_rng(13)
    got, want = [], []
    # cold-start picks first (priors), then measured
    for _ in range(5):
        args = (int(rng.integers(1, 10_000)), int(rng.integers(3, 400)),
                float(rng.uniform(0.0, 1.0)))
        got.append(gate.pick(*args))
        want.append(legacy.pick(*args))
    for _ in range(300):
        if rng.integers(0, 2):
            kind = ("pip_s", "cls_s")[rng.integers(0, 2)]
            # include the non-positive-sample guard in the replay
            seconds = float(rng.uniform(-0.1, 0.5))
            units = int(rng.integers(0, 1_000_000))
            gate.update(kind, seconds, units)
            legacy.update(kind, seconds, units)
        else:
            args = (int(rng.integers(1, 10_000)), int(rng.integers(3, 400)),
                    float(rng.uniform(0.0, 1.0)))
            got.append(gate.pick(*args))
            want.append(legacy.pick(*args))
    assert got == want
    assert set(got) == {"raster", "exact"}


class _LegacyMatchGate:
    """The pre-migration streaming/standing.py _MatchGate verbatim."""

    _A, _HOST_PRIOR = 0.25, 4e-9

    def __init__(self):
        self._host = None
        self._fused = None

    def update(self, kind, seconds, units):
        if units <= 0 or seconds <= 0:
            return
        per = seconds / units
        if kind == "host_s":
            self._host = (
                per if self._host is None
                else (1.0 - self._A) * self._host + self._A * per
            )
        else:
            self._fused = (
                per if self._fused is None
                else (1.0 - self._A) * self._fused + self._A * per
            )

    def pick(self, host_units, fused_units):
        if self._fused is None:
            return None
        host = self._host if self._host is not None else self._HOST_PRIOR
        return fused_units * self._fused < host_units * host


def test_standing_gate_differential():
    from geomesa_tpu.streaming.standing import _MatchGate

    gate, legacy = _MatchGate(), _LegacyMatchGate()
    rng = np.random.default_rng(17)
    hu = rng.integers(1, 1_000_000, 32).astype(np.float64)
    fu = rng.integers(1, 1_000_000, 32).astype(np.float64)
    # fused unmeasured: both sides say "run the probe"
    assert gate.pick(hu, fu) is None and legacy.pick(hu, fu) is None
    saw_mask = False
    for _ in range(200):
        kind = ("host_s", "fused_s")[rng.integers(0, 2)]
        seconds = float(rng.uniform(0.0, 0.2))
        units = int(rng.integers(0, 5_000_000))
        gate.update(kind, seconds, units)
        legacy.update(kind, seconds, units)
        a, b = gate.pick(hu, fu), legacy.pick(hu, fu)
        if a is None or b is None:
            assert a is None and b is None
        else:
            saw_mask = True
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert saw_mask


# -- the gates' module is a leaf ------------------------------------------


@pytest.mark.parametrize(
    "module",
    ["geomesa_tpu.cache.tiles", "geomesa_tpu.sql.join", "geomesa_tpu.streaming.standing"],
)
def test_a_gate_user_loads_no_tuning_tier(module):
    """A process that imports one of the three gates loads no
    ``geomesa_tpu.tuning`` package for their arithmetic."""
    import subprocess
    import sys

    code = (
        f"import sys, {module}\n"
        "print([m for m in sys.modules if m.startswith('geomesa_tpu.tuning')])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
