"""The self-tuning controller tier (geomesa_tpu.tuning, docs/tuning.md).

Four pinned surfaces, per ISSUE 19:

1. **Gate differentials** — the three pre-existing measured-cost gates
   (tile compose gate, adaptive join gate, standing match gate)
   migrated onto tuning/primitives.py; each test replays
   the PRE-migration arithmetic inline as a reference implementation
   and asserts the migrated gate produces the identical DECISION
   sequence over seeded inputs (decisions, not internal floats: the
   tile gate's old nudge-form EWMA is algebraically equal to the
   canonical blend but may differ in the last ulp).
2. **Disarmed bit-identity** — a store with a disarmed manager behaves
   bit-identically to a store without the tier: same plans, same
   explains, no hooks installed, no knob writes, zero pulses.
3. **The three legs armed** — reweighting converges with hysteresis,
   knob controllers hold/step/collapse within bounds, burn shedding
   engages before the queue is full and releases.
4. **Persistence** — learned state survives close()/reopen; a corrupt
   state file means re-learning, never failing.
"""

import json
import time

import numpy as np
import pytest

from geomesa_tpu import conf
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.obs.accuracy import EstimateAccuracy
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.tuning.burnshed import BurnShed
from geomesa_tpu.tuning.controllers import CONTROLLER_SPECS, KnobController
from geomesa_tpu.tuning.primitives import (
    CostEwma,
    ProbeGate,
    ewma_step,
)
from geomesa_tpu.tuning.reweight import IndexReweighter

DAY = 86400_000
Q = "bbox(geom, -10, -10, 10, 10)"

_TUNED_KNOBS = (
    "CACHE_MIN_COST",
    "STREAM_FOLD_SLICE_ROWS",
    "STREAM_CHUNK_ROWS",
)


@pytest.fixture(autouse=True)
def _clean_tuned_state():
    """Armed controllers write through GLOBAL conf; every test leaves
    the steered knobs as it found them."""
    yield
    for name in _TUNED_KNOBS:
        getattr(conf, name).clear()


def _mkstore(metrics=None, cache=None, n=512, seed=7):
    sft = FeatureType.from_spec(
        "ev", "kind:String:index=true,dtg:Date,*geom:Point:srid=4326"
    )
    ds = DataStore(tile=64, metrics=metrics, cache=cache)
    ds.create_schema(sft)
    rng = np.random.default_rng(seed)
    t0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
    ds.write("ev", FeatureCollection.from_columns(
        sft, [str(i) for i in range(n)],
        {
            "kind": np.array(["a", "b", "c"])[rng.integers(0, 3, n)],
            "dtg": t0 + rng.integers(0, 20 * DAY, n),
            "geom": (rng.uniform(-60, 60, n), rng.uniform(-45, 45, n)),
        },
    ))
    return ds


# -- 1. shared primitives + the four gate differentials -------------------


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


# -- 2. disarmed == today, bit-identical ---------------------------------


def test_disarmed_is_bit_identical():
    plain = _mkstore(metrics=MetricsRegistry())
    tuned = _mkstore(metrics=MetricsRegistry())
    mgr = tuned.attach_tuning()  # geomesa.tuning.enabled defaults false
    assert mgr.enabled is False
    # no hooks installed
    assert tuned.planner.reweighter is None
    knobs_before = {
        s.knob: conf.REGISTRY[s.knob].get() for s in CONTROLLER_SPECS
    }
    for f in (Q, "kind = 'a'", "bbox(geom, 0, 0, 50, 40) AND kind = 'b'"):
        e1, e2 = Explainer(), Explainer()
        r1 = plain.query("ev", f, explain=e1)
        r2 = tuned.query("ev", f, explain=e2)
        np.testing.assert_array_equal(np.asarray(r1.ids), np.asarray(r2.ids))
        # identical traces modulo wall-clock timing lines
        strip = lambda exp: [l for l in exp.lines if "ms" not in l]
        assert strip(e1) == strip(e2)
    # the disarmed manager never pulsed, never wrote a knob
    assert mgr.report()["pulses"] == 0
    assert tuned.metrics.counter_value("geomesa.tuning.pulse") == 0
    knobs_after = {
        s.knob: conf.REGISTRY[s.knob].get() for s in CONTROLLER_SPECS
    }
    assert knobs_after == knobs_before
    plain.close()
    tuned.close()


def test_rearm_and_disarm_restore_hooks():
    ds = _mkstore(metrics=MetricsRegistry())
    sched = ds.serve()
    try:
        armed = ds.attach_tuning(enabled=True)
        assert ds.planner.reweighter is armed.reweighter
        assert sched.burn_gate is armed.burnshed
        disarmed = ds.attach_tuning(enabled=False)
        assert ds.tuning is disarmed
        assert ds.planner.reweighter is None
        assert sched.burn_gate is None
    finally:
        sched.close()
        ds.close()


# -- 3a. plan-feedback reweighting: convergence + hysteresis --------------


def _feed(acc, n, estimated, actual, index="z2"):
    for _ in range(n):
        acc.record("ev", index, estimated, actual)


def test_reweighter_convergence_and_hysteresis():
    acc = EstimateAccuracy()
    rw = IndexReweighter(acc, max_adjust=4.0, deadband=2.0, step=0.5,
                         min_count=8)
    # below min_count: too few samples to indict
    _feed(acc, 7, 29, 9)  # error factor 3.0
    assert rw.pulse() == [] and rw.factor("ev", "z2") == 1.0
    # chronic over-selector: p90 ~3x >= deadband -> multiplicative
    # growth, clamped at max_adjust
    _feed(acc, 3, 29, 9)
    trail = []
    for _ in range(6):
        for d in rw.pulse():
            trail.append(d["to"])
    assert trail == [1.5, 2.25, 3.375, 4.0]  # capped; then no-op pulses
    assert rw.factor("ev", "z2") == 4.0
    d = rw.pulse()
    assert d == []  # parked at the clamp: the trail records no non-moves
    # hold band: p90 lands between release (1.5) and deadband (2.0) —
    # the factor parks (no flapping either direction)
    _feed(acc, 150, 5, 4)   # error factor 1.2
    _feed(acc, 50, 7, 4)    # error factor 1.6
    p90 = [
        r for r in acc.report()["indexes"] if r["index"] == "z2"
    ][0]["p90_error"]
    assert 1.5 < p90 < 2.0, p90
    assert rw.pulse() == [] and rw.factor("ev", "z2") == 4.0
    # recovery: honest samples drive p90 to ~1.0 -> decay back to 1.0
    _feed(acc, 2000, 9, 9)  # error factor 1.0
    steps = []
    for _ in range(8):
        for d in rw.pulse():
            steps.append(d["to"])
    # decision records round to 4 decimals; the internal factor is exact
    assert steps == [2.6667, 1.7778, 1.1852, 1.0]
    assert rw.factor("ev", "z2") == 1.0
    assert rw.factors() == {}  # fully recovered keys leave the table


def test_reweight_factor_shows_in_plan_explain():
    ds = _mkstore(metrics=MetricsRegistry())
    try:
        mgr = ds.attach_tuning(enabled=True)
        e1 = Explainer()
        ds.query("ev", Q, explain=e1)
        [strat] = [l for l in e1.lines if l.strip().startswith("Strategy:")]
        chosen = strat.split()[1]
        assert not any("estimate-accuracy reweight" in l for l in e1.lines)
        mgr.reweighter.restore([["ev", chosen, 2.0]])
        e2 = Explainer()
        ds.query("ev", Q, explain=e2)
        assert any(
            f"Index {chosen}: estimate-accuracy reweight x2.00" in l
            for l in e2.lines
        )
    finally:
        ds.close()


# -- 3b. knob controllers ------------------------------------------------


def _spec(name):
    return next(s for s in CONTROLLER_SPECS if s.name == name)


def test_knob_controller_steps_flips_holds_and_clamps():
    spec = _spec("fold_slice_rows")  # lower-is-better, integral
    ctl = KnobController(spec)
    width = spec.hi - spec.lo
    assert ctl.propose(65536.0, 1.0) is None        # first reading seeds
    # improving: keep direction (relax_dir=-1), step down, clamp at lo
    assert ctl.propose(65536.0, 0.5) == spec.lo
    # mildly worse (outside deadband, not collapsed): reverse direction
    nxt = ctl.propose(spec.lo, 0.56)
    assert nxt == spec.lo + 0.25 * width
    assert nxt == float(int(nxt))                   # integral knob rounds
    # within the deadband: hold
    assert ctl.propose(nxt, 0.57) is None
    # at a clamp, a proposal that lands back on current is suppressed:
    # improving at lo keeps dir=-1, which clamps to lo == current
    lo_ctl = KnobController(spec)
    assert lo_ctl.propose(spec.lo, 100.0) is None
    assert lo_ctl.propose(spec.lo, 10.0) is None


def test_knob_controller_collapse_relaxes():
    spec = _spec("cache_min_cost")  # higher-is-better, relax_dir=-1
    ctl = KnobController(spec)
    assert ctl.propose(0.04, 100.0) is None
    assert ctl.propose(0.04, 101.0) is None  # deadband: steady is healthy
    # collapse: reading far below best -> step in the declared relax
    # direction (threshold down), not the hill-climb guess
    nxt = ctl.propose(0.04, 10.0)
    assert nxt == pytest.approx(0.04 - 0.25 * (spec.hi - spec.lo))
    # snapshot/restore round-trips; junk direction is rejected
    snap = ctl.snapshot()
    other = KnobController(spec)
    other.restore(snap)
    assert other.snapshot() == snap
    other.restore({"dir": 5})
    assert other.snapshot()["dir"] == snap["dir"]


def test_manager_pulse_steers_cache_min_cost(tmp_path):
    reg = MetricsRegistry()
    ds = _mkstore(metrics=reg, cache=True)
    try:
        conf.CACHE_MIN_COST.set(0.04)
        mgr = ds.attach_tuning(enabled=True, interval=1)
        reg.counter("geomesa.cache.hit", 100)
        assert mgr.pulse() == []  # seeds the counter baseline
        reg.counter("geomesa.cache.hit", 100)
        assert mgr.pulse() == []  # first delta seeds the controller
        reg.counter("geomesa.cache.hit", 10)  # hits collapsed
        decisions = mgr.pulse()
        [d] = [d for d in decisions if d["controller"] == "cache_min_cost"]
        assert d["knob"] == "geomesa.cache.min.cost"
        assert d["from"] == pytest.approx(0.04)
        assert d["to"] == pytest.approx(0.0275)
        # actuation is real: the knob AND the live cache conf moved
        assert conf.CACHE_MIN_COST.get() == pytest.approx(0.0275)
        assert ds.cache.result.conf.min_cost_s == pytest.approx(0.0275)
        assert reg.counter_value("geomesa.tuning.adjust") >= 1
        assert reg.counter_value("geomesa.tuning.pulse") == 3
        report = mgr.report()
        assert report["pulses"] == 3
        assert d in report["decisions"]
    finally:
        ds.close()


# -- 3c. SLO-burn admission shedding --------------------------------------


class _StubSlo:
    def __init__(self):
        self.burn = 0.0

    def report(self, now=None):
        return {"objectives": [
            {"objective": "query_p99", "burn_rate": self.burn},
        ]}


class _StubStore:
    def __init__(self, weights):
        class _T:
            def __init__(self, w):
                self._w = w

            def weights(self):
                return dict(self._w)

        class _S:
            pass

        self.slo = _StubSlo()
        self.scheduler = _S()
        self.scheduler.tenants = _T(weights)


def test_burn_shed_hysteresis_and_weight_tiers():
    store = _StubStore({"gold": 8.0, "bronze": 1.0})
    gate = BurnShed(store, threshold=2.0, release=1.0)
    assert gate.should_shed("bronze", now=1.0) is None  # no burn
    store.slo.burn = 3.0
    why = gate.should_shed("bronze", now=2.0)
    assert why is not None and "slo burn 3.00x" in why
    assert gate.should_shed("gold", now=2.0) is None  # top weight admits
    # unseen tenants (and the anonymous pool) get the default weight,
    # which sits below gold's: they shed too
    assert gate.should_shed("nobody", now=2.0) is not None
    assert gate.should_shed(None, now=2.0) is not None
    # hysteresis: between release and threshold an ENGAGED gate stays
    # engaged...
    store.slo.burn = 1.5
    assert gate.should_shed("bronze", now=3.0) is not None
    # ...releases only at/below release...
    store.slo.burn = 0.9
    assert gate.should_shed("bronze", now=4.0) is None
    # ...and a RELEASED gate does not re-engage in the same band
    store.slo.burn = 1.5
    assert gate.should_shed("bronze", now=5.0) is None


def test_burn_shed_uniform_weights_shed_nothing():
    store = _StubStore({"a": 1.0, "b": 1.0})
    store.slo.burn = 50.0
    gate = BurnShed(store, threshold=2.0)
    assert gate.should_shed("a", now=1.0) is None
    assert gate.should_shed("b", now=1.0) is None
    assert gate.report()["engaged"] is True


def test_burn_shed_engages_before_queue_full_and_releases():
    from geomesa_tpu.obs.slo import SloTracker
    from geomesa_tpu.serving import (
        QueryScheduler,
        ServingConfig,
        ServingRejected,
    )
    from geomesa_tpu.serving.tenancy import TenantRegistry

    reg = MetricsRegistry()
    ds = _mkstore(metrics=reg)
    # a short real window so the burn decays within the test
    ds.slo = SloTracker(window_s=0.6)
    tenants = TenantRegistry(metrics=reg)
    tenants.configure("gold", weight=8.0)
    tenants.configure("bronze", weight=1.0)
    # unstarted scheduler: queue states stay deterministic
    sched = QueryScheduler(
        ds, ServingConfig(queue_max=64), metrics=reg, tenants=tenants
    )
    ds.scheduler = sched
    try:
        mgr = ds.attach_tuning(enabled=True)
        assert sched.burn_gate is mgr.burnshed
        # p99 objective burning hard: every observation blows the budget
        for _ in range(60):
            ds.slo.observe("geomesa.query.scan", 60.0)
        mgr.pulse()
        assert mgr.burnshed.report()["engaged"]
        # the queue is EMPTY (far from queue_max=64), yet low-priority
        # work sheds — the gate fires before physical pressure exists
        shed = sched.submit("ev", Q, block=False, tenant="bronze")
        with pytest.raises(ServingRejected, match="slo burn"):
            shed.result(timeout=5)
        assert reg.counter_value("geomesa.tuning.shed") == 1
        # top-weight work admits through the same burn
        kept = sched.submit("ev", Q, block=False, tenant="gold")
        assert not kept.done()
        # burn decays past release as the window slides empty -> released
        time.sleep(1.0)
        mgr.pulse()
        assert not mgr.burnshed.report()["engaged"]
        ok = sched.submit("ev", Q, block=False, tenant="bronze")
        assert not ok.done()  # admitted (queued; scheduler never started)
        assert reg.counter_value("geomesa.tuning.shed") == 1
    finally:
        sched.close()
        ds.close()


# -- 4. persistence: learned state survives close()/reopen ----------------


def test_state_survives_close_and_reopen(tmp_path):
    path = str(tmp_path / "_tuning.json")
    ds1 = _mkstore(metrics=MetricsRegistry())
    mgr1 = ds1.attach_tuning(enabled=True, state_path=path)
    mgr1.reweighter.restore([["ev", "z2", 2.25]])
    mgr1.controllers["cache_min_cost"].restore(
        {"last": 5.0, "best": 9.0, "dir": 1}
    )
    conf.CACHE_MIN_COST.set(0.03)  # as if the controller had steered it
    ds1.close()  # saves
    state = json.load(open(path))
    assert state["factors"] == [["ev", "z2", 2.25]]
    conf.CACHE_MIN_COST.clear()  # simulate a fresh process
    ds2 = _mkstore(metrics=MetricsRegistry())
    mgr2 = ds2.attach_tuning(enabled=True, state_path=path)
    assert mgr2.reweighter.factor("ev", "z2") == 2.25
    assert mgr2.controllers["cache_min_cost"].snapshot() == {
        "last": 5.0, "best": 9.0, "dir": 1,
    }
    # tuned knob values re-applied: the reopened store starts from what
    # it learned, not from the defaults
    assert conf.CACHE_MIN_COST.get() == pytest.approx(0.03)
    ds2.close()


def test_corrupt_state_file_means_relearning_not_failing(tmp_path):
    path = tmp_path / "_tuning.json"
    path.write_text("{this is not json", encoding="utf-8")
    ds = _mkstore(metrics=MetricsRegistry())
    mgr = ds.attach_tuning(enabled=True, state_path=str(path))
    assert mgr.reweighter.factors() == {}
    assert mgr.pulse() == []  # fully operational
    ds.close()


# -- the ops surface ------------------------------------------------------


def test_tuning_report_shapes():
    ds = _mkstore(metrics=MetricsRegistry())
    try:
        bare = ds.tuning_report()
        assert bare["enabled"] is False
        mgr = ds.attach_tuning(enabled=True)
        report = ds.tuning_report()
        assert report["enabled"] is True
        assert report["interval"] == mgr.interval
        names = {row["name"] for row in report["controllers"]}
        assert names == {s.name for s in CONTROLLER_SPECS}
        for row in report["controllers"]:
            assert row["lo"] < row["hi"]
            assert row["knob"] in conf.REGISTRY
        assert report["burn"]["objective"] == "query_p99"
        assert report["plan_factors"] == {}
        assert report["decisions"] == []
    finally:
        ds.close()
