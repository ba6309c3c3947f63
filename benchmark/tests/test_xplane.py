import os

import pytest

from harness import xplane

DATA = os.path.join(os.path.dirname(__file__), "data", "small_tpu.xplane.pb")


def test_union_and_gaps():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8), (20, 21)])
    assert merged == [[0, 3], [5, 8], [20, 21]]
    assert xplane.gaps(merged, 0, 30) == [(3, 5), (8, 20), (21, 30)]
    assert xplane.gaps([], 0, 4) == [(0, 4)]


def test_reduce_on_hand_made_events():
    ev = {
        "device": {"/device:TPU:0": [("fusion.1", 100.0, 50.0), ("scan_kernel", 120.0, 100.0),
                                     ("fusion.1", 400.0, 100.0), ("late", 2000.0, 10.0)]},
        "host": [("bench:window", 0.0, 1000.0), ("bench:plan", 220.0, 150.0),
                 ("bench:gather", 500.0, 400.0), ("bench:http.get", 200.0, 900.0)],
    }
    r = xplane.reduce(ev)
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 220] and [400, 500]; the event at 2000 is outside the window
    assert r["busy_s"] == pytest.approx(220e-9)
    assert r["ops"]["fusion.1"] == pytest.approx(150e-9) and "late" not in r["ops"]
    assert xplane.family_seconds(r["ops"], "scan") == pytest.approx(100e-9)
    # gaps: 500..1000 (gather covers 400 of it), 220..400 (plan covers 150), 0..100
    assert [g[0] for g in r["idle_gaps"]] == ["bench:gather", "bench:plan",
                                              "host: no bench annotation"]
    assert [round(g[1] * 1e9) for g in r["idle_gaps"]] == [500, 180, 100]


def test_two_chips_are_averaged():
    ev = {"device": {"/device:TPU:0": [("a", 0.0, 100.0)], "/device:TPU:1": [("a", 0.0, 50.0)]},
          "host": [("bench:window", 0.0, 200.0)]}
    r = xplane.reduce(ev)
    assert r["busy_s"] == pytest.approx(75e-9) and r["ops"]["a"] == pytest.approx(75e-9)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace kept")
def test_recorded_tpu_trace():
    """A short trace recorded on the v5e (a few dashboard requests)."""
    ev = xplane.load(DATA)
    assert list(ev["device"]) == ["/device:TPU:0"]
    r = xplane.reduce(ev)
    assert r["n_device_events"] > 0
    assert 0 < r["busy_s"] < r["window_s"]
    busy_by_sum = sum(r["ops"].values())
    assert r["busy_s"] <= busy_by_sum * (1 + 1e-9) or busy_by_sum > 0
    assert any(name.startswith("bench:") for name, _ in r["idle_gaps"])
