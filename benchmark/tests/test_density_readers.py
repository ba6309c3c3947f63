"""The eight readers of PR 33 and ``kernels/density.py`` over hand-made
views and calls: the ``density`` root's ``dispatch`` and ``agg`` spans with
the new counter ``full`` and without it (the parent's shape: the readers
that need it give None, the others read what they read), and the family's
roofline (bound by bytes; pad slots not counted)."""

import json
import os

import pytest

from harness import instrument
from kernels import density as fam
from layer_metrics import (agg_pull_ms, agg_wait_ms, density_full_ms, density_full_pct,
                           density_roofline, density_rows_per_s, density_slot_us,
                           density_useful_pct)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PEAKS = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]
BLOCK = 16384


def _span(i, trace, root, name, dur_ms, parent=None, **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True, device=None, calls=()):
    """Three ``density`` roots (a zoom-8 tile of 3 blocks in 32 slots, a
    zoom-1 tile of 1,500 in 2,048, a zoom-0 tile past the ladder: 8,192 of
    8,192) and one ``query`` root whose dispatch no reader here may count;
    roots listed twice, as the harness lists them."""
    spans = []
    tiles = ((3, 32, 0.4, 0.1), (1500, 2048, 22.0, 0.2), (8192, 8192, 90.0, 0.3))
    for k, (blocks, slots, wait, pull) in enumerate(tiles):
        base = 10 * (k + 1)
        new_d = {"full": int(blocks > 4096)} if counted else {}
        root = _span(base, base, "density", "density", wait + pull + 1.0, requests=1)
        spans += [root, dict(root),
                  _span(base + 1, base, "density", "plan", 0.5, parent=base),
                  _span(base + 2, base, "density", "dispatch", 0.4, parent=base, blocks=blocks,
                        slots=slots, spans_reused=1,
                        segments={"prune": 0.1e-3, "enqueue": 0.3e-3}, **new_d),
                  _span(base + 3, base, "density", "agg", wait + pull, parent=base,
                        segments={"wait": wait / 1e3, "pull": pull / 1e3})]
    q = _span(50, 50, "query", "query", 2.0)
    spans += [q, dict(q),
              _span(51, 50, "query", "dispatch", 0.5, parent=50, blocks=1, slots=32,
                    segments={"prune": 0.1e-3, "enqueue": 0.3e-3},
                    **({"full": 1} if counted else {}))]
    return {"workload": "osm-gpx.heatmap", "spans": spans, "device": device,
            "trace_t": (100.0, 103.0) if device else None,
            "kernel_calls": {"density": list(calls)}, "kernels": instrument.kernel_families(HERE),
            "peaks": lambda: PEAKS, "client": {"query_ms": [], "between_s": []}}


def _call(t, blocks, slots):
    return {"kind": "block_density", "t": t, "slots": slots, "blocks": blocks,
            "block_rows": BLOCK, "n_cols": 2, "width": 256, "height": 256}


def test_wait_and_pull_are_the_medians_of_the_agg_segments():
    for counted in (True, False):  # the parent marks the segments too
        assert agg_wait_ms.read(_view(counted)) == pytest.approx(22.0)
        assert agg_pull_ms.read(_view(counted)) == pytest.approx(0.2)


def test_useful_share_is_blocks_over_slots_under_density_roots_only():
    want = 100.0 * (3 + 1500 + 8192) / (32 + 2048 + 8192)
    assert density_useful_pct.read(_view()) == pytest.approx(want)
    assert density_useful_pct.read(_view(counted=False)) == pytest.approx(want)


def test_full_share_counts_the_whole_table_shape_and_is_none_on_the_parent():
    assert density_full_pct.read(_view()) == pytest.approx(100.0 / 3)
    assert density_full_pct.read(_view(counted=False)) is None


def test_full_ms_is_the_whole_table_tiles_root_and_is_none_on_the_parent():
    assert density_full_ms.read(_view()) == pytest.approx(90.0 + 0.3 + 1.0)
    assert density_full_ms.read(_view(counted=False)) is None
    laddered = _view()
    for s in laddered["spans"]:
        if s["name"] == "dispatch":
            s["attrs"]["full"] = 0
    assert density_full_ms.read(laddered) is None and density_full_pct.read(laddered) == 0.0


def test_nothing_to_read_is_none():
    empty = {"workload": "gdelt.dashboard", "spans": [], "device": None, "trace_t": None,
             "kernel_calls": {}, "kernels": instrument.kernel_families(HERE),
             "peaks": lambda: PEAKS, "client": {"query_ms": [], "between_s": []}}
    for reader in (agg_wait_ms, agg_pull_ms, density_useful_pct, density_full_pct,
                   density_full_ms, density_roofline, density_rows_per_s, density_slot_us):
        assert reader.read(empty) is None, reader.__name__
    # a trace without the family's device op: no share, no rate
    other = {"ops": {"geomesa_block_scan.1 s32[32,4,128]": 0.01}}
    view = _view(device=other, calls=[_call(101.0, 3, 32)])
    assert density_roofline.read(view) is None and density_rows_per_s.read(view) is None
    assert density_slot_us.read(view) is None


def test_density_bytes_hand_worked():
    # one zoom-0 tile of the new cell: all 8,192 blocks of 16,384 rows, x and y
    # as f32, and the 256 x 256 f32 grid written once
    assert fam.density_bytes(8192, BLOCK, 2, 256, 256) == (1 << 27) * 8 + (1 << 18)
    assert fam.density_bytes(0, BLOCK, 2, 256, 256) == 1 << 18


def test_the_family_is_bound_by_bytes_and_pad_slots_are_not_counted():
    got = fam.roofline([_call(0.0, 8192, 8192)], PEAKS)
    assert got["bound"] == "bytes" and got["rows"] == 1 << 27
    assert got["flops"] == 16 << 27  # the question's 16 a row, not the matmul's 131,072
    assert got["least_s"] == pytest.approx(((1 << 30) + (1 << 18)) / 819e9)
    assert 1.3e-3 < got["least_s"] < 1.32e-3
    # 3 real blocks in a bucket of 32 and in one of 4,096 need the same
    a, b = fam.roofline([_call(0.0, 3, 32)], PEAKS), fam.roofline([_call(0.0, 3, 4096)], PEAKS)
    assert a == b and a["bytes"] == 3 * BLOCK * 8 + (1 << 18)


def test_share_and_rate_read_the_calls_inside_the_traced_window():
    device = {"ops": {"geomesa_density.3 f32[256,256]": 0.100,
                      "geomesa_block_scan.1 s32[32,4,128]": 0.010}}
    calls = [_call(99.0, 8192, 8192),  # before the profiler ran: not counted
             _call(101.0, 8192, 8192), _call(102.0, 3, 32)]
    view = _view(device=device, calls=calls)
    least = fam.roofline(calls[1:], PEAKS)["least_s"]
    assert density_roofline.read(view) == pytest.approx(100.0 * least / 0.100)
    assert density_roofline.read(view) < 2.0  # 1.3 ms of HBM against 100 ms of MXU
    assert density_rows_per_s.read(view) == pytest.approx((8192 + 3) * BLOCK / 0.100)
    assert density_slot_us.read(view) == pytest.approx(1e6 * 0.100 / (8192 + 32))  # 12.2 us


def test_install_records_a_call_beside_the_scan_family(monkeypatch):
    """Two families wrap ``pad_bids``: each keeps its own count and neither
    changes what the function returns."""
    import numpy as np

    from geomesa_tpu.scan import aggregations
    from geomesa_tpu.scan import block_kernels as bk

    seen = []
    monkeypatch.setattr(aggregations, "block_density",
                        lambda cols3, bids, *a, **kw: seen.append(len(bids)) or "grid")
    rec = instrument.Recorder(HERE)
    try:
        bids, n_real = bk.pad_bids(np.arange(5), 100, pad=-1)
        assert n_real == 5 and len(bids) == 32 and list(bids[:6]) == [0, 1, 2, 3, 4, -1]
        cols3 = (np.zeros((100, 128, 128), np.float32),) * 2
        out = aggregations.block_density(cols3, bids, None, None, None, col_names=("x", "y"),
                                         width=256, height=128)
    finally:
        rec.close()
    assert out == "grid" and seen == [32]
    (call,) = rec.calls["density"]
    assert {k: call[k] for k in ("kind", "slots", "blocks", "block_rows", "n_cols", "width",
                                 "height")} == {
        "kind": "block_density", "slots": 32, "blocks": 5, "block_rows": BLOCK, "n_cols": 2,
        "width": 256, "height": 128}
    assert rec.calls["scan"] == []
    assert bk.pad_bids.__module__ == "geomesa_tpu.scan.block_kernels"  # unwrapped again


def test_the_new_metrics_are_listed_for_the_new_cell():
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in ("density_rows_per_s", "agg_wait_ms", "agg_pull_ms", "density_useful_pct",
                 "density_full_pct", "density_full_ms"):
        assert by[name]["workloads"] == ["osm-gpx.heatmap"], name
    for name in ("density_roofline", "density_slot_us"):  # one-chip cells that run the family
        assert by[name]["workloads"] == ["osm-gpx.heatmap", "gdelt.analyst"], name
    assert by["density_roofline"]["unit"] == "%" and by["density_roofline"]["layer"] == "kernels"
    listed = [m["name"] for m in bench["per_layer"] if "osm-gpx.heatmap" in m["workloads"]]
    assert len(listed) == 29 and "scan_roofline" not in listed
    e2e = [m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or "osm-gpx.heatmap" in m["workloads"]]
    assert e2e == ["queries_per_s", "query_p95_ms", "setup_s"]
