"""The readers and the kernel family PR 26 added for a mesh store, each
over hand-made spans, calls and device planes with known answers, and over
a view of a program whose mesh table marks nothing (the parent commit) or
of a store that is no mesh: None, never an error."""

import json
import os

import pytest

from harness import layers, xplane
from kernels import scan, scan_mesh
from layer_metrics import mesh_deal_ms, mesh_merge_ms, mesh_scan_roofline, shard_skew

PEAKS = json.load(open(os.path.join(os.path.dirname(__file__), "..", "peaks.json")))["TPU v5 lite"]


def _span(i, trace, root, name, parent=None, segments=None, **attrs):
    if segments:
        attrs["segments"] = {k: v / 1e3 for k, v in segments.items()}
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": 0.01, "self_s": 0.01, "attrs": attrs}


def mesh_view():
    """Two ``query_many`` roots (each listed twice, as the harness lists
    roots) and one ``query``. Root 10: a staging ``dispatch`` of two fused
    chunks, a nested member ``dispatch``, three ``scan`` spans. Root 20: one
    chunk, dealt evenly."""
    a = _span(10, 10, "query_many", "query_many", members=3)
    b = _span(20, 20, "query_many", "query_many", members=2)
    q = _span(30, 30, "query", "query")
    spans = [
        a, dict(a),
        _span(11, 10, "query_many", "dispatch", 10, dict(prune=5.0, deal=1.5, enqueue=2.0),
              blocks=60, slots=256, blocks_max=30, devices=4, groups=2),
        _span(12, 10, "query_many", "dispatch", 11, dict(prune=0.5, deal=0.5, enqueue=1.0),
              blocks=8, slots=128, blocks_max=2, devices=4),
        _span(13, 10, "query_many", "scan", 10, dict(wait=1.0, pull=0.5, bits=2.0, merge=0.75),
              member=0, group=2),
        _span(14, 10, "query_many", "scan", 10, dict(bits=1.0, merge=0.25), member=1),
        _span(15, 10, "query_many", "scan", 10, member=2),  # no candidate block: nothing decoded
        b, dict(b),
        _span(21, 20, "query_many", "dispatch", 20, dict(prune=2.0, deal=1.0, enqueue=1.0),
              blocks=40, slots=128, blocks_max=10, devices=4, groups=1),
        _span(22, 20, "query_many", "scan", 20, dict(wait=1.0, pull=0.5, bits=1.0, merge=0.5),
              member=0, group=2),
        q, dict(q),
        _span(31, 30, "query", "dispatch", 30, dict(prune=1.0, deal=0.25, enqueue=1.0),
              blocks=6, slots=128, blocks_max=3, devices=4),
        _span(32, 30, "query", "dispatch", 30, dict(prune=1.0)),  # an empty answer: no deal
        _span(33, 30, "query", "scan", 30, dict(wait=0.5, pull=0.5, bits=1.0, merge=9.0)),
    ]
    return {"workload": "gdelt-mesh4.analyst", "spans": spans, "device": None, "trace_t": None,
            "kernels": {"scan_mesh": {}}}


def one_chip_view():
    """What a one-chip store (or the parent's mesh table) gives: the same
    spans without ``deal``, ``merge``, ``devices`` or ``blocks_max``."""
    v = mesh_view()
    for s in v["spans"]:
        a = s["attrs"]
        for k in ("devices", "blocks_max"):
            a.pop(k, None)
        for k in ("deal", "merge"):
            a.get("segments", {}).pop(k, None)
    return v


def test_deal_and_merge_are_summed_a_query_many_root():
    v = mesh_view()
    # root 10: 1.5 + 0.5 (the nested dispatch too); root 20: 1.0; the query root is not read
    assert mesh_deal_ms.read(v) == pytest.approx((2.0 + 1.0) / 2)
    # root 10: 0.75 + 0.25; root 20: 0.5
    assert mesh_merge_ms.read(v) == pytest.approx((1.0 + 0.5) / 2)


def test_shard_skew_is_the_fullest_device_over_an_even_share():
    # 30*4/60 = 2.0, 2*4/8 = 1.0, 10*4/40 = 1.0, 3*4/6 = 2.0; the span without blocks is no sample
    assert shard_skew.read(mesh_view()) == pytest.approx(1.5)


@pytest.mark.parametrize("reader", [mesh_deal_ms, mesh_merge_ms, shard_skew, mesh_scan_roofline])
def test_nothing_to_read_is_none(reader):
    assert reader.read(one_chip_view()) is None
    assert reader.read({"workload": "x", "spans": [], "device": None, "trace_t": None,
                        "kernels": {"scan_mesh": {}}}) is None


CALLS = [
    # a per-query z3 scan: 40 candidate blocks over four devices, four columns
    {"kind": "mesh_scan", "t": 1.0, "devices": 4, "slots": 128, "blocks": 40,
     "block_rows": 16384, "n_cols": 4},
    # a fused chunk: 400 candidates in 4 x 256 slots
    {"kind": "mesh_scan_multi", "t": 2.0, "devices": 4, "slots": 1024, "blocks": 400,
     "block_rows": 16384, "n_cols": 4},
]


def test_roofline_is_scan_arithmetic_per_chip():
    got = scan_mesh.roofline(CALLS, PEAKS)
    whole = scan.roofline(CALLS, PEAKS)
    assert got["bytes"] == whole["bytes"] == 440 * scan.scan_bytes(1, 16384, 4)
    assert got["flops"] == whole["flops"] and got["bound"] == "bytes"
    assert got["least_s"] == pytest.approx(whole["least_s"] / 4)
    assert scan_mesh.roofline([], PEAKS)["least_s"] == 0.0


def test_share_on_four_device_planes():
    """Every chip streams a quarter of the bytes; the trace shows each
    chip's scan ops taking ten times a quarter's least time: 10%."""
    per_chip_s = scan.roofline(CALLS, PEAKS)["least_s"] / 4
    ns = per_chip_s * 10 * 1e9
    device = {f"/device:TPU:{d}": [("geomesa_block_scan s32[32,4,128]", 1000.0, ns * 0.25),
                                    ("geomesa_block_scan_multi s32[256,4,128]", 1e6, ns * 0.75),
                                    ("psum.1 f32[256,256]", 2e6, 500.0)]
              for d in range(4)}
    reduced = xplane.reduce({"device": device, "host": [("bench:window", 0.0, 1e12)]})
    with open(os.path.join(os.path.dirname(__file__), "..", "kernels", "scan_mesh.json")) as fh:
        family = json.load(fh)
    view = {"device": reduced, "trace_t": (0.0, 10.0), "kernels": {"scan_mesh": family},
            "kernel_calls": {"scan_mesh": CALLS + [dict(CALLS[0], t=11.0)]},  # one after the trace
            "peaks": lambda: PEAKS}
    assert layers.roofline_share(view, "scan_mesh") == pytest.approx(10.0)
    assert mesh_scan_roofline.read(view) == pytest.approx(10.0)
    # one chip doing all the work in the same time: the other three planes idle, same share of
    # the time summed over the planes
    lone = {"/device:TPU:0": [("geomesa_block_scan s32[32,4,128]", 0.0, 4 * ns)],
            **{f"/device:TPU:{d}": [] for d in (1, 2, 3)}}
    view["device"] = xplane.reduce({"device": lone, "host": [("bench:window", 0.0, 1e12)]})
    assert mesh_scan_roofline.read(view) == pytest.approx(10.0)


class _Rec:
    """What ``instrument.Recorder`` gives a family's ``install``."""

    def __init__(self):
        import contextlib

        self.calls, self._undo = {}, []
        self.annotation = lambda name: contextlib.nullcontext()

    def patch(self, owner, attr, make):
        fn = getattr(owner, attr)
        self._undo.append((owner, attr, fn))
        setattr(owner, attr, make(fn))

    def close(self):
        for owner, attr, fn in reversed(self._undo):
            setattr(owner, attr, fn)


def test_install_records_the_mesh_tables_scan_dispatches():
    """A 2-device mesh store under the recorder: one record a per-query
    scan and a fused chunk, real candidates over both devices."""
    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        pytest.skip("one device: XLA_FLAGS=--xla_force_host_platform_device_count=2")
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.parallel import make_mesh
    from geomesa_tpu.sft import FeatureType

    with open(os.path.join(os.path.dirname(__file__), "..", "kernels", "scan_mesh.json")) as fh:
        family = json.load(fh)
    n = 1 << 15
    rng = np.random.default_rng(7)
    sft = FeatureType.from_spec("p", "dtg:Date,*geom:Point:srid=4326")
    ds = DataStore(mesh=make_mesh(2), tile=4096)
    ds.create_schema(sft)
    t0 = int(np.datetime64("2024-01-01", "ms").astype(np.int64))
    ds.write("p", FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), {
        "dtg": t0 + np.sort(rng.integers(0, 86_400_000, n)),
        "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))}), check_ids=False)
    rec = _Rec()
    scan_mesh.install(rec, family)
    try:
        ds.query("p", "bbox(geom, -100, -50, 100, 50)")
        boxes = [f"bbox(geom, {x}, -40, {x + 30}, 40)" for x in range(-170, 130, 20)]
        ds.query_many("p", boxes)
    finally:
        rec.close()
    calls = rec.calls["scan_mesh"]
    kinds = [c["kind"] for c in calls]
    assert kinds[0] == "mesh_scan" and "mesh_scan_multi" in kinds
    table = ds.table("p", "z2")
    for c in calls:
        assert c["devices"] == 2 and c["block_rows"] == table.block and c["n_cols"] == 2
        assert 0 < c["blocks"] <= c["slots"] and c["slots"] % 2 == 0
    assert calls[0]["blocks"] <= table.n_blocks
