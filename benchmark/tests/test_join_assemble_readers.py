"""``layer_metrics/join_assemble_ns_pair.py`` and
``join_assemble_moved_pct.py`` (PR 52) over hand-made spans: three ``join``
roots as ``sql/join.py`` leaves them (four neighborhoods copied into the
answer's arrays, one borough whose rows are the answer, sixteen blocks that
answered nothing and so assembled nothing); the spans of PR 52's parent,
whose ``join.assemble`` carries ``members`` alone, give the first its
reading and the second None; no ``join`` root, or another cell's spans,
give both None."""

import json
import os

import pytest

from layer_metrics import join_assemble_moved_pct, join_assemble_ns_pair

CELL = "nyc-taxi.zone-join"
READERS = {"join_assemble_ns_pair": join_assemble_ns_pair,
           "join_assemble_moved_pct": join_assemble_moved_pct}


def _span(i, trace, name, dur_ms, parent=None, root="join", **attrs):
    return {"trace": trace, "root": root, "id": i, "parent": parent, "name": name, "t0": 0.0,
            "dur_s": dur_ms / 1e3, "self_s": dur_ms / 1e3, "attrs": attrs}


def _view(counted=True):
    nbhd = _span(10, 10, "join", 250.0, members=4, predicate="contains", pairs=3_000_000)
    boro = _span(20, 20, "join", 700.0, members=1, predicate="contains", pairs=15_000_000)
    none = _span(30, 30, "join", 40.0, members=16, predicate="contains", pairs=0)
    how = ({"pairs": 3_000_000, "sorted": 4, "moved": 3_000_000},
           {"pairs": 15_000_000, "sorted": 0, "moved": 0}) if counted else ({}, {})
    spans = [
        nbhd, dict(nbhd),  # roots listed twice, as the harness lists them
        _span(11, 10, "join.refine", 90.0, parent=10, member=0, rows=800_000, certain=700_000,
              uncertain=100_000),
        _span(12, 10, "join.assemble", 60.0, parent=10, members=4, **how[0]),
        boro, dict(boro),
        _span(21, 20, "join.host", 600.0, parent=20, members=1, points=1 << 24, decided=16_000_000,
              residue=777_216, chunks=64, chunked=1 << 24),
        _span(22, 20, "join.assemble", 120.0, parent=20, members=1, **how[1]),
        none, dict(none),
        _span(31, 30, "join.plan", 30.0, parent=30, pip=16, rast=0, bbox_only=0, host_raster=0,
              empty=0),
        # an assembly that is no root's child is not the join's
        _span(32, 30, "join.assemble", 999.0, parent=31, members=1, pairs=1, sorted=0, moved=1),
    ]
    return {"workload": CELL, "spans": spans, "device": None,
            "client": {"query_ms": [251.0, 702.0, 41.0], "between_s": [0.0001]}}


def test_the_wall_is_over_the_roots_pairs_and_the_share_over_the_spans():
    view = _view()
    assert join_assemble_ns_pair.read(view) == pytest.approx(180e6 / 18_000_000)  # 10 ns
    assert join_assemble_moved_pct.read(view) == pytest.approx(100.0 * 3 / 18)


def test_a_window_of_lone_members_moves_nothing_and_of_patches_everything():
    view = _view()
    lone = dict(view, spans=[s for s in view["spans"] if s["trace"] == 20])
    assert join_assemble_moved_pct.read(lone) == 0.0
    assert join_assemble_ns_pair.read(lone) == pytest.approx(8.0)
    patches = dict(view, spans=[s for s in view["spans"] if s["trace"] == 10])
    assert join_assemble_moved_pct.read(patches) == 100.0
    assert join_assemble_ns_pair.read(patches) == pytest.approx(20.0)


def test_the_parent_reads_its_cost_a_pair_and_no_share():
    """PR 52's parent has the span and the root's ``pairs`` (PR 41's) and
    counts neither ``moved`` nor the span's own ``pairs``."""
    view = _view(counted=False)
    assert join_assemble_ns_pair.read(view) == pytest.approx(10.0)
    assert join_assemble_moved_pct.read(view) is None
    mixed = _view()  # a span that counts beside one that does not: the counted one alone
    mixed["spans"][3]["attrs"] = {"members": 4}
    assert join_assemble_moved_pct.read(mixed) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_without_an_assembly_or_a_join_root(name):
    view = _view()
    assert READERS[name].read(dict(view, spans=[s for s in view["spans"] if s["trace"] == 30])) \
        is None
    assert READERS[name].read(dict(view, spans=[])) is None
    query = _span(40, 40, "query", 5.0, root="query")
    other = dict(view, spans=[query, dict(query),
                              _span(41, 40, "scan", 1.0, parent=40, root="query")])
    assert READERS[name].read(other) is None


def test_the_cell_lists_both_in_the_joins_layer():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    host = next(m for m in bench["per_layer"] if m["name"] == "join_host_ms")
    assert mine["join_assemble_ns_pair"] == dict(host, name="join_assemble_ns_pair", unit="ns")
    assert mine["join_assemble_moved_pct"] == dict(
        host, name="join_assemble_moved_pct", unit="%", source="program_counter")
    assert host["workloads"] == [CELL] and host["moves"] == "query_p95_ms"
