"""BENCHMARK.json against the letter of the benchmark's contract, and
against the files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_and_sizes():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
                      "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["paths"]) <= 16 and len(b["command"]) <= 32
    assert all(_line(w) for w in b["command"])
    assert b["command"][1].startswith(b["paths"][0] + "/")


def test_configs_and_cells():
    b = _bench()
    names = [c["name"] for c in b["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert all(NAME.match(k) for k in c["reduced"])
    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in b["workloads"]}
    assert len(pairs) == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in b["workloads"]} == set(names)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(cells) // 2)


def test_metrics():
    b = _bench()
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    every = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(every)) == len(every)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"]) and m["source"] in SOURCES
        here = os.path.join(ROOT, "benchmark", "layer_metrics", m["name"])
        assert os.path.exists(here + ".py")
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in moved.get("workloads", cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:  # every cell: setup_s, one more end-to-end metric, one per-layer metric
        assert sum(cell in m.get("workloads", cells) for m in b["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


READ_KEYS = {"name", "about", "chips", "store", "type_name", "schema", "indices", "z3_interval",
             "rows", "span_days", "data", "guarantees", "properties", "jax", "reduced",
             "reduced_why"}


def test_every_key_of_a_configuration_is_one_the_harness_reads():
    """``about`` and ``reduced_why`` are prose; a key outside this set would
    look like a setting and change nothing."""
    b = _bench()
    bench_dir = os.path.join(ROOT, "benchmark")
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert set(cfg) == READ_KEYS
        assert os.path.exists(os.path.join(bench_dir, "stores", cfg["store"] + ".py"))
        assert os.path.exists(os.path.join(bench_dir, "datagen", cfg["data"]["generator"] + ".py"))
        assert set(cfg["reduced"]) == set(cfg["reduced_why"])
        for w in b["workloads"]:
            if w["config"] == c["name"]:
                assert w["chips"] == cfg["chips"]
    for w in b["workloads"]:
        with open(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")) as fh:
            mix = json.load(fh)
        assert os.path.exists(os.path.join(bench_dir, "clients", mix["client"] + ".py"))
        for role in mix["roles"]:
            assert os.path.exists(os.path.join(bench_dir, "generators", role["generator"] + ".py"))


def test_every_kernel_family_names_a_module_and_what_it_wraps():
    import glob

    for path in glob.glob(os.path.join(ROOT, "benchmark", "kernels", "*.json")):
        with open(path) as fh:
            fam = json.load(fh)
        assert os.path.exists(os.path.join(ROOT, "benchmark", "kernels", fam["module"] + ".py"))
        assert fam["entry_points"] and re.compile(fam["trace_name_pattern"])
