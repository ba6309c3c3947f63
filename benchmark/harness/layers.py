"""From a traced run to the per-layer metrics: builds the view every
reader takes and calls each metric's own reader,
``layer_metrics/<metric>.py``'s ``read(view)``. A reader that finds
nothing to read returns None and its metric is left out of the line.

The view: ``spans`` (the program's, raw), ``client`` (latency samples and
the load generators' own gaps), ``kernel_calls`` {family: records},
``kernels`` {family: its json}, ``device`` (the reduced trace) and
``trace_t`` (when it ran), ``setup``, ``peaks()`` (the device's peaks,
looked up when a reader asks: an unknown device is an error then),
``perf_window``, ``workload``, ``seconds``.

``roofline_share`` is what every ``<family>_roofline`` reader returns."""

from __future__ import annotations

import importlib
import json
import os

from harness import instrument, xplane
from harness.cells import HERE, emit


def peaks_of(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as fh:
        table = json.load(fh)
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def read_all(bench: dict, run: dict, device: dict):
    """(metrics {name: value}, breakdown or None); fills device busy_s and
    window_s in place."""
    reduced = None
    tw = run.get("trace_window")
    if tw is not None and tw.state == "done":
        path = xplane.newest_xplane(run["trace_dir"])
        reduced = xplane.reduce(xplane.load(path))
        emit("trace", file=os.path.relpath(path, run["trace_dir"]),
             bytes=os.path.getsize(path), planes=reduced["planes"],
             device_events=reduced["n_device_events"], window_s=reduced["window_s"],
             busy_s=reduced["busy_s"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    rec = run.get("recorder")
    r0, _, s0 = run["compiles_at_start"]
    view = {
        "workload": run["workload"], "seconds": run["seconds"],
        "spans": run.get("spans", []), "client": run["client"],
        "kernel_calls": {k: list(v) for k, v in rec.calls.items()} if rec else {},
        "trace_t": (tw.t0, tw.t1) if reduced else None,
        "device": reduced,
        "setup": {"rows": len(run["cols"]), "load_s": run["store"].load_s, "compile_s": s0,
                  "compile_requests": r0, "setup_s": run["setup_s"]},
        "peaks": lambda: peaks_of(device["kind"]),
        "kernels": instrument.kernel_families(HERE),
        "perf_window": (run["perf_start"], run["perf_stop"]),
    }
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m and run["workload"] not in m["workloads"]:
            continue
        value = importlib.import_module("layer_metrics." + m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = float(value)
    breakdown = None
    if reduced:
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    return metrics, breakdown


def roofline_share(view, family: str):
    """100 x the least time the chip could take for the family's calls
    dispatched while the profiler ran (``kernels/<module>.roofline``) over
    the family's device time in the trace; None where the trace shows no
    device op under the family's name pattern."""
    d, fam = view["device"], view["kernels"][family]
    if not d or view["trace_t"] is None:
        return None
    seconds = xplane.family_seconds(d["ops"], fam["trace_name_pattern"])
    if seconds <= 0:
        return None
    t0, t1 = view["trace_t"]
    calls = [c for c in view["kernel_calls"].get(family, []) if t0 <= c["t"] < t1]
    least = importlib.import_module("kernels." + fam["module"]).roofline(calls, view["peaks"]())
    return 100.0 * least["least_s"] / seconds
