"""One run of one cell: make the data, build the store, warm up, drive
the traffic for the window, check a sample of the answers, reduce.
``run.py`` looks for the chip and calls ``run_cell``; ``rehearse.py`` and
the tests call it without that look and at a tiny size.

Everything that belongs to one configuration or one mix is found by the
name its file gives: the data set (``datagen/<config's data.generator>``),
the store (``stores/<config's store>``), the client loop (``clients/<mix's
client>``), the request generators (``generators/``) and the ops
(``ops/<request's op>``)."""

from __future__ import annotations

import importlib
import json
import os
import shutil
import time

from harness import check

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmark/
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "benchmark_out")  # listed in .gitignore


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell_files(bench: dict, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"unknown workload {workload!r}")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        config = json.load(fh)
    return cell, config, load_json("traffic", cell["traffic"] + ".json")


# ------------------------------------------------------------------ trace


class TraceWindow:
    """A profiler trace over [t0, t1) of ``time.monotonic``, with one
    ``bench:window`` annotation spanning it. ``poll`` is for a caller
    that owns the loop; ``block`` sleeps through it."""

    def __init__(self, out_dir: str, t0: float, t1: float):
        self.dir, self.t0, self.t1 = out_dir, t0, t1
        self.state = "before"
        self._ann = None

    def _start(self):
        import jax.profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench:window")
        self._ann.__enter__()
        self.state = "on"

    def _stop(self):
        import jax.profiler

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def poll(self) -> None:
        now = time.monotonic()
        if self.state == "before" and now >= self.t0:
            self._start()
        elif self.state == "on" and now >= self.t1:
            self._stop()

    def block(self) -> None:
        time.sleep(max(self.t0 - time.monotonic(), 0.0))
        self._start()
        time.sleep(max(self.t1 - time.monotonic(), 0.0))
        self._stop()

    def finish(self) -> None:
        if self.state == "on":
            self._stop()


def trace_window(run, t_start: float) -> "TraceWindow | None":
    """A traced run profiles ``trace_s`` seconds in the middle of its window."""
    if not run["trace"]:
        return None
    mid = t_start + run["seconds"] / 2
    run["trace_window"] = TraceWindow(run["trace_dir"], mid - run["trace_s"] / 2,
                                      mid + run["trace_s"] / 2)
    return run["trace_window"]


# ------------------------------------------------------------------- run


def prepare(workload: str, seed: int, rows: "int | None" = None) -> dict:
    """The set-up that does not depend on the traffic: the data from the
    seed, the store loaded, the program's own warm-up. Returns the run's
    state; ``rows`` overrides the configuration's size (rehearsals, tests)."""
    from harness import compile_cache, instrument

    bench = load_benchmark()
    cell, config, traffic = cell_files(bench, workload)
    if int(config["chips"]) != int(cell["chips"]):
        raise SystemExit(f"{workload}: the cell asks for {cell['chips']} chips, its configuration "
                         f"is laid out for {config['chips']}")
    n = int(rows if rows is not None else config["rows"])
    run_dir = os.path.join(OUT_DIR, workload)
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    run = {"workload": workload, "bench": bench, "cell": cell, "config": config,
           "traffic": traffic, "trace_dir": os.path.join(run_dir, "trace")}
    t = time.perf_counter()
    datagen = importlib.import_module("datagen." + config["data"]["generator"])
    cols = run["cols"] = datagen.make(config, n, seed)
    emit("generate", rows=n, seed=seed, attributes=len(cols.schema),
         seconds=time.perf_counter() - t)
    cache_dir = compile_cache.place(ROOT, config)
    events = run["events"] = instrument.CompileEvents()
    store = run["store"] = importlib.import_module("stores." + config["store"]).build(
        config, cols, run_dir)
    emit("load", rows=n, seconds=store.load_s, rows_per_s=n / store.load_s,
         compile_cache_dir=cache_dir, nbytes_device=store.nbytes_device())
    t = time.perf_counter()
    calls = store.warmup()
    r, h, s = events.snapshot()
    emit("warmup", kernel_calls=calls, seconds=time.perf_counter() - t, compile_requests=r,
         persistent_cache_hits=h, backend_compile_s=s)
    return run


def measure(run: dict, seed: int, seconds: float, trace: int, t_birth: float,
            control: "str | None" = None, trace_s: float = 3.0) -> dict:
    """One window over a prepared store: the mix's requests from ``seed``,
    its own warm traffic, the window, the check, the result line.
    ``control`` names a guarantee to break (harness/controls.py)."""
    import jax

    from harness import instrument

    bench, cell, traffic, workload = run["bench"], run["cell"], run["traffic"], run["workload"]
    run.update(seed=int(seed), seconds=float(seconds), trace=bool(trace), trace_s=float(trace_s),
               tally=check.new_tally(), gctx=run["cols"].context() | {"seed": int(seed)})
    undo = recorder = None
    if control is not None:
        from harness import controls

        undo = controls.arm(control)
        emit("control", broken=control)
    if trace:
        instrument.arm_spans(int(traffic["span_buffer"]))
        recorder = run["recorder"] = instrument.Recorder(HERE)
        if recorder.missing:
            emit("annotations_missing", labels=recorder.missing)
    client = importlib.import_module("clients." + traffic["client"])
    try:
        client.drive(run)
        run["setup_s"] = run["t_start"] - t_birth
        r0, h0, s0 = run["compiles_at_start"]
        r1, h1, s1 = run["compiles_at_stop"]
        emit("window", seconds=run["seconds"], setup_s=run["setup_s"],
             compile_requests_in_window=r1 - r0, cache_hits_in_window=h1 - h0,
             backend_compile_s_in_setup=s0)
        if trace:
            run["spans"] = instrument.raw_spans(run["perf_start"], run["perf_stop"])
        client.reduce(run)
    finally:
        if recorder is not None:
            recorder.close()
        if undo is not None:
            undo()
    emit("guarantee", answers=run["config"]["guarantees"]["answers"])
    correct = check.verdict(run["tally"], emit)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[: cell["chips"]])
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": int(peak)}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    line = {"correct": bool(correct), "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "device": device}
    if trace:
        from harness import layers

        metrics, breakdown = layers.read_all(bench, run, device)
        line["metrics"] = {k: {"value": v, "unit": units[k]} for k, v in metrics.items()}
        if breakdown:
            line["breakdown"] = breakdown
    else:
        e2e = dict(run["e2e"], setup_s=run["setup_s"])
        wanted = [m["name"] for m in bench["end_to_end"]
                  if "workloads" not in m or workload in m["workloads"]]
        line["metrics"] = {k: {"value": float(e2e[k]), "unit": units[k]} for k in wanted}
    return line


def run_cell(workload: str, seed: int, seconds: float, trace: int, t_birth: float,
             rows: "int | None" = None, control: "str | None" = None,
             trace_s: float = 3.0) -> dict:
    """Everything after the look for the chip: one store, one window, both
    from ``seed``. Returns the result line as a dict."""
    run = prepare(workload, seed, rows)
    try:
        return measure(run, seed, seconds, trace, t_birth, control, trace_s)
    finally:
        run["store"].close()
