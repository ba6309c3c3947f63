"""The rest of a run with the look for the chip skipped: every cell comes
out correct at a tiny size, and comes out NOT correct with the timed path
broken underneath."""

import time

import pytest

from harness import cells

ROWS = 1 << 15
SEED = 2_500_000_023


def _run(workload, control=None, trace=0, rows=ROWS, seconds=2.0):
    return cells.run_cell(workload, SEED, seconds, trace, time.monotonic(), rows=rows,
                          control=control, trace_s=0.5)


@pytest.mark.parametrize("workload", ["gdelt.dashboard", "gdelt.analyst"])
def test_a_sound_run_is_correct(workload):
    bench = cells.load_benchmark()
    line = _run(workload)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}


def test_a_traced_run_reports_the_layer_metrics():
    bench = cells.load_benchmark()
    line = _run("gdelt.dashboard", trace=1, seconds=3.0)
    assert line["correct"] is True
    listed = {m["name"] for m in bench["per_layer"] if "gdelt.dashboard" in m["workloads"]}
    assert set(line["metrics"]) <= listed
    for name in ("plan_ms", "scan_ms", "queue_ms", "fused_batch", "http_ms", "load_rows_per_s",
                 "compile_s"):
        assert name in line["metrics"], name
    assert "window_s" in line["device"]


@pytest.mark.parametrize("workload,control", [
    # every answer of more than one row loses its last row where it is produced
    ("gdelt.analyst", "drop-row"),
    ("gdelt.dashboard", "drop-row"),
    # every answer's first Integer attribute comes back one too high
    ("gdelt.analyst", "swap-attr"),
    ("gdelt.dashboard", "swap-attr"),
])
def test_a_broken_timed_path_is_not_correct(workload, control):
    # the dashboard's answers hold more than one row only where the rows are dense
    rows = 1 << 18 if workload == "gdelt.dashboard" else ROWS
    line = _run(workload, control=control, rows=rows)
    assert line["correct"] is False


def test_an_operation_that_compiles_inside_the_window_is_failed(monkeypatch, capsys):
    """A program the warm-up did not reach: the operation is no sample."""
    from harness import instrument
    import ops.count as count_op

    made = []
    real_init = instrument.CompileEvents.__init__

    def init(self):
        real_init(self)
        made.append(self)

    real = count_op.embedded

    def embedded(store, req):
        made[-1].requests += 1  # as a backend compile during this call would
        return real(store, req)

    monkeypatch.setattr(instrument.CompileEvents, "__init__", init)
    monkeypatch.setattr(count_op, "embedded", embedded)
    line = _run("gdelt.analyst")
    out = capsys.readouterr().out
    assert line["correct"] is True
    assert line["failed"] == out.count('"op_compiled"') > 0
