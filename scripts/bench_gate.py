#!/usr/bin/env python
"""Bench regression gate: compare a fresh bench json against the recorded
baseline and FAIL on regression (docs/ingest.md "Benchmarks & regression
gate"; docs/streaming.md "Bench recipe").

Usage:
    # produce a fresh run at a SCRATCH path (never the committed
    # baseline!), then gate it against the repo's recorded file
    GEOMESA_BENCH_CONFIGS=pip_join \
        GEOMESA_BENCH_PIP_OUT=/tmp/BENCH_PIP_JOIN.json python bench.py
    python scripts/bench_gate.py --fresh /tmp/BENCH_PIP_JOIN.json

    GEOMESA_BENCH_CONFIGS=stream \
        GEOMESA_BENCH_STREAM_OUT=/tmp/BENCH_STREAM.json python bench.py
    python scripts/bench_gate.py --fresh /tmp/BENCH_STREAM.json

    GEOMESA_BENCH_CONFIGS=standing \
        GEOMESA_BENCH_GEOFENCE_OUT=/tmp/BENCH_GEOFENCE.json python bench.py
    python scripts/bench_gate.py --fresh /tmp/BENCH_GEOFENCE.json

``bench.py`` runs only on a TPU (it exits 2 and prints no row anywhere
else), so a fresh file comes from the chip; a gate between a chip run and
a committed ``"platform": "cpu"`` record compares two different machines
and proves nothing (ROADMAP S1 replaces these records with cells).

The default --baseline is inferred from the fresh file's name
(BENCH_STREAM* gates against the committed BENCH_STREAM.json, everything
else against BENCH_PIP_JOIN.json). The gate refuses to compare a file
against itself (exit 2): a self-comparison always passes and would mask
any regression.

Checks, per scenario present in BOTH files:
- the guarded metric may not regress by more than --max-regress
  (default 0.20 = 20%) against the baseline — cost metrics
  (``raster_ms_per_q``, ``raster_ms``, ``adaptive_ms``) may not rise,
  throughput metrics (``streamed_rows_per_s``,
  ``wal_interval_rows_per_s``, ``replay_rows_per_s``) may not fall;
- every ``identical`` flag in the fresh run must be true — a speedup
  that changed answers is a bug, not a win;
- within-run bounds on the fresh file alone (FRESH_BOUNDS): the
  streaming WAL's ``sync=interval`` overhead must stay within 15% of
  the same run's no-WAL throughput (``interval_over_nowal >= 0.85``).

Exit code 0 = pass, 1 = regression / broken identity, 2 = unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# scenario -> guarded metric specs, each (field path, direction,
# fallback baseline paths): "lower" metrics are costs (regression =
# rising), "higher" metrics are throughputs (regression = falling).
# Paths are dot-nested into the scenario row ("query.fold_window_p99_ms");
# fallbacks let a renamed field gate against a baseline recorded under
# the old name (round 11: fold_window_p99_ms was in_fold_p99_ms).
SCENARIO_SPECS = {
    "z2_polygon_pip_batch": [("raster_ms_per_q", "lower", ())],
    "z2_polygon_join": [("raster_ms", "lower", ())],
    "host_grid_join": [("adaptive_ms", "lower", ())],
    "stream_sustained": [
        ("streamed_rows_per_s", "higher", ()),
        ("query.fold_window_p99_ms", "lower", ("query.in_fold_p99_ms",)),
    ],
    "stream_wal": [("wal_interval_rows_per_s", "higher", ())],
    "wal_replay": [("replay_rows_per_s", "higher", ())],
    "knn_batched": [("batched_qps", "higher", ())],
    "serving_obs": [
        ("off.qps", "higher", ()),
        ("sampled.qps", "higher", ()),
    ],
    "ops_plane": [
        ("qps_unscraped", "higher", ()),
        ("qps_scraped", "higher", ()),
    ],
    "standing_geofence": [
        ("speedup_vs_naive", "higher", ()),
        ("inverted_us_per_event", "lower", ()),
        ("matcher_on_rows_per_s", "higher", ()),
    ],
    # replication: the baseline-compared metric is the SCALING RATIO
    # (host-speed cancels out; absolute QPS and staleness wall-clock
    # swing >20% run-to-run on a shared host) — the teeth for
    # staleness/loss live in FRESH_BOUNDS, which run on every fresh
    # file; the deterministic row counts pin the bench shape and keep
    # the scenarios in the identical-flag sweep
    "replica_scaling": [("qps_scaling_2f", "higher", ())],
    "replica_staleness": [("streamed_rows", "higher", ())],
    "replica_failover": [("acked_rows", "higher", ())],
    # data plane (docs/serving.md "The data plane"): like replication,
    # absolute QPS/latency swing run-to-run on a shared host, so the
    # baseline comparison pins only deterministic shape counts (and the
    # identical-flag sweep); the fairness/durability teeth live in
    # FRESH_BOUNDS, which run on every fresh file
    "serve_http_mixed": [("cold_rows", "higher", ())],
    "serve_http_fairness": [],
    "serve_http_durability": [("acked_rows", "higher", ())],
    # live map tiles (docs/tiles.md): same shared-host reasoning — the
    # baseline comparison pins the deterministic workload shape (and
    # the identical-flag sweep); the speedup / p99 / hit-ratio /
    # invalidation teeth live in FRESH_BOUNDS
    "tiles_serving": [
        ("cold_rows", "higher", ()),
        ("zooms_measured", "higher", ()),
    ],
    "tiles_invalidation": [("warmed_tiles", "higher", ())],
    # self-tuning drift (docs/tuning.md "The drift gate"): absolute QPS
    # swings on a shared host, so the baseline comparison pins only the
    # deterministic workload shape (and the identical-flag sweep, which
    # here is the DISARMED-off-switch bit-identity oracle); the
    # degradation / oracle-ratio / decision teeth live in FRESH_BOUNDS
    "config_drift": [("n_points", "higher", ())],
    # multi-host pods (docs/distributed.md): the baseline-compared
    # metrics are the WITHIN-RUN speedup ratios (host-speed cancels
    # out, like replica_scaling); the absolute floors live in
    # FRESH_BOUNDS and the in-bench differential rides the
    # identical-flag sweep
    "pod_scan": [("scan_speedup", "higher", ())],
    "pod_ingest": [("ingest_speedup", "higher", ())],
}

# within-run invariants checked on the FRESH file alone (no baseline
# needed): scenario -> (field path, bound, kind, message). kind "min":
# the value may not fall below the bound (the ISSUE 10 WAL acceptance);
# kind "max": it may not exceed it (the round-11 pause-kill acceptance:
# fold-window query p99 within 2x steady state; the round-11 kNN bar:
# batched throughput >= 60 q/s).
FRESH_BOUNDS = {
    "stream_wal": [(
        "interval_over_nowal", 0.85, "min",
        "sync=interval throughput must stay within 15% of no-WAL",
    )],
    "stream_sustained": [(
        "query.fold_window_p99_over_steady", 2.0, "max",
        "fold-window query p99 must stay within 2x steady-state p99",
    )],
    "knn_batched": [(
        "batched_qps", 60.0, "min",
        "batched kNN must clear the 60 q/s bar (VERDICT weak #5)",
    )],
    # the ISSUE 13 observability acceptance: sampled (1/64) tracing
    # keeps >=95% of tracing-off serving QPS within the same run; the
    # live histogram p99 agrees with the offline percentile within one
    # log bucket; a captured slow-query trace explains >=90% of its
    # wall through >=5 top-level phases
    "serving_obs": [
        ("sampled_over_off", 0.95, "min",
         "sampled (1/64) tracing must keep >=95% of tracing-off QPS"),
        ("hist_p99.bucket_delta", 1.0, "max",
         "live histogram p99 must agree with offline p99 within 1 bucket"),
        ("slow_trace.phase_cover", 0.90, "min",
         "slow-query trace phases must cover >=90% of the root wall"),
        ("slow_trace.n_phases", 5.0, "min",
         "a fused batched slow query must show >=5 distinct phases"),
    ],
    # the ISSUE 15 ops-plane acceptance: a 1 Hz /metrics+/health
    # scraper costs the serving tier <=5% QPS within the same run;
    # estimate-vs-actual is recorded for >=99% of executed scans; the
    # stale-stats trigger fires on a mutated-without-analyze store and
    # clears after analyze_stats
    "ops_plane": [
        ("scraped_over_unscraped", 0.95, "min",
         "a 1 Hz /metrics+/health scraper must keep >=95% of unscraped QPS"),
        ("scrapes", 10.0, "min",
         "the scraped mode must actually have scraped (>=2 per rep)"),
        ("estimate_coverage", 0.99, "min",
         "estimate-vs-actual must be recorded for >=99% of executed scans"),
        ("stale_demonstrated", 1.0, "min",
         "the stale-stats health reason must fire on the mutated store"),
        ("stale_cleared", 1.0, "min",
         "analyze_stats must clear the stale-stats reason"),
    ],
    # the ISSUE 14 standing-query acceptance: >=1M registered geofences
    # under sustained ingest; inverted matching >=50x cheaper per event
    # than the naive all-subscription evaluation measured in the SAME
    # run; the matcher riding the ack path may not cost ingest more
    # than 10% of the matcher-off rate (also within-run)
    "standing_geofence": [
        ("subscriptions", 1_000_000.0, "min",
         "the bench must register >=1M standing geofences"),
        ("speedup_vs_naive", 50.0, "min",
         "inverted matching must be >=50x below naive per-event cost"),
        ("ingest_ratio", 0.9, "min",
         "matcher-on ingest must hold >=0.9x the matcher-off rate"),
    ],
    # the replication acceptance (docs/replication.md): two followers
    # must add real aggregate read capacity; the measured staleness
    # watermark stays bounded under sustained ingest; kill-the-leader
    # failover loses ZERO acknowledged rows and invents none
    "replica_scaling": [(
        "qps_scaling_2f", 1.5, "min",
        "aggregate read QPS at 2 followers must be >=1.5x leader-alone",
    )],
    "replica_staleness": [(
        "staleness_p99_ms", 2000.0, "max",
        "follower staleness p99 must stay bounded (the SLO default)",
    )],
    "replica_failover": [
        ("acked_loss", 0.0, "max",
         "kill-the-leader failover may lose ZERO acknowledged rows"),
        ("invented", 0.0, "max",
         "failover may not invent rows that were never written"),
    ],
    # the data-plane acceptance (docs/serving.md "The data plane"): an
    # adversarial tenant flooding the listener costs a compliant
    # tenant's read p99 at most 1.5x, the adversary is VISIBLY shed
    # (429s accounted per tenant, never silent queueing), and every
    # HTTP-acked ingest row survives kill -9 + recover
    "serve_http_fairness": [
        ("degradation", 1.5, "max",
         "compliant-tenant p99 under adversarial flood must stay <=1.5x"),
        ("adversary_shed", 1.0, "min",
         "the flooding tenant must have been visibly shed (429s)"),
    ],
    "serve_http_durability": [
        ("acked_loss", 0.0, "max",
         "HTTP-acked ingest rows may not be lost by kill -9 + recover"),
        ("invented", 0.0, "max",
         "recover may not invent rows that were never acked"),
    ],
    # the ISSUE 18 map-tile acceptance (docs/tiles.md): precomposed
    # serving >=5x the from-scratch path at matched workload across
    # >=3 zooms with the in-bench bit-identity oracle green (the
    # identical-flag sweep); warm-hit p99 bounded under sustained
    # ingest; the pyramid absorbs the warm working set; one localized
    # write invalidates ONLY touched tiles — dirty tiles recompose
    # under a new ETag while far tiles keep answering 304
    "tiles_serving": [
        ("speedup_min", 5.0, "min",
         "precomposed tiles must be >=5x from-scratch at every zoom"),
        ("zooms_measured", 3.0, "min",
         "the speedup must be measured across >=3 zooms"),
        ("warm_p99_ms", 75.0, "max",
         "tile p99 must stay bounded under sustained ingest"),
        ("hit_ratio", 0.7, "min",
         "the pyramid must absorb the warm working set (cache hits)"),
    ],
    "tiles_invalidation": [
        ("far_304", 1.0, "min",
         "a tile far from the write must keep answering 304"),
        ("touched_recomposed", 1.0, "min",
         "a tile overlapping the write must recompose with a new ETag"),
    ],
    # the ISSUE 19 self-tuning acceptance (docs/tuning.md): under the
    # drifted workload a FROZEN config degrades its own pre-drift rate
    # by >=30% while the armed controller holds within 1.5x of the
    # oracle config, records its decisions, and the disarmed store
    # stays bit-identical to a store without the tier
    "config_drift": [
        ("frozen_degradation", 1.30, "min",
         "the frozen config must degrade >=30% under the drifted workload"),
        ("tuned_over_oracle", 1.5, "max",
         "the self-tuned store must hold within 1.5x of the oracle config"),
        ("decisions_recorded", 1.0, "min",
         "the controller must RECORD the decisions that recovered the rate"),
        ("disarmed_identical", 1.0, "min",
         "geomesa.tuning.enabled=false must be bit-identical to no tier"),
    ],
    # the ISSUE 20 pod acceptance (docs/distributed.md): H=4 sim hosts
    # on the same device budget clear real speedup floors — selective
    # scan from owning-host-only dispatch, ingest from per-host 1/H
    # legs (slowest-host wall, the host-parallel model) — with the
    # in-bench pod-vs-flat differential green
    "pod_scan": [
        ("scan_speedup", 2.5, "min",
         "H=4 selective scan must clear 2.5x the flat mesh on the "
         "same device budget"),
        ("hosts", 4.0, "min",
         "the pod bench must run >= 4 sim hosts"),
    ],
    "pod_ingest": [(
        "ingest_speedup", 2.0, "min",
        "host-local ingest (slowest-host wall) must clear 2x the "
        "single flat loader",
    )],
}

# fresh-file basename marker -> committed baseline it gates against
BASELINES = {
    "BENCH_STREAM": "BENCH_STREAM.json",
    "BENCH_WAL": "BENCH_WAL.json",
    "BENCH_KNN": "BENCH_KNN.json",
    "BENCH_OBS": "BENCH_OBS.json",
    "BENCH_OPS_PLANE": "BENCH_OPS_PLANE.json",
    "BENCH_GEOFENCE": "BENCH_GEOFENCE.json",
    "BENCH_REPLICA": "BENCH_REPLICA.json",
    "BENCH_SERVE_HTTP": "BENCH_SERVE_HTTP.json",
    "BENCH_TILES": "BENCH_TILES.json",
    "BENCH_DRIFT": "BENCH_DRIFT.json",
    "BENCH_POD": "BENCH_POD.json",
}
DEFAULT_BASELINE = "BENCH_PIP_JOIN.json"


def _get(row: dict, path: str):
    """Dot-nested field lookup ("query.fold_window_p99_ms"); None when
    any step is missing."""
    cur = row
    for part in path.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def default_baseline(fresh_path: str, repo: str) -> str:
    name = os.path.basename(fresh_path).upper()
    for marker, baseline in BASELINES.items():
        if name.startswith(marker):
            return os.path.join(repo, baseline)
    return os.path.join(repo, DEFAULT_BASELINE)


def _rows(path: str) -> dict:
    with open(path) as fh:
        payload = json.load(fh)
    return {r["scenario"]: r for r in payload.get("rows", []) if "scenario" in r}


def gate(fresh_path: str, baseline_path: str, max_regress: float) -> int:
    if os.path.realpath(fresh_path) == os.path.realpath(baseline_path):
        print(
            "bench_gate: --fresh and --baseline are the same file; a "
            "self-comparison cannot detect a regression — write the fresh "
            "run to a scratch path (GEOMESA_BENCH_PIP_OUT / "
            "GEOMESA_BENCH_STREAM_OUT)",
            file=sys.stderr,
        )
        return 2
    try:
        fresh = _rows(fresh_path)
        base = _rows(baseline_path)
    except (OSError, ValueError, KeyError) as e:
        print(f"bench_gate: cannot read inputs: {e}", file=sys.stderr)
        return 2
    shared = [s for s in SCENARIO_SPECS if s in fresh and s in base]
    if not shared:
        print("bench_gate: no shared scenarios between fresh and baseline",
              file=sys.stderr)
        return 2
    failed = False
    for s, bounds in FRESH_BOUNDS.items():
        if s not in fresh:
            continue
        for field, bound, kind, why in bounds:
            val = _get(fresh[s], field)
            if val is None:
                continue
            val = float(val)
            bad = val < bound if kind == "min" else val > bound
            verdict = "FAIL" if bad else "ok"
            edge = "floor" if kind == "min" else "ceiling"
            print(f"{verdict:4s} {s}: {field} {val:.3f} ({edge} {bound}; {why})")
            if bad:
                failed = True
    for s in shared:
        f_row, b_row = fresh[s], base[s]
        if not f_row.get("identical", False):
            print(f"FAIL {s}: fresh run's identical flag is not true")
            failed = True
        for field, direction, fallbacks in SCENARIO_SPECS[s]:
            f_val = _get(f_row, field)
            b_val = _get(b_row, field)
            b_name = field
            for fb in fallbacks if b_val is None else ():
                b_val = _get(b_row, fb)
                if b_val is not None:
                    b_name = fb
                    break
            if f_val is None or b_val is None:
                continue
            f_val, b_val = float(f_val), float(b_val)
            if direction == "lower":
                ratio = f_val / max(b_val, 1e-12) - 1.0
            else:
                ratio = 1.0 - f_val / max(b_val, 1e-12)
            verdict = "FAIL" if ratio > max_regress else "ok"
            arrow = "rose" if direction == "lower" else "fell"
            via = "" if b_name == field else f" (baseline field {b_name})"
            print(
                f"{verdict:4s} {s}: {field} {b_val:.3f} -> {f_val:.3f} "
                f"({arrow} {ratio:+.1%}, limit +{max_regress:.0%}){via}"
            )
            if ratio > max_regress:
                failed = True
    return 1 if failed else 0


def main() -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--fresh", required=True,
        help="freshly produced bench json (a scratch path, e.g. the "
        "GEOMESA_BENCH_PIP_OUT / GEOMESA_BENCH_STREAM_OUT target — never "
        "the committed baseline)",
    )
    ap.add_argument(
        "--baseline", default=None,
        help="recorded baseline json (default: the committed file matching "
        "the fresh file's name)",
    )
    ap.add_argument(
        "--max-regress", type=float, default=0.20,
        help="max tolerated fractional regression (default 0.20)",
    )
    args = ap.parse_args()
    baseline = args.baseline or default_baseline(args.fresh, repo)
    return gate(args.fresh, baseline, args.max_regress)


if __name__ == "__main__":
    sys.exit(main())
