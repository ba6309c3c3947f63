"""Client ``embedded``: one caller through the store's own API, closed
loop: the next call when the last has returned with its rows. The mix's
``warm`` passes (the mix itself, then ladders of sizes) run in set-up."""

from __future__ import annotations

import time

from harness import check, stats
from harness import requests as rq
from harness.cells import emit, trace_window
from harness.data import sub_rng


def drive(run) -> None:
    store, traffic, seed, events = run["store"], run["traffic"], run["seed"], run["events"]
    role = traffic["roles"][0]
    gctx = run["gctx"] | {"client_index": 0}
    warm = []
    for k, w in enumerate(traffic["warm"]):
        warm += rq.generate(w if "generator" in w else dict(role, **w), (seed, 50 + k),
                            int(w["requests"]), gctx)
    reqs = rq.generate(role, (seed, 100), int(role["requests_per_client"]), gctx)
    keep = sub_rng(seed, 200).random(len(reqs))
    share = traffic["check"]["keep_share"]
    t = time.perf_counter()
    for req in warm:
        rq.op_of(req).embedded(store, req)
    emit("warm_traffic", requests=len(warm), seconds=time.perf_counter() - t)
    t_start = time.monotonic()
    t_stop = t_start + run["seconds"]
    run["t_start"], run["t_stop"] = t_start, t_stop
    run["perf_start"] = time.perf_counter()
    run["compiles_at_start"] = events.snapshot()
    tw = trace_window(run, t_start)
    samples, kept, largest = [], {}, (-1, None, None)
    for i, req in enumerate(reqs):
        op = rq.op_of(req)
        compiles = events.requests
        t0 = time.monotonic()
        if t0 >= t_stop:
            break
        try:
            ans = op.embedded(store, req)
            ok = True
        except Exception as e:  # counted as failed, and said
            emit("op_failed", index=i, klass=req["klass"], error=f"{type(e).__name__}: {e}")
            ans, ok = None, False
        t1 = time.monotonic()
        if events.requests != compiles:
            # a program the warm-up did not reach: no sample, and failed
            emit("op_compiled", index=i, klass=req["klass"], seconds=t1 - t0)
            ok = False
        samples.append((i, t0, t1, ok))
        if ok:
            if keep[i] < share.get(req["klass"], 0.0):
                kept[i] = ans
            elif op.size(ans) > largest[0]:
                largest = (op.size(ans), i, ans)
        if tw is not None:
            tw.poll()
    if tw is not None:
        tw.finish()
    run["perf_stop"] = time.perf_counter()
    run["compiles_at_stop"] = events.snapshot()
    if largest[1] is not None:
        kept[largest[1]] = largest[2]
    run["embedded"] = {"reqs": reqs, "samples": samples, "kept": kept}


def reduce(run) -> None:
    e = run["embedded"]
    reqs, tally = e["reqs"], run["tally"]
    # the sample is capped per class so that the NumPy pass stays well
    # under the window; the largest answer is always in it
    cap = dict(run["traffic"]["check"]["max_per_class"])
    chosen = []
    for i in sorted(e["kept"], key=lambda i: -rq.op_of(reqs[i]).size(e["kept"][i])):
        k = reqs[i]["klass"]
        if cap.get(k, 0) > 0:
            cap[k] -= 1
            chosen.append(i)
    t = time.perf_counter()
    bad = set()
    for i in chosen:
        before = {k: tally[k] for k in check.LIMITS}
        tally["compared"] += 1
        rq.op_of(reqs[i]).compare(tally, run["cols"], reqs[i], e["kept"][i])
        if any(tally[k] != before[k] for k in check.LIMITS):
            bad.add(i)
    emit("checked", answers=len(chosen), rows=tally["rows_compared"],
         witnesses=tally["witnesses"], seconds=time.perf_counter() - t)
    done = [(i, t0, t1) for i, t0, t1, ok in e["samples"] if ok and i not in bad]
    members = [rq.op_of(reqs[i]).members(reqs[i]) for i, _, _ in done]
    lat = [(t1 - t0) * 1e3 for _, t0, t1 in done]
    single = [ms for ms, m in zip(lat, members) if m == 1]
    run["attempted"] = len(e["samples"])
    run["failed"] = len(e["samples"]) - len(done)
    run["e2e"] = {
        "queries_per_s": sum(members) / run["seconds"],
        "query_p95_ms": stats.percentile(lat, 95.0),
    }
    if single:
        # all the time spent in the operations that are one query, over their
        # number; the tail of the same samples is the per-layer single_tail_ms
        run["e2e"]["single_mean_ms"] = sum(single) / len(single)
    by = {}
    for (i, _, _), ms in zip(done, lat):
        by.setdefault(reqs[i]["klass"], []).append(ms)
    emit("latency", samples=len(lat), p50_ms=stats.median(lat), single_samples=len(single),
         single_p95_ms=stats.percentile(single, 95.0) if single else None,
         highest_percentile_with_10_beyond=stats.highest_percentile(len(lat)),
         single_highest_percentile_with_10_beyond=stats.highest_percentile(len(single)),
         by_class={k: {"n": len(v), "p50_ms": stats.median(v)} for k, v in sorted(by.items())})
    between = [b[1] - a[2] for a, b in zip(e["samples"], e["samples"][1:])]
    run["client"] = {"query_ms": lat, "single_ms": single, "between_s": between}
