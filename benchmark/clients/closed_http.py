"""Client ``closed_http``: closed-loop load generators over HTTP. Child
processes started with ``spawn``, one thread per client, one keep-alive
connection per client (stdlib ``http.client``).

A child imports neither JAX nor the program, so it does not share the
server's interpreter lock and the parent alone holds the chip. A client
sends its next request only when the last answer is wholly read. Times
are ``time.monotonic`` (one clock for every process of the machine). Each
client reports, per request: when it was sent, when the whole answer was
in, the status, the answer as its op parses it (after the clock stopped),
and its own time from an answer received to the next request sent.
"""

from __future__ import annotations

import http.client
import multiprocessing
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import check, stats
from harness import requests as rq
from harness.cells import emit, trace_window
from harness.data import sub_rng


def _one_client(host, port, type_name, reqs, t_go, t_stop, out):
    conn = http.client.HTTPConnection(host, port, timeout=120.0)
    sent, done, status, between, answers = [], [], [], [], []
    while time.monotonic() < t_go:
        time.sleep(min(0.01, max(t_go - time.monotonic(), 0.0)))
    last = None
    for req in reqs:
        op = rq.op_of(req)
        method, path, body, headers = op.http(req, type_name)
        t0 = time.monotonic()
        if t0 >= t_stop:
            break
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            code = resp.status
        except (OSError, http.client.HTTPException):
            conn.close()
            data, code = b"", 599
        t1 = time.monotonic()
        between.append(0.0 if last is None else t0 - last)
        sent.append(t0), done.append(t1), status.append(code)
        answers.append(op.parse(req, data) if code == 200 else None)
        last = time.monotonic()
    conn.close()
    out.update(sent=np.array(sent), done=np.array(done), status=np.array(status, np.int32),
               between=np.array(between), answers=answers)


def child_main(pipe, host, port, type_name, clients):
    """``clients``: [(client id, role, rng key, n requests, generator
    context), ...], one thread each; every client makes its own requests
    (``requests.generate``) before it says "ready". Says "ready", is told
    (t_go, t_stop), runs, sends back one dict per client, and ends."""
    clients = [(cid, rq.generate(role, key, n, gctx)) for cid, role, key, n, gctx in clients]
    pipe.send("ready")
    t_go, t_stop = pipe.recv()
    outs = [{"client": c[0]} for c in clients]
    threads = [
        threading.Thread(target=_one_client,
                         args=(host, port, type_name, reqs, t_go, t_stop, out))
        for (_, reqs), out in zip(clients, outs)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    pipe.send(outs)
    pipe.close()


def drive(run) -> None:
    store, traffic, seed = run["store"], run["traffic"], run["seed"]
    host, port = store.serve()
    clients, specs = [], []  # (client id, requests); what a child is sent
    for role in traffic["roles"]:
        for k in range(int(role["clients"])):
            cid = len(clients)
            gctx = run["gctx"] | {"client_index": k}
            key, n = (seed, 100 + cid), int(role["requests_per_client"])
            clients.append((cid, rq.generate(role, key, n, gctx)))
            specs.append((cid, role, key, n, gctx))
    n_proc = int(traffic["processes"])
    ctx = multiprocessing.get_context("spawn")
    procs = []
    for p in range(n_proc):
        mine = [spec for spec in specs if spec[0] % n_proc == p]
        here, there = ctx.Pipe()
        proc = ctx.Process(target=child_main, daemon=True,
                           args=(there, host, port, store.type_name, mine))
        proc.start()
        there.close()
        procs.append((proc, here))
    try:
        for proc, pipe in procs:
            if not pipe.poll(60.0) or pipe.recv() != "ready":
                raise RuntimeError(f"a load generator did not come up (exit code {proc.exitcode})")
        t_go = time.monotonic() + 0.2
        t_start = t_go + float(traffic["warm_s"])
        t_stop = t_start + run["seconds"]
        for _, pipe in procs:
            pipe.send((t_go, t_stop))
        run["t_start"], run["t_stop"] = t_start, t_stop
        time.sleep(max(t_start - time.monotonic(), 0.0))
        run["perf_start"] = time.perf_counter()
        run["compiles_at_start"] = run["events"].snapshot()
        tw = trace_window(run, t_start)
        if tw is not None:
            tw.block()
        time.sleep(max(t_stop - time.monotonic(), 0.0))
        run["perf_stop"] = time.perf_counter()
        run["compiles_at_stop"] = run["events"].snapshot()
        outs = []
        for proc, pipe in procs:
            if not pipe.poll(180.0):
                raise RuntimeError(f"a load generator did not report (exit code {proc.exitcode})")
            outs.extend(pipe.recv())
    finally:
        for proc, pipe in procs:
            proc.join(30.0)
            if proc.is_alive():
                proc.kill()
                proc.join()
            pipe.close()
    run["served"] = {"clients": clients, "outs": outs}


def reduce(run) -> None:
    s, cols, tally = run["served"], run["cols"], run["tally"]
    t_start, t_stop = run["t_start"], run["t_stop"]
    by_client = {o["client"]: o for o in s["outs"]}
    reads, between = [], []  # reads: answers done in the window
    attempted = failed = 0
    for cid, reqs in s["clients"]:
        o = by_client[cid]
        for k in range(len(o["sent"])):
            sent, done, good = o["sent"][k], o["done"][k], int(o["status"][k]) == 200
            if sent >= t_start:
                attempted += 1
                failed += int(not good)
                if sent > t_start:
                    between.append(float(o["between"][k]))
            if good and sent >= t_start and done <= t_stop:
                reads.append((reqs[k], sent, done, o["answers"][k]))
    # a seeded sample of the window's answers, the largest with it
    sizes = [rq.op_of(r[0]).size(r[3]) for r in reads]
    order = sub_rng(run["seed"], 200).permutation(len(reads))[
        :int(run["traffic"]["check"]["max_answers"])]
    if len(reads):
        order = np.unique(np.append(order, int(np.argmax(sizes))))
    t = time.perf_counter()

    def one(j):
        req, _, _, ans = reads[j]
        mine = check.new_tally()
        mine["compared"] += 1
        rq.op_of(req).compare(mine, cols, req, ans)
        return j, mine

    bad = set()
    with ThreadPoolExecutor(8) as pool:
        for j, mine in pool.map(one, [int(j) for j in order]):
            if any(mine[k] for k in check.LIMITS):
                bad.add(j)
            for k, v in mine.items():
                tally[k] += v
    emit("checked", answers=len(order), of=len(reads), rows=tally["rows_compared"],
         witnesses=tally["witnesses"], seconds=time.perf_counter() - t)
    good_reads = [r for j, r in enumerate(reads) if j not in bad]
    lat = [(r[2] - r[1]) * 1e3 for r in good_reads]
    # a compile inside the window cannot be laid to one request here: each counts as a failure
    compiled = run["compiles_at_stop"][0] - run["compiles_at_start"][0]
    run["attempted"], run["failed"] = attempted, failed + len(bad) + compiled
    run["e2e"] = {"queries_per_s": len(good_reads) / run["seconds"],
                  "query_p95_ms": stats.percentile(lat, 95.0)}
    emit("latency", samples=len(lat), p50_ms=stats.median(lat),
         highest_percentile_with_10_beyond=stats.highest_percentile(len(lat)),
         hits_p50=stats.median([s for j, s in enumerate(sizes) if j not in bad]),
         hits_total=int(sum(sizes)))
    emit("load_generators", between_requests_ms_p50=stats.median(between) * 1e3,
         between_requests_ms_p95=stats.percentile(between, 95.0) * 1e3, samples=len(between))
    run["client"] = {"query_ms": lat, "between_s": between}
