"""Client ``ingest_http``: a feed's writers beside closed-loop readers, all
over HTTP, against a store that persists its hot tier on a period.

Readers are ``closed_http``'s clients. A writer holds one keep-alive
connection and posts its batches on a fixed schedule: with W writers and
batches of B rows at ``rows_per_s``, writer w's batch k is due at
``t_go`` + (k + w / W) x W x B / ``rows_per_s`` seconds, and is sent when
due or when the last acknowledgement is wholly read, whichever is later
(never two in flight on a connection). ``rows_per_s`` null is the closed
loop the rate was found with. Every body is made before the writer says
"ready". Each role has its own child processes (``spawn``; they import
neither JAX nor the program). The store's persist loop
(``stores/lambda_store.py``) starts at ``t_go`` in the server's process.

An operation is a reader's answer or an acknowledged batch. ``reduce``
holds a seeded sample of the answers (the largest always in it) to
``ops/query_live.py``'s reference of what had been acknowledged and sent
by then, and after the window, writers stopped and one last flush: the
store's row count to preloaded + acknowledged, and a seeded sample of the
acknowledged batches (the first and the last always with them), read back
whole by feature id, to the generator's (``ops/ingest.py``). Failed: a
status other than 200, an acknowledgement that is not the guarantee's, an
answer the check finds wrong, a compile in the window, and every batch due
in the window and not acknowledged by its end beyond a tenth of those due.
"""

from __future__ import annotations

import http.client
import math
import multiprocessing
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from clients import closed_http
from harness import check, stats
from harness import requests as rq
from harness.cells import emit, trace_window
from harness.data import sub_rng

WRITER = "writer"  # the role whose clients post on the schedule; every other role reads


def period_s(traffic: dict, role: dict):
    """Seconds between one writer's batches, None for a closed loop."""
    rate = traffic.get("rows_per_s")
    if not rate:
        return None
    return int(role["clients"]) * int(role["params"]["batch_rows"]) / float(rate)


def _one_writer(host, port, type_name, posts, offsets, t_go, t_stop, out):
    """``posts``: [(method, path, body, headers)], made already;
    ``offsets``: seconds after ``t_go`` at which each is due (None: at once)."""
    conn = http.client.HTTPConnection(host, port, timeout=120.0)
    sent, done, status, replies = [], [], [], []
    while time.monotonic() < t_go:
        time.sleep(min(0.01, max(t_go - time.monotonic(), 0.0)))
    for k, (method, path, body, headers) in enumerate(posts):
        if offsets is not None:
            due = t_go + offsets[k]
            if due >= t_stop:
                break
            time.sleep(max(due - time.monotonic(), 0.0))
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
        sent.append(t0), done.append(t1), status.append(code), replies.append(data)
    conn.close()
    out.update(sent=np.array(sent), done=np.array(done), status=np.array(status, np.int32),
               replies=replies)


def child_main(pipe, host, port, type_name, clients):
    """``clients``: [(client id, role, rng key, n requests, generator
    context, offsets or None)], one thread each. A writer makes its bodies
    before it says "ready"."""
    work = []
    for cid, role, key, n, gctx, offsets in clients:
        reqs = rq.generate(role, key, n, gctx)
        if role["name"] == WRITER:
            posts = [rq.op_of(r).http(r, type_name) for r in reqs]
            work.append((cid, _one_writer, (posts, offsets)))
        else:
            work.append((cid, closed_http._one_client, (reqs,)))
    pipe.send("ready")
    t_go, t_stop = pipe.recv()
    outs = [{"client": cid} for cid, _, _ in work]
    threads = [threading.Thread(target=fn, args=(host, port, type_name, *args, t_go, t_stop, out))
               for (_, fn, args), out in zip(work, outs)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for (_, fn, _), out in zip(work, outs):
        if fn is _one_writer:  # parsed after every clock has stopped
            out["answers"] = [None if c != 200 else data
                              for c, data in zip(out["status"], out.pop("replies"))]
    pipe.send(outs)
    pipe.close()


def drive(run) -> None:
    store, traffic, seed = run["store"], run["traffic"], run["seed"]
    host, port = store.serve()
    warm_s = float(traffic["warm_s"])
    clients, groups = [], []  # (client id, role, requests, offsets); the specs of one process
    for role in traffic["roles"]:
        period = period_s(traffic, role) if role["name"] == WRITER else None
        n = int(role["requests_per_client"])
        if period is not None:  # as many batches as fall due before the window's end
            n = min(n, math.ceil((warm_s + run["seconds"]) / period) + 1)
        specs = []
        for k in range(int(role["clients"])):
            cid = len(clients)
            gctx = run["gctx"] | {"client_index": k}
            key = (seed, 100 + cid)
            offsets = None
            if period is not None:
                offsets = [(j + k / int(role["clients"])) * period for j in range(n)]
            clients.append((cid, role, rq.generate(role, key, n, gctx), offsets))
            specs.append((cid, role, key, n, gctx, offsets))
        n_proc = int(role["processes"])
        groups.extend([s for j, s in enumerate(specs) if j % n_proc == p] for p in range(n_proc))
    ctx = multiprocessing.get_context("spawn")
    procs = []
    for mine in groups:
        here, there = ctx.Pipe()
        proc = ctx.Process(target=child_main, daemon=True,
                           args=(there, host, port, store.type_name, mine))
        proc.start()
        there.close()
        procs.append((proc, here))
    try:
        for proc, pipe in procs:
            if not pipe.poll(120.0) or pipe.recv() != "ready":
                raise RuntimeError(f"a load generator did not come up (exit code {proc.exitcode})")
        t_go = time.monotonic() + 0.2
        t_start = t_go + warm_s
        t_stop = t_start + run["seconds"]
        for _, pipe in procs:
            pipe.send((t_go, t_stop))
        store.start_persist(t_go)
        run["t_go"], run["t_start"], run["t_stop"] = t_go, t_start, t_stop
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
    # writers stopped: the loop ends after the flush in hand, then one last flush
    flushes = store.stop_persist()
    t = time.monotonic()
    last = store.lam.flush()
    in_window = [f for f in flushes if t_start <= f[0] < t_stop]
    wal = store.lam.wal.metrics
    delta = getattr(store.ds.table(store.type_name, store.indices[0]), "delta", None)
    emit("persist", flushes=len(flushes), flushes_in_window=len(in_window),
         rows_in_window=sum(f[1] for f in in_window),
         seconds_p50=stats.median([f[2] for f in flushes]) if flushes else None,
         seconds_max=max((f[2] for f in flushes), default=None),
         first_seconds=flushes[0][2] if flushes else None,
         last_flush_rows=int(last), last_flush_seconds=time.monotonic() - t,
         # records a real fsync: 1.0 where no two producers met in a group commit
         wal_appends=wal.counter_value("geomesa.stream.wal.appends"),
         wal_fsyncs=wal.counter_value("geomesa.stream.wal.syncs"),
         hot_rows_left=len(store.lam.hot), delta_rows=0 if delta is None else len(delta.zs))
    run["served"] = {"clients": clients, "outs": outs}


def _batches(run):
    """Every batch a writer sent, in the order sent: (request, sent, done,
    sound 200 or not, the time it was due or None)."""
    by_client = {o["client"]: o for o in run["served"]["outs"]}
    out = []
    for cid, role, reqs, offsets in run["served"]["clients"]:
        if role["name"] != WRITER:
            continue
        o = by_client[cid]
        for k in range(len(o["sent"])):
            op = rq.op_of(reqs[k])
            data = o["answers"][k]
            ok = data is not None and op.sound(reqs[k], op.parse(reqs[k], data))
            due = None if offsets is None else run["t_go"] + offsets[k]
            out.append((reqs[k], float(o["sent"][k]), float(o["done"][k]), bool(ok), due))
    return sorted(out, key=lambda b: b[1])


def _due_in_window(run) -> int:
    """Batches the schedule has in [t_start, t_stop), sent or not."""
    n = 0
    for _, role, _, offsets in run["served"]["clients"]:
        if role["name"] == WRITER and offsets is not None:
            n += sum(run["t_start"] <= run["t_go"] + off < run["t_stop"] for off in offsets)
    return n


def reduce(run) -> None:
    from ops import ingest, query_live

    s, cols, tally, store = run["served"], run["cols"], run["tally"], run["store"]
    for name in check.LIMITS:  # the keys the two ops brought
        tally.setdefault(name, 0)
    t_start, t_stop, seconds = run["t_start"], run["t_stop"], run["seconds"]
    batches = _batches(run)
    appended = query_live.Appended(
        cols, [(req["spec"], sent, done if ok else math.inf) for req, sent, done, ok, _ in batches])
    by_client = {o["client"]: o for o in s["outs"]}
    reads, between = [], []  # reads: answers done in the window
    attempted = failed = 0
    for cid, role, reqs, _ in s["clients"]:
        if role["name"] == WRITER:
            continue
        o = by_client[cid]
        for k in range(len(o["sent"])):
            sent, done, good = o["sent"][k], o["done"][k], int(o["status"][k]) == 200
            if sent >= t_start:
                attempted += 1
                failed += int(not good)
                if sent > t_start:
                    between.append(float(o["between"][k]))
            if good and sent >= t_start and done <= t_stop:
                reads.append((reqs[k], float(sent), float(done), o["answers"][k]))
    acks = []  # sound acknowledgements wholly read in the window
    for req, sent, done, ok, _ in batches:
        if sent >= t_start:
            attempted += 1
            failed += int(not ok)
        if ok and sent >= t_start and done <= t_stop:
            acks.append((req, sent, done))
    # the pace is the cell's guard: due in the window, not acknowledged by its end
    due_n = _due_in_window(run)
    on_time = sum(1 for _, _, done, ok, due in batches
                  if due is not None and t_start <= due < t_stop and ok and done <= t_stop)
    late = due_n - on_time
    failed += max(late - due_n // 10, 0)
    # a seeded sample of the window's answers, the largest with it
    sizes = [rq.op_of(r[0]).size(r[3]) for r in reads]
    order = sub_rng(run["seed"], 200).permutation(len(reads))[
        :int(run["traffic"]["check"]["max_answers"])]
    if len(reads):
        order = np.unique(np.append(order, int(np.argmax(sizes))))
    t = time.perf_counter()

    def one(j):
        req, sent, done, ans = reads[j]
        mine = check.new_tally()
        mine["compared"] += 1
        rq.op_of(req).compare(mine, cols, req, ans,
                              {"sent": sent, "done": done, "appended": appended})
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
    # after the window: the count, and acknowledged batches read back whole
    t = time.perf_counter()
    sound = [b for b in batches if b[3]]
    ingest.count(tally, store.ds.row_count(store.type_name) + len(store.lam.hot), len(cols),
                 sum(b[0]["spec"]["rows"] for b in sound))
    pick = sub_rng(run["seed"], 201).permutation(len(sound))[
        :int(run["traffic"]["check"]["read_back_batches"])]
    if sound:
        pick = np.unique(np.append(pick, [0, len(sound) - 1]))
    for j in pick:
        req = sound[int(j)][0]
        ingest.compare(tally, cols, req, ingest.embedded(store, req))
    emit("read_back", batches=len(pick), of=len(sound), rows_acknowledged=sum(
        b[0]["spec"]["rows"] for b in sound), store_rows=store.ds.row_count(store.type_name),
         seconds=time.perf_counter() - t)
    good_reads = [r for j, r in enumerate(reads) if j not in bad]
    read_ms = [(r[2] - r[1]) * 1e3 for r in good_reads]
    ack_ms = [(done - sent) * 1e3 for _, sent, done in acks]
    lat = read_ms + ack_ms
    # a compile inside the window cannot be laid to one request here: each counts as a failure
    compiled = run["compiles_at_stop"][0] - run["compiles_at_start"][0]
    run["attempted"], run["failed"] = attempted, failed + len(bad) + compiled
    run["e2e"] = {"queries_per_s": len(lat) / seconds, "query_p95_ms": stats.percentile(lat, 95.0)}
    rows_acked = sum(req["spec"]["rows"] for req, _, _ in acks)
    emit("latency", samples=len(lat), reads=len(read_ms), acks=len(ack_ms),
         read_p50_ms=stats.median(read_ms) if read_ms else None,
         read_p95_ms=stats.percentile(read_ms, 95.0) if read_ms else None,
         ack_p50_ms=stats.median(ack_ms) if ack_ms else None,
         ack_p95_ms=stats.percentile(ack_ms, 95.0) if ack_ms else None,
         highest_percentile_with_10_beyond=stats.highest_percentile(len(lat)),
         hits_p50=stats.median([z for j, z in enumerate(sizes) if j not in bad]) if reads else None,
         hits_total=int(sum(sizes)))
    emit("ingest", rows_per_s_asked=run["traffic"].get("rows_per_s"),
         rows_per_s_acknowledged=rows_acked / seconds, batches_due=due_n, batches_late=late,
         batches_sent=len(batches), batches_sound=len(sound))
    if between:
        emit("load_generators", between_requests_ms_p50=stats.median(between) * 1e3,
             between_requests_ms_p95=stats.percentile(between, 95.0) * 1e3, samples=len(between))
    run["client"] = {"query_ms": lat, "between_s": between, "read_ms": read_ms, "ack_ms": ack_ms,
                     "ingest_rows_per_s": rows_acked / seconds}
