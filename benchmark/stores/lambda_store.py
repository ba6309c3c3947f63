"""Store kind ``lambda_store``: the program's ``LambdaStore`` over a cold
``DataStore`` bulk-loaded exactly as ``stores/datastore.py`` loads it (the
checkpointed store a Lambda deployment starts from), with a write-ahead
log under the run's directory (``<run_dir>/_wal``, ``sync`` at the
program's default, ``always``), the scheduler attached, the data plane
mounted over the Lambda store (so ``POST /ingest`` rides ``write``'s WAL
path and ``GET /query`` merges both tiers), and a persist loop that calls
``lam.flush()`` every ``flush_interval_s`` seconds from the moment the
client names. ``build(config, cols, run_dir)`` returns the handle:
``stores/datastore.py``'s, with ``lam``, ``start_persist(t_go)``,
``stop_persist()`` and a ``close()`` that stops the loop and closes the
server, the scheduler, the flusher and the log."""

from __future__ import annotations

import json
import os
import threading
import time

from stores import datastore


def fs_type(path: str) -> str:
    """The filesystem type of the mount that holds ``path`` (/proc/mounts:
    the longest mount point that is a prefix), "unknown" where that cannot
    be read. An fsync to a tmpfs is no write to a disk."""
    path = os.path.realpath(path)
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts") as fh:
            for line in fh:
                _, mount, fstype = line.split()[:3]
                inside = path == mount or path.startswith(mount.rstrip("/") + "/")
                if inside and len(mount) > len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind


class Store(datastore.Store):
    def __init__(self, base: datastore.Store, lam, flush_interval_s: float):
        super().__init__(base.ds, base.type_name, base.indices, base.load_s)
        self.lam, self.flush_interval_s = lam, float(flush_interval_s)
        self.flushes: list = []  # (started on time.monotonic, rows, seconds)
        self._stop = threading.Event()
        self._thread = self._error = None

    def serve(self):
        """The data plane over the Lambda store, started once: (host, port)."""
        if self._served is None:
            srv = self.lam.serve(port=0)
            self._served = (srv.host, srv.port)
        return self._served

    def _persist(self, t_go: float) -> None:
        k = 1
        try:
            while not self._stop.wait(max(t_go + k * self.flush_interval_s - time.monotonic(),
                                          0.0)):
                t = time.monotonic()
                rows = self.lam.flush()
                self.flushes.append((t, int(rows), time.monotonic() - t))
                # a flush that ran past its next tick skips it
                k = max(k + 1, int((time.monotonic() - t_go) / self.flush_interval_s) + 1)
        except BaseException as e:  # read by stop_persist, which raises it
            self._error = e

    def start_persist(self, t_go: float) -> None:
        """``lam.flush()`` at ``t_go`` + k x ``flush_interval_s`` on
        ``time.monotonic``, k = 1, 2, ..., in a thread of this process."""
        self._thread = threading.Thread(target=self._persist, args=(t_go,),
                                        name="bench-persist", daemon=True)
        self._thread.start()

    def stop_persist(self) -> list:
        """Stops the loop after the flush in hand; its log. Raises what a
        flush raised."""
        self._halt()
        if self._thread is not None:
            raise RuntimeError("the persist loop did not stop")
        if self._error is not None:
            raise self._error
        return self.flushes

    def _halt(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(120.0)
            if not self._thread.is_alive():
                self._thread = None

    def close(self) -> None:
        self._halt()
        self.lam.close()  # the data plane, the flusher's pool, the log
        super().close()   # the scheduler


def build(config: dict, cols, run_dir: str) -> Store:
    from geomesa_tpu import conf
    from geomesa_tpu.streaming.store import LambdaStore

    base = datastore.build(config, cols, run_dir)
    wal_dir = os.path.join(run_dir, "_wal")
    lam = LambdaStore(base.ds, base.type_name, wal_dir=wal_dir)
    lam.serve()  # the scheduler: the cold half of every read is admitted through it
    print(json.dumps({"phase": "wal", "dir": os.path.relpath(wal_dir, run_dir),
                      "fs_type": fs_type(wal_dir), "sync": str(lam.wal.config.sync),
                      "fold_rows": int(conf.STREAM_FOLD_ROWS.get()),
                      "compact_min_rows": int(base.ds.COMPACT_MIN_ROWS),
                      "flush_interval_s": float(config["flush_interval_s"])}), flush=True)
    return Store(base, lam, config["flush_interval_s"])
