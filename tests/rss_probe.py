"""Resident-set accounting for tests/test_compact_stream.py's bounded-memory
proof: the child process that test starts imports these."""

from __future__ import annotations

import os

import numpy as np


def _rss_bytes() -> int:
    """Current resident set size of this process (Linux /proc)."""
    with open("/proc/self/statm") as fh:
        return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def _malloc_trim() -> None:
    """Release freed-but-retained allocator arenas before a baseline RSS
    capture, so the measured ratios compare live bytes, not glibc
    retention. NOT called while sampling a phase's peak — the peak stays
    conservative (what an OOM killer would actually see)."""
    try:
        import ctypes

        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass


class _RssSampler:
    """Background peak-RSS sampler (the compaction memory-model proof:
    ru_maxrss is a process-lifetime high-water mark, useless for scoping
    one phase)."""

    def __init__(self, interval_s: float = 0.02):
        import threading

        self.interval_s = interval_s
        self.peak = 0
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        while not self._stop.is_set():
            self.peak = max(self.peak, _rss_bytes())
            self._stop.wait(self.interval_s)

    def __enter__(self):
        self.peak = _rss_bytes()
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join()
        self.peak = max(self.peak, _rss_bytes())


def _ingest_column_set_bytes(ds, type_name: str) -> int:
    """Host bytes attributable to one type's column set: feature columns
    + ids + every index's key columns + the resident table columns (RAM
    on a CPU backend)."""
    from geomesa_tpu.ingest.pipeline import _chunk_nbytes

    total = 0
    for fc in ds._chunks.get(type_name, []):
        total += _chunk_nbytes(fc, {})
    for (t, name), parts in ds._key_chunks.items():
        if t != type_name:
            continue
        for k in parts:
            total += int(k.bins.nbytes) + int(k.zs.nbytes)
            total += sum(int(v.nbytes) for v in k.device_cols.values())
    for (t, name), table in ds._tables.items():
        if t == type_name:
            total += int(table.nbytes_device)  # RAM on a CPU backend
            # the table's host half: sorted key copies + the permutation
            for arr in (table.perm, table.bins, table.zs):
                total += int(np.asarray(arr).nbytes)
    return total
