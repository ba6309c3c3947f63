"""Native (C++) runtime tier: build-on-demand, ctypes-bound, with exact
numpy fallback.

The compute path is JAX/XLA (device); this is the *host runtime* native
tier — the analogue of the reference's server-side JVM plugin code for the
ingest hot loop (see geomesa_native.cpp). The library builds lazily with
g++ the first time it's needed and caches next to the source; every entry
point has a pure-numpy fallback, so the package works identically without
a toolchain (set GEOMESA_TPU_NO_NATIVE=1 to force the fallback).
"""

from __future__ import annotations

import ctypes
import hashlib
import itertools
import logging
import math
import os
import subprocess
import threading
import time
from pathlib import Path

import numpy as np

from geomesa_tpu.obs.trace import _tls as _trace_tls

_DIR = Path(__file__).resolve().parent
_SRC = _DIR / "geomesa_native.cpp"

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # None = untried, False = unavailable
# (stamp_entry, stamp_return): the library's getters of the calling thread's
# last entry and return stamps, bound through ``ctypes.PyDLL`` (its calls
# KEEP the interpreter lock, so reading a stamp is no hand-off). None where
# the library is not loaded or ``time.perf_counter`` is not the stamps' clock.
_stamps = None
_perf = time.perf_counter


def _lib_path() -> Path:
    """The artefact is named by a hash of the source it was built from:
    a ``build/`` left by another tree (the directory is not committed,
    but a copied checkout can carry one) is never what gets loaded."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _DIR / "build" / f"libgeomesa_native-{digest}.so"


def _build(lib: Path) -> bool:
    lib.parent.mkdir(exist_ok=True)
    tmp = lib.with_name(f".{lib.name}.{os.getpid()}.tmp")
    # -ffp-contract=off: the point-in-polygon ray cast promises bit-exact
    # parity with numpy's two-rounding float sequence; fused multiply-adds
    # (default under -O3 on FMA targets) would round differently for
    # points lying exactly on slanted edges
    base = [
        "g++", "-O3", "-ffp-contract=off", "-shared", "-fPIC",
        str(_SRC), "-o", str(tmp),
    ]
    why = ""
    for extra in (["-fopenmp"], []):  # prefer threaded; fall back
        try:
            r = subprocess.run(
                base[:2] + extra + base[2:],
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.TimeoutExpired) as e:
            why = repr(e)
            break
        if r.returncode == 0:
            os.replace(tmp, lib)  # atomic: concurrent loaders never see half a file
            for stale in lib.parent.glob("libgeomesa_native*.so"):
                if stale != lib:
                    stale.unlink(missing_ok=True)
            return True
        why = r.stderr.decode(errors="replace")
    tmp.unlink(missing_ok=True)
    # the numpy twins keep every entry point exact, at a several-fold
    # ingest cost nobody should pay without knowing
    _log.warning(
        "geomesa_tpu.native: building %s failed, using the numpy fallbacks:\n%s",
        _SRC.name, why,
    )
    return False


def _load():
    global _lib
    if _lib is not None:
        return _lib if _lib is not False else None
    with _lock:
        if _lib is not None:
            return _lib if _lib is not False else None
        if os.environ.get("GEOMESA_TPU_NO_NATIVE"):
            _lib = False
            return None
        path = _lib_path()
        try:
            if not path.exists() and not _build(path):
                _lib = False
                return None
            lib = ctypes.CDLL(str(path))
        except OSError as e:
            _log.warning("geomesa_tpu.native: loading %s failed: %s", path.name, e)
            _lib = False
            return None
        u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
        f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        lib.morton2.argtypes = [u64p, u64p, ctypes.c_int64, u64p]
        lib.morton2_decode.argtypes = [u64p, ctypes.c_int64, u64p, u64p]
        lib.morton3.argtypes = [u64p, u64p, u64p, ctypes.c_int64, u64p]
        lib.morton3_decode.argtypes = [u64p, ctypes.c_int64, u64p, u64p, u64p]
        lib.z3_write_keys.argtypes = [
            f64p, f64p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_double, ctypes.c_int32, u64p, i32p, f32p, f32p, i32p,
        ]
        lib.z3_write_keys.restype = ctypes.c_int32
        lib.z2_write_keys.argtypes = [f64p, f64p, ctypes.c_int64, u64p, f32p, f32p]
        u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.sort_bins_z.argtypes = [i32p, u64p, ctypes.c_int64, u32p]
        for name, tp in (
            ("gather_f32", f32p), ("gather_i32", i32p), ("gather_i64", i64p),
            ("gather_u64", u64p), ("gather_f64", f64p),
        ):
            getattr(lib, name).argtypes = [tp, u32p, ctypes.c_int64, tp]
        for name, tp in (("gather_rows_f32", f32p), ("gather_rows_f64", f64p)):
            getattr(lib, name).argtypes = [
                tp, u32p, ctypes.c_int64, ctypes.c_int64, tp
            ]
        # raw pointers, no ndpointer checks: ColumnTable and gather_columns
        # below validate what they pass
        lib.gather_columns.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64,
        ]
        lib.gather_columns.restype = None
        lib.geojson_features.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.geojson_features.restype = ctypes.c_int64
        lib.arrow_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_void_p,
        ]
        lib.arrow_batch.restype = ctypes.c_int64
        lib.arrow_batch_release.argtypes = [ctypes.c_void_p]
        lib.arrow_batch_release.restype = None
        lib.points_in_polygon_cpp.argtypes = [
            f64p, f64p, ctypes.c_int64, f64p, i64p, ctypes.c_int64, i32p, u8p
        ]
        lib.zranges_each_cpp.argtypes = [
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,
            u64p, u64p, u64p, u64p,
            ctypes.c_int64, ctypes.c_int64,
            u64p, u64p, u8p, i64p, ctypes.c_int64,
        ]
        lib.zranges_each_cpp.restype = ctypes.c_int64
        lib.bitmask_count.argtypes = [i32p, ctypes.c_int64, ctypes.c_int64]
        lib.bitmask_count.restype = ctypes.c_int64
        lib.bitmask_decode_pair.argtypes = [
            i32p, i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            i64p, u8p,
        ]
        lib.bitmask_decode_pair.restype = ctypes.c_int64
        lib.merge_rows_spans.argtypes = [
            i64p, i64p, ctypes.c_int64, i64p, u8p, ctypes.c_int64, i64p, u8p,
        ]
        lib.merge_rows_spans.restype = ctypes.c_int64
        lib.counting_argsort.argtypes = [
            i32p, ctypes.c_int64, ctypes.c_int64, u32p,
        ]
        lib.bitmask_decode.argtypes = [
            i32p, i64p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i64p,
        ]
        lib.bitmask_decode.restype = ctypes.c_int64
        lib.xz_index.argtypes = [
            f64p, f64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
            i64p, i64p,
        ]
        lib.xz_ranges.argtypes = [
            ctypes.c_int32, ctypes.c_int32, i64p, f64p, f64p,
            ctypes.c_int64, ctypes.c_int64, u64p, u64p, u8p, ctypes.c_int64,
        ]
        lib.xz_ranges.restype = ctypes.c_int64
        lib.nap.argtypes = [ctypes.c_double]
        lib.nap.restype = None
        # the stamps are CLOCK_MONOTONIC: comparable with perf_counter only
        # where that is the same clock (Linux); elsewhere no ``reacquire_s``
        if time.get_clock_info("perf_counter").implementation == (
            "clock_gettime(CLOCK_MONOTONIC)"
        ):
            global _stamps
            held = ctypes.PyDLL(str(path))  # the same library: one set of stamps
            for fn in (held.stamp_entry, held.stamp_return):
                fn.argtypes, fn.restype = [], ctypes.c_double
            _stamps = (held.stamp_entry, held.stamp_return)
        _lib = lib
        return lib


def available() -> bool:
    return _load() is not None


def _call(fn, *args):
    """The ONE door to the library: every ctypes call below goes through
    here, because every one of them lets the interpreter lock go and has
    to take it back (docs/observability.md "The interpreter lock"). Under
    an active span it counts the hand-off (``handoffs``); under a span of
    a RETAINED trace it also reads the two stamps the library's guard left
    on this thread (geomesa_native.cpp ``Stamp``) and adds ``native_s``,
    the seconds the work ran with the lock released, ``reacquire_s``,
    ``perf_counter`` now less the library's stamp of its return: the wait
    to get the lock back, read directly, and ``native_n``, the calls that
    wrote one. Untraced: one thread-local probe."""
    cur = getattr(_trace_tls, "span", None)
    if cur is None:
        return fn(*args)
    out = fn(*args)
    if _stamps is not None and cur.trace.retain:
        now = _perf()
        back = _stamps[1]()
        cur.add("native_s", back - _stamps[0]())
        cur.add("reacquire_s", max(now - back, 0.0))
        cur.add("native_n", 1)
    cur.add("handoffs", 1)
    return out


def nap(seconds: float) -> "float | None":
    """Sleep ``seconds`` inside the library, with the interpreter lock
    released as for any call here, and return the seconds this thread then
    waited to hold the lock again: ``perf_counter`` on return less the
    library's stamp of the sleep's end (the timer's slack is outside it).
    What ``obs.trace``'s hand-off probe samples. None where the library or
    the stamps' clock is not there."""
    lib = _load()
    if lib is None or _stamps is None:
        return None
    lib.nap(seconds)
    return max(_perf() - _stamps[1](), 0.0)


def morton2(x, y) -> "np.ndarray | None":
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.uint64)
    y = np.ascontiguousarray(y, dtype=np.uint64)
    out = np.empty(len(x), dtype=np.uint64)
    _call(lib.morton2, x, y, len(x), out)
    return out


def morton3(x, y, t) -> "np.ndarray | None":
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.uint64)
    y = np.ascontiguousarray(y, dtype=np.uint64)
    t = np.ascontiguousarray(t, dtype=np.uint64)
    out = np.empty(len(x), dtype=np.uint64)
    _call(lib.morton3, x, y, t, len(x), out)
    return out


def morton3_decode(z):
    lib = _load()
    if lib is None:
        return None
    z = np.ascontiguousarray(z, dtype=np.uint64)
    x = np.empty(len(z), dtype=np.uint64)
    y = np.empty(len(z), dtype=np.uint64)
    t = np.empty(len(z), dtype=np.uint64)
    _call(lib.morton3_decode, z, len(z), x, y, t)
    return x, y, t


# fixed-width periods the native binning supports: millis/bin, offset divisor
_FIXED_PERIODS = {"day": (86_400_000, 1), "week": (604_800_000, 1000)}


def z3_write_keys(x, y, millis, period: str, max_offset: int, max_bin: int):
    """Fused (bins, zs, device cols) for fixed-width periods, or None when
    native is unavailable / the period is calendar-based."""
    lib = _load()
    cfg = _FIXED_PERIODS.get(period)
    if lib is None or cfg is None:
        return None
    bin_ms, off_div = cfg
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    millis = np.ascontiguousarray(millis, dtype=np.int64)
    n = len(x)
    z = np.empty(n, dtype=np.uint64)
    bins = np.empty(n, dtype=np.int32)
    xf = np.empty(n, dtype=np.float32)
    yf = np.empty(n, dtype=np.float32)
    toff = np.empty(n, dtype=np.int32)
    status = _call(
        lib.z3_write_keys, x, y, millis, n, bin_ms, off_div, float(max_offset), max_bin,
        z, bins, xf, yf, toff,
    )
    if status == 1:
        raise ValueError(f"pre-epoch timestamp(s) not supported by period {period}")
    if status == 2:
        raise ValueError(
            f"timestamp(s) past the max representable date for period {period}"
        )
    return bins, z, {"x": xf, "y": yf, "tbin": bins, "toff": toff}


def z2_write_keys(x, y):
    """Fused (zs, device cols) for the z2 index, or None."""
    lib = _load()
    if lib is None:
        return None
    x = np.ascontiguousarray(x, dtype=np.float64)
    y = np.ascontiguousarray(y, dtype=np.float64)
    n = len(x)
    z = np.empty(n, dtype=np.uint64)
    xf = np.empty(n, dtype=np.float32)
    yf = np.empty(n, dtype=np.float32)
    _call(lib.z2_write_keys, x, y, n, z, xf, yf)
    return z, {"x": xf, "y": yf}


def sort_bins_z(bins, zs) -> "np.ndarray | None":
    """Stable argsort by (bin, z) via LSD radix — the ingest sort hot path
    (np.lexsort replacement; ~10x at 100M rows). Returns uint32 perm, or
    None when native is unavailable or n >= 2^32."""
    lib = _load()
    if lib is None or len(zs) >= (1 << 32):
        return None
    bins = np.ascontiguousarray(bins, dtype=np.int32)
    zs = np.ascontiguousarray(zs, dtype=np.uint64)
    perm = np.empty(len(zs), dtype=np.uint32)
    _call(lib.sort_bins_z, bins, zs, len(zs), perm)
    return perm


_GATHERS = {
    np.dtype(np.float32): "gather_f32",
    np.dtype(np.int32): "gather_i32",
    np.dtype(np.int64): "gather_i64",
    np.dtype(np.uint64): "gather_u64",
    np.dtype(np.float64): "gather_f64",
}


def take(src: np.ndarray, idx: np.ndarray) -> "np.ndarray | None":
    """out[i] = src[idx[i]] for ONE 1-D array of the supported dtypes, or
    None: a serial loop behind an ``ndpointer``-checked call, for the
    table builds that permute a key column at a time (storage/table.py).
    ``idx`` is unchecked and must fit uint32. An answer's rows of many
    columns go through :func:`gather_columns`."""
    lib = _load()
    name = _GATHERS.get(src.dtype)
    if lib is None or name is None or src.ndim != 1:
        return None
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, dtype=np.uint32)
    out = np.empty(len(idx), dtype=src.dtype)
    _call(getattr(lib, name), src, idx, len(idx), out)
    return out


class ColumnTable:
    """What :func:`gather_columns` needs of a set of columns, made once:
    the arrays (kept alive here for as long as their addresses are), a
    ``c_void_p`` array of their data pointers, a ``c_int64`` array of the
    bytes an item (for an N-D array, a row of the trailing axes) and how
    to allocate an answer's arrays. ``like``: the table these arrays were
    gathered from, whose widths and recipe they share."""

    __slots__ = ("arrays", "srcs", "widths", "row_bytes", "_recipe")

    def __init__(self, arrays, like: "ColumnTable | None" = None):
        self.arrays = arrays
        if like is None:
            addresses = [a.ctypes.data for a in arrays]  # read-only arrays too
            widths = [a.dtype.itemsize * math.prod(a.shape[1:]) for a in arrays]
            self.widths = (ctypes.c_int64 * len(arrays))(*widths)
            self.row_bytes = sum(widths)
            # np.empty zero-fills a `<U` array (a serial pass over a third
            # of an answer's bytes, under the interpreter lock): those are
            # allocated as raw bytes and renamed, which leaves the array
            # the owner of its data; the gather overwrites every item
            self._recipe = [
                (np.dtype(f"V{a.dtype.itemsize}") if a.dtype.kind == "U" else a.dtype,
                 a.dtype, a.shape[1:])
                for a in arrays
            ]
        else:
            # fresh and writable: the cheaper way to an address (an empty
            # array exports no byte)
            addresses = [
                _ADDRESS(_BYTE.from_buffer(a)) if a.nbytes else a.ctypes.data
                for a in arrays
            ]
            self.widths, self.row_bytes, self._recipe = like.widths, like.row_bytes, like._recipe
        self.srcs = (ctypes.c_void_p * len(arrays))(*addresses)

    @staticmethod
    def fits(a) -> bool:
        """True for what a bytewise copy is right for: a C-contiguous
        ndarray of fixed-width items that hold no object pointers (their
        reference counts)."""
        return (
            type(a) is np.ndarray and a.ndim >= 1 and a.flags.c_contiguous
            and not a.dtype.hasobject
        )

    def empty(self, n: int) -> list:
        """Uninitialised arrays for ``n`` rows of every column."""
        outs = []
        for raw, dtype, tail in self._recipe:
            out = np.empty((n,) + tail, raw)
            if raw is not dtype:
                out.dtype = dtype
            outs.append(out)
        return outs


_BYTE, _ADDRESS = ctypes.c_char, ctypes.addressof


def gather_columns(table: ColumnTable, idx: np.ndarray) -> "ColumnTable | None":
    """``[a[idx] for a in table.arrays]`` in one native call, threaded
    over columns and runs of rows above a floor of bytes
    (geomesa_native.cpp): fresh C-contiguous arrays of the same dtypes,
    returned as their own ColumnTable (the outputs' addresses are in hand,
    so a take of the answer builds nothing). ``idx``: 1-D, of an integer
    dtype, every ordinal in ``[0, len(a))``: the copy is unchecked, the
    caller bounds them. None when the library is not there."""
    lib = _load()
    if lib is None:
        return None
    if idx.dtype.itemsize not in (4, 8):
        idx = idx.astype(np.int64)
    idx = np.ascontiguousarray(idx)
    out = ColumnTable(table.empty(len(idx)), like=table)
    _call(
        lib.gather_columns, table.srcs, table.widths, out.srcs, len(out.arrays),
        idx.ctypes.data, idx.dtype.itemsize, len(idx),
    )
    return out


class GeoJSONColumns:
    """What :func:`geojson_features` needs of a collection, made once an
    answer: the arrays (kept alive here for as long as their addresses
    are), four int64 a column (kind, address, stride, bytes an item) for
    the ids, a point column's x and y and every property, and the
    properties' ``"name": `` texts end to end. ``of`` gives None where
    the library is not there or a column is one the native code cannot
    read: the caller's per-feature route then serves the collection."""

    __slots__ = ("arrays", "rows", "cols", "keys", "key_off")

    #: geomesa_native.cpp's GJ_* kinds, by ``dtype.char``
    NONE, STR, BOOL, INT, UINT, F32, F64, DATE = -1, 0, 1, 2, 3, 4, 5, 6
    _KINDS = {
        "U": STR, "?": BOOL, "f": F32, "d": F64,
        **dict.fromkeys("bhilq", INT), **dict.fromkeys("BHILQ", UINT),
    }

    @classmethod
    def of(cls, ids, xy, props) -> "GeoJSONColumns | None":
        """``ids``: a ``<U`` or int64 array; ``xy``: a point column's two
        float64 arrays, or None (every feature's geometry is null);
        ``props``: (``"name": `` as json.dumps writes it, array, True for
        a Date of int64 epoch milliseconds) in member order."""
        if _load() is None:
            return None
        n = len(ids)
        arrays = [ids, *(xy or ()), *[p[1] for p in props]]
        first = 1 if xy is None else 3
        flat = []
        for j, a in enumerate(arrays):
            if type(a) is not np.ndarray or a.ndim != 1 or len(a) != n:
                return None
            dt = a.dtype
            kind, width = cls._KINDS.get(dt.char), dt.itemsize
            if kind is None or not dt.isnative:
                return None
            if j == 0:
                if kind != cls.STR and (kind, width) != (cls.INT, 8):
                    return None
            elif j < first:
                if kind != cls.F64:
                    return None
            elif props[j - first][2]:
                if (kind, width) != (cls.INT, 8):
                    return None
                kind = cls.DATE
            try:  # the cheaper way to an address, where the buffer allows it
                address = _ADDRESS(_BYTE.from_buffer(a))
            except (TypeError, ValueError, BufferError):  # read-only, strided, empty
                address = a.ctypes.data
            flat += (kind, address, a.strides[0], width)
        if xy is None:
            flat[4:4] = (cls.NONE, 0, 0, 0) * 2
        self = cls()
        self.arrays, self.rows = arrays, n
        self.cols = (ctypes.c_int64 * len(flat))(*flat)
        self.keys = b"".join([p[0] for p in props])
        self.key_off = (ctypes.c_int64 * (len(props) + 1))(
            0, *itertools.accumulate([len(p[0]) for p in props])
        )
        return self


def geojson_features(table: GeoJSONColumns, lo: int, hi: int) -> "bytes | None":
    """The GeoJSON text of the features ``[lo, hi)``, ``", "``-joined:
    byte for byte what ``json.dumps`` gives for the dicts
    ``io.exporters.geojson_features`` builds, in one native call that
    holds no interpreter lock (geomesa_native.cpp). None where a value's
    text is not the native code's to decide (NaN, an infinity, a year
    outside 0001-9999): the caller's per-feature route then serves the
    collection."""
    lo, hi = max(int(lo), 0), min(int(hi), table.rows)
    if hi <= lo:
        return b""
    out = ctypes.c_void_p()
    n = _call(
        _load().geojson_features, table.cols, len(table.cols) // 4, table.keys,
        table.key_off, lo, hi, ctypes.byref(out),
    )
    # the bytes are the calling thread's until its next call: copied here
    return None if n < 0 else ctypes.string_at(out, n)


#: geomesa_native.cpp's AR_* ops: what ``arrow_batch`` makes of a column
AR_STRING, AR_DICT, AR_BITS, AR_COPY, AR_DATE, AR_XY = range(1, 7)


def arrow_batch(table: GeoJSONColumns, ops, order, importer):
    """The record batch of the columns ``table`` describes, made whole in
    one native call that holds no interpreter lock (geomesa_native.cpp
    says how: ``ops`` is one ``AR_*`` a row of ``table.cols``, ``order``
    the row of each of the batch's columns) and handed over through
    Arrow's C data interface: ``importer(address)`` takes the ArrowArray
    at ``address`` and owns its buffers from then on, as
    ``pyarrow.RecordBatch._import_from_c`` does; what it returns is
    returned. None where the bytes are pyarrow's to decide (NaT, a
    surrogate, a code point past U+10FFFF): the caller's pyarrow route
    then builds the table."""
    lib = _load()
    out = (ctypes.c_int64 * 10)()  # arrow/c/abi.h's ArrowArray: ten words
    if _call(
        lib.arrow_batch, table.cols, (ctypes.c_int64 * len(ops))(*ops),
        (ctypes.c_int64 * len(order))(*order), len(order), table.rows, out,
    ) < 0:
        return None
    try:
        return importer(ctypes.addressof(out))
    finally:
        if out[8]:  # ``release`` still set: nothing took the buffers
            _call(lib.arrow_batch_release, out)


_ROW_GATHERS = {
    np.dtype(np.float32): "gather_rows_f32",
    np.dtype(np.float64): "gather_rows_f64",
}


def take_rows(src: np.ndarray, idx: np.ndarray) -> "np.ndarray | None":
    """out[i, :] = src[idx[i], :] for f32/f64 [n, width] arrays, or None.
    The threaded row gather hides the random-access memory latency that
    dominates numpy fancy indexing on multi-100k-row result pulls."""
    lib = _load()
    name = _ROW_GATHERS.get(src.dtype)
    if lib is None or name is None or src.ndim != 2:
        return None
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(idx, dtype=np.uint32)
    out = np.empty((len(idx), src.shape[1]), dtype=src.dtype)
    _call(getattr(lib, name), src, idx, len(idx), src.shape[1], out)
    return out


def bitmask_decode_pair(wide, inner, bids, n_real: int, block: int):
    """(rows i64, certain bool) from wide/inner bit planes — the scan
    decode hot path (see geomesa_native.cpp), or None when native is
    unavailable. ~25x the numpy unpackbits route on large pulls."""
    lib = _load()
    if lib is None or n_real == 0:
        return None
    wide = np.ascontiguousarray(wide[:n_real], dtype=np.int32)
    inner = np.ascontiguousarray(inner[:n_real], dtype=np.int32)
    bids = np.ascontiguousarray(bids[:n_real], dtype=np.int64)
    pack = wide.shape[1]
    count = _call(lib.bitmask_count, wide, n_real, pack)
    rows = np.empty(count, dtype=np.int64)
    cert = np.empty(count, dtype=np.uint8)
    k = _call(lib.bitmask_decode_pair, wide, inner, bids, n_real, pack, block, rows, cert)
    assert k == count
    return rows, cert.astype(bool)


def xz_index(lo, hi, dims: int, g: int, subtree) -> "np.ndarray | None":
    """Element boxes ([n, dims] normalized lo/hi) -> XZ sequence codes, or
    None. ``subtree`` is XZSFC.subtree_size (len g+2) so native and Python
    agree on the preorder arithmetic. The extent-table ingest hot loop."""
    lib = _load()
    if lib is None or dims > 4:  # C++ cell buffers are fixed at 4 dims
        return None
    lo = np.ascontiguousarray(lo, dtype=np.float64)
    hi = np.ascontiguousarray(hi, dtype=np.float64)
    sub = np.ascontiguousarray(subtree, dtype=np.int64)
    n = lo.shape[0]
    out = np.empty(n, dtype=np.int64)
    _call(lib.xz_index, lo.reshape(-1), hi.reshape(-1), n, int(dims), int(g), sub, out)
    return out


def xz_ranges(dims: int, g: int, subtree, qlo, qhi, max_ranges: int):
    """Covering XZ sequence-code ranges of normalized query boxes (C++
    BFS + merge, ~100x the python pass at g=12). Returns (lo u64[k],
    hi u64[k], contained bool[k]) or None when native is unavailable."""
    lib = _load()
    if lib is None or dims > 4:
        return None
    qlo = np.ascontiguousarray(qlo, dtype=np.float64)
    qhi = np.ascontiguousarray(qhi, dtype=np.float64)
    sub = np.ascontiguousarray(subtree, dtype=np.int64)
    nq = qlo.shape[0] if qlo.ndim == 2 else len(qlo) // dims
    cap = max(int(max_ranges) * 2 + 64, 256)
    lo = np.empty(cap, dtype=np.uint64)
    hi = np.empty(cap, dtype=np.uint64)
    cont = np.empty(cap, dtype=np.uint8)
    n = _call(
        lib.xz_ranges, dims, g, sub, qlo.reshape(-1), qhi.reshape(-1), nq,
        int(max_ranges), lo, hi, cont, cap,
    )
    if n < 0:
        return None
    return lo[:n].copy(), hi[:n].copy(), cont[:n].astype(bool)


def bitmask_decode(wide, bids, n_real: int, block: int):
    """Ascending rows from a wide bit plane (no certainty — extent scans
    skip the inner plane), or None when native is unavailable."""
    lib = _load()
    if lib is None or n_real == 0:
        return None
    wide = np.ascontiguousarray(wide[:n_real], dtype=np.int32)
    bids = np.ascontiguousarray(bids[:n_real], dtype=np.int64)
    pack = wide.shape[1]
    count = _call(lib.bitmask_count, wide, n_real, pack)
    rows = np.empty(count, dtype=np.int64)
    k = _call(lib.bitmask_decode, wide, bids, n_real, pack, block, rows)
    assert k == count
    return rows


def merge_rows_spans(lo, hi, rows, cert):
    """(rows, certain) union of contained spans [lo[k], hi[k]) (certain)
    and ascending kernel rows, deduplicated — one C++ two-pointer pass,
    or None."""
    lib = _load()
    if lib is None:
        return None
    lo = np.ascontiguousarray(lo, dtype=np.int64)
    hi = np.ascontiguousarray(hi, dtype=np.int64)
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    cert8 = np.ascontiguousarray(cert, dtype=np.uint8)
    cap = int((hi - lo).sum()) + len(rows)
    out_rows = np.empty(cap, dtype=np.int64)
    out_cert = np.empty(cap, dtype=np.uint8)
    k = _call(lib.merge_rows_spans, lo, hi, len(lo), rows, cert8, len(rows), out_rows, out_cert)
    return out_rows[:k], out_cert[:k].astype(bool)


def counting_argsort(keys, n_buckets: int) -> "np.ndarray | None":
    """Stable O(n) argsort of int keys in [0, n_buckets) — the spatial
    join's cell-id sort (np.argsort stable is n log n). Returns uint32
    perm, or None when native is unavailable, n >= 2^32, or any key is
    out of range (the C++ indexes its offsets vector by key unchecked)."""
    lib = _load()
    if lib is None or len(keys) >= (1 << 32) or n_buckets > (1 << 31) - 2:
        return None
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    if len(keys) and (keys.min() < 0 or keys.max() >= n_buckets):
        return None
    keys = keys.astype(np.int32)
    perm = np.empty(len(keys), dtype=np.uint32)
    _call(lib.counting_argsort, keys, len(keys), int(n_buckets), perm)
    return perm


def zranges(dims, bits_per_dim, mins, maxes, inner_mins, inner_maxes,
            max_ranges, max_recurse):
    """Covering z-ranges of ``nq`` unions of ordinal boxes, one
    decomposition each (C++ BFS + zdiv tightening; see geomesa_native.cpp
    zranges_cpp). The boxes are u64 ``[nq, nbox, dims]``; containment is
    classified against the inner boxes. Returns (lo u64[k], hi u64[k],
    contained bool[k], counts i64[nq]), query q's ranges after query
    q-1's, or None when native is unavailable."""
    lib = _load()
    if lib is None:
        return None
    mins = np.ascontiguousarray(mins, dtype=np.uint64)
    maxes = np.ascontiguousarray(maxes, dtype=np.uint64)
    inner_mins = np.ascontiguousarray(inner_mins, dtype=np.uint64)
    inner_maxes = np.ascontiguousarray(inner_maxes, dtype=np.uint64)
    nq, nbox = mins.shape[0], mins.shape[1]
    cap = nq * max(int(max_ranges) * 2 + 64, 256)
    lo = np.empty(cap, dtype=np.uint64)
    hi = np.empty(cap, dtype=np.uint64)
    cont = np.empty(cap, dtype=np.uint8)
    counts = np.empty(nq, dtype=np.int64)
    n = _call(
        lib.zranges_each_cpp, dims, bits_per_dim, nq, nbox,
        mins.reshape(-1), maxes.reshape(-1),
        inner_mins.reshape(-1), inner_maxes.reshape(-1),
        int(max_ranges), int(max_recurse), lo, hi, cont, counts, cap,
    )
    if n < 0:
        return None
    return lo[:n].copy(), hi[:n].copy(), cont[:n].astype(bool), counts


def points_in_polygon(px, py, rings, ring_part) -> "np.ndarray | None":
    """Even-odd point-in-polygon over flattened rings, or None when the
    native library is unavailable. ``rings`` is a list of closed [k, 2]
    f64 rings; ``ring_part[r]`` groups rings into multipolygon parts
    (within a part parity XORs; parts OR). Crossing semantics match
    geometry.points_in_ring exactly."""
    lib = _load()
    if lib is None:
        return None
    px = np.ascontiguousarray(px, dtype=np.float64)
    py = np.ascontiguousarray(py, dtype=np.float64)
    verts = np.ascontiguousarray(
        np.concatenate(rings, axis=0), dtype=np.float64
    )
    offsets = np.concatenate(
        [[0], np.cumsum([len(r) for r in rings])]
    ).astype(np.int64)
    part = np.ascontiguousarray(ring_part, dtype=np.int32)
    out = np.empty(len(px), dtype=np.uint8)
    _call(
        lib.points_in_polygon_cpp, px, py, len(px), verts, offsets, len(rings), part, out
    )
    return out.astype(bool)
