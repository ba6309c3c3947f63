"""``FeatureCollection.take`` gathers an answer's rows of all columns in
one pass: NumPy's indexing alone under ``features.NATIVE_TAKE_BYTES``
(rows x bytes a row), one native ``gather_columns`` call from it on.
The oracle is ``np.asarray(col)[idx]``, column by column: dtype, bytes,
contiguity and ownership.

- every dtype of the benchmark's ``gdelt`` type, a PointColumn, a packed
  geometry column, a Bytes column; int64 and ``<U`` ids;
- sizes 0, 1, below, at and above the constant; sorted, shuffled and
  repeated ordinals of several integer dtypes;
- IndexError and negative ordinals as NumPy's, on both sides of it;
- the native side makes one call an answer and none a column, and keeps
  no address of a column that was replaced;
- ``DataStore.gather`` over several chunks, eight threads at once, a
  store with no native library, the ``decode`` span's ``gather_native``.
"""

import importlib.util
import os
import pickle
import sys
import threading
import types
from unittest import mock

import numpy as np
import pytest

from geomesa_tpu import conf, features, geometry as geo, native, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.predicates import PointColumn
from geomesa_tpu.sft import FeatureType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SPEC = (
    "code2:String,code3:String,code4:String,code6:String,gid:String,name:String,"
    "root:Integer,mentions:Long,tone:Double,payload:Bytes,dtg:Date,"
    "area:Polygon:srid=4326,*geom:Point:srid=4326"
)
U_WIDTHS = {"code2": 2, "code3": 3, "code4": 4, "code6": 6, "gid": 10, "name": 24}
N = 4096
AT = 64  # the rows at which the patched constant sits


def _collection(n=N, ids="int64", seed=7):
    rng = np.random.default_rng(seed)
    sft = FeatureType.from_spec("t", SPEC)
    cols = {
        name: np.array(["%x" % v for v in rng.integers(0, 16 ** min(w, 12), n)], dtype=f"<U{w}")
        for name, w in U_WIDTHS.items()
    }
    cols["root"] = rng.integers(-5, 5, n).astype(np.int32)
    cols["mentions"] = rng.integers(0, 1 << 40, n)
    tone = rng.normal(size=n)
    tone[::97] = np.nan
    cols["tone"] = tone
    payload = np.empty(n, dtype=object)
    payload[:] = [None if i % 11 == 0 else bytes([i % 251]) * (i % 5) for i in range(n)]
    cols["payload"] = payload
    cols["dtg"] = np.sort(rng.integers(0, 1 << 41, n))
    x, y = rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)
    cols["geom"] = PointColumn(x, y)
    cols["area"] = geo.PackedGeometryColumn.from_geometries([
        geo.Polygon(np.array([[a, b], [a + 1, b], [a + 1, b + 1 + i % 3], [a, b]]))
        for i, (a, b) in enumerate(zip(x, y))
    ])
    fid = np.arange(n, dtype=np.int64) if ids == "int64" else np.array([f"f{i:07d}" for i in range(n)])
    return FeatureCollection.from_columns(sft, fid, cols)


@pytest.fixture(scope="module")
def collections():
    return {kind: _collection(ids=kind) for kind in ("int64", "U")}


@pytest.fixture()
def low_constant(monkeypatch):
    """``low_constant(fc)`` puts the constant at AT rows of ``fc``, so
    that both sides of it are cheap to reach; the cases name their side."""
    def at(fc):
        monkeypatch.setattr(features, "NATIVE_TAKE_BYTES", AT * fc._row_bytes())
        return fc
    return at


def _arrays(fc):
    """{name: ndarray} of everything ``take`` indexes with NumPy's rule."""
    out = {"__id__": fc.ids}
    for name, col in fc.columns.items():
        if isinstance(col, PointColumn):
            out[name + ".x"], out[name + ".y"] = col.x, col.y
        elif not isinstance(col, geo.PackedGeometryColumn):
            out[name] = col
    return out


def _same(got: np.ndarray, want: np.ndarray, what=""):
    assert type(got) is np.ndarray, what
    assert got.dtype == want.dtype and got.shape == want.shape, what
    assert got.flags.c_contiguous and got.flags.owndata and got.base is None, what
    if want.dtype.hasobject:
        assert got.tolist() == want.tolist(), what
    else:
        assert got.tobytes() == want.tobytes(), what  # bit-equal: NaN payloads too


def _check(fc, idx, got=None):
    got = fc.take(idx) if got is None else got
    want = {k: np.asarray(v)[idx] for k, v in _arrays(fc).items()}
    have = _arrays(got)
    assert list(have) == list(want) and got.sft is fc.sft
    for k in want:
        _same(have[k], want[k], k)
    for name, col in fc.columns.items():
        if isinstance(col, geo.PackedGeometryColumn):
            a, b = got.columns[name], col.take(np.asarray(idx))
            assert np.array_equal(a.bboxes, b.bboxes) and np.array_equal(a.coords, b.coords)
    return got


def _ordinals(size, order, dtype, n=N, seed=3):
    rng = np.random.default_rng(seed + size)
    if order == "repeated":
        idx = rng.integers(0, max(n // 64, 1), size)
    else:
        idx = rng.permutation(n)[:size] if size <= n else rng.integers(0, n, size)
        if order == "sorted":
            idx = np.sort(idx)
    return idx.astype(dtype)


SIZES = {"empty": 0, "one": 1, "below": AT - 1, "at": AT, "above": AT + 1, "large": 3000}


@pytest.mark.parametrize("ids", ["int64", "U"])
@pytest.mark.parametrize("dtype", ["uint32", "int32", "int64"])
@pytest.mark.parametrize("order", ["sorted", "shuffled", "repeated"])
@pytest.mark.parametrize("size", list(SIZES))
def test_take_is_numpys_indexing_of_every_column(collections, low_constant, size, order, dtype, ids):
    fc = low_constant(collections[ids])
    got = _check(fc, _ordinals(SIZES[size], order, dtype))
    assert got.gathered_native == (SIZES[size] >= AT)
    assert len(got) == SIZES[size]


@pytest.mark.parametrize("dtype", ["int8", "uint8", "int16", "uint16", "uint64"])
def test_other_integer_ordinals(collections, low_constant, dtype):
    idx = (np.arange(300) % 120).astype(dtype)
    assert _check(low_constant(collections["int64"]), idx).gathered_native


@pytest.mark.parametrize("idx", [
    np.arange(0, 2 * AT)[::2],                    # a strided view of ordinals
    np.arange(3 * AT).reshape(3, AT),             # 2-D: NumPy's shapes
    np.zeros(N, bool) | (np.arange(N) % 3 == 0),  # a mask
    slice(10, 10 + 2 * AT),                       # what np.asarray makes 0-d
    list(range(2 * AT)),
], ids=["strided", "two-d", "bool", "slice", "list"])
def test_what_is_not_a_1d_integer_array_is_numpys(collections, low_constant, idx):
    fc = low_constant(collections["int64"])
    if isinstance(idx, slice):
        with pytest.raises(IndexError):
            fc.take(idx)  # as before: an object array is no index
        return
    arr = np.asarray(idx)
    if arr.ndim == 1 and arr.dtype.kind in "iu":
        assert _check(fc, idx).gathered_native
        return
    point_only = FeatureCollection(
        fc.sft, fc.ids, {k: v for k, v in fc.columns.items() if k != "area"}
    )
    _check(point_only, arr)  # (the packed column takes 1-D ordinals only)


@pytest.mark.parametrize("side", ["small", "large"])
@pytest.mark.parametrize("bad", [N, N + 5, -N - 1], ids=["n", "past", "under"])
def test_out_of_range_raises_index_error(collections, low_constant, side, bad):
    idx = np.arange(4 if side == "small" else 4 * AT, dtype=np.int64)
    idx[len(idx) // 2] = bad
    for fc in collections.values():
        with pytest.raises(IndexError):
            low_constant(fc).take(idx)


@pytest.mark.parametrize("side", ["small", "large"])
def test_negative_ordinals_count_from_the_end(collections, low_constant, side):
    fc = low_constant(collections["int64"])
    point_only = FeatureCollection(
        fc.sft, fc.ids, {k: v for k, v in fc.columns.items() if k != "area"}
    )
    idx = -1 - np.arange(4 if side == "small" else 4 * AT, dtype=np.int64)
    idx[::3] = 5
    got = _check(point_only, idx)
    assert not got.gathered_native  # NumPy answers: the native copy is unchecked


def test_the_real_constant_splits_small_from_large(collections):
    fc = collections["int64"]
    rowb = fc._row_bytes()
    assert rowb == 8 + sum(4 * w for w in U_WIDTHS.values()) + 4 + 8 + 8 + 8 + 8 + 16
    at = -(-features.NATIVE_TAKE_BYTES // rowb)
    rng = np.random.default_rng(1)
    assert not _check(fc, rng.integers(0, N, at - 1)).gathered_native
    assert _check(fc, rng.integers(0, N, at)).gathered_native


# -- one call an answer ----------------------------------------------------

def _bench(name, *rel):
    """A module of benchmark/ by path, its ``harness`` and
    ``layer_metrics`` imports served by path too and left out of
    ``sys.modules`` (other test files load their own)."""
    def load(mod_name, *parts, shim=None):
        spec = importlib.util.spec_from_file_location(mod_name, os.path.join(BENCH, *parts))
        mod = importlib.util.module_from_spec(spec)
        with mock.patch.dict(sys.modules, shim or {}):
            spec.loader.exec_module(mod)
        return mod

    harness = types.ModuleType("harness")
    harness.data = load(name + "_data", "harness", "data.py")
    harness.stats = load(name + "_stats", "harness", "stats.py")
    shim = {"harness": harness, "harness.data": harness.data, "harness.stats": harness.stats}
    layer_metrics = types.ModuleType("layer_metrics")
    layer_metrics._segments = load(name + "_segments", "layer_metrics", "_segments.py", shim=shim)
    shim.update({"layer_metrics": layer_metrics, "layer_metrics._segments": layer_metrics._segments})
    return load(name, *rel, shim=shim)


def _gdelt_collection(n=2048):
    """The benchmark's schema and generator (19 String, 5 Integer, 2
    Double, dtg, geom), loaded as benchmark/stores/datastore.py does."""
    import json

    gdelt = _bench("_take_paths_gdelt", "datagen", "gdelt.py")
    with open(os.path.join(BENCH, "configs", "gdelt-events-1chip.json")) as fh:
        cfg = json.load(fh)
    cols = gdelt.make(cfg, n, 4_300_000_019)
    sft = FeatureType.from_spec(cfg["type_name"], cfg["schema"])
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
    return FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)


@pytest.fixture()
def counted(monkeypatch):
    """Calls that cross into the library: gather_columns counted, the
    single-dtype gathers forbidden."""
    lib = native._load()
    assert lib is not None, "the native tier did not build"
    calls = []

    class Counting:
        def __getattr__(self, name):
            fn = getattr(lib, name)
            if name.startswith("gather_"):
                def fn(*a, _fn=fn, _name=name):
                    calls.append(_name)
                    return _fn(*a)
            return fn

    monkeypatch.setattr(native, "_lib", Counting())
    return calls


def test_a_gdelt_take_crosses_into_the_library_at_most_once(counted, monkeypatch):
    fc = _gdelt_collection()
    assert len(fc.columns) == 27 and fc._row_bytes() == 472 + 8 + 8 + 16
    dtypes = sorted({str(np.asarray(c).dtype) for c in fc.columns.values() if not isinstance(c, PointColumn)})
    assert dtypes == ["<U10", "<U2", "<U24", "<U3", "<U4", "<U6", "float64", "int32", "int64"]
    rng = np.random.default_rng(0)
    small = rng.integers(0, len(fc), 9)
    _check(fc, small)
    assert counted == []  # a small answer: NumPy alone
    monkeypatch.setattr(features, "NATIVE_TAKE_BYTES", 100 * fc._row_bytes())
    big = rng.integers(0, len(fc), 1500)
    got = _check(fc, big)
    assert counted == ["gather_columns"] and got.gathered_native
    keep = rng.random(1500) < 0.5
    _check(got, np.flatnonzero(keep), got.mask(keep))  # the refinement's mask
    assert counted == ["gather_columns"] * 2


def test_the_answers_table_is_in_hand(counted, low_constant, monkeypatch):
    fc = low_constant(_collection(seed=11))
    built = []
    real = native.ColumnTable.__init__
    monkeypatch.setattr(native.ColumnTable, "__init__",
                        lambda self, arrays, like=None: built.append(like is None) or real(self, arrays, like))
    first = fc.take(np.arange(2 * AT))
    assert built == [True, False]  # the collection's own, then the outputs'
    _check(first, np.arange(AT, 2 * AT)[::-1].copy())
    _check(fc, np.arange(AT)[::-1].copy())
    assert built == [True, False, False, False]  # neither built addresses again


def test_a_replaced_column_is_read_not_remembered(low_constant):
    fc = low_constant(_collection(seed=5))
    idx = np.arange(2 * AT)[::-1].copy()
    _check(fc, idx)
    fc.columns["tone"] = np.arange(N, dtype=np.float64)  # another object, the old one freed
    fc.columns["name"] = fc.columns["name"].astype("<U30")
    got = _check(fc, idx)
    assert got.gathered_native and got.columns["tone"][0] == idx[0]
    fc.columns["root"][:] = 7  # in place: the same address, new values
    assert (_check(fc, idx).columns["root"] == 7).all()
    fc.ids = fc.ids[::-1].copy()
    _check(fc, idx)


def test_columns_the_copy_cannot_serve_keep_their_route(low_constant):
    fc = low_constant(_collection(seed=9))
    wide = np.arange(2 * N, dtype=np.int32)
    fc.columns["root"] = wide[::2]                       # strided: NumPy's route
    fc.columns["mentions"] = np.arange(3 * N).reshape(N, 3)  # rows of three: one item
    fc.columns["code3"] = fc.columns["code3"].astype(">U3")  # bytes are bytes
    idx = np.random.default_rng(2).integers(0, N, 5 * AT)
    got = _check(fc, idx)
    assert got.gathered_native and got.columns["mentions"].shape == (5 * AT, 3)


def test_a_collection_travels_without_its_addresses(low_constant):
    fc = low_constant(_collection(seed=13))
    idx = np.arange(3 * AT)
    got = fc.take(idx)
    for src in (fc, got):
        assert "_gather" in src.__dict__
        back = pickle.loads(pickle.dumps(src))
        assert "_gather" not in back.__dict__
        _check(back, idx[: 2 * AT])


def test_without_the_library_numpy_answers(low_constant, monkeypatch):
    monkeypatch.setattr(native, "_lib", False)
    got = _check(low_constant(_collection(seed=15)), np.arange(4 * AT))
    assert not got.gathered_native


# -- callers ---------------------------------------------------------------

@pytest.mark.parametrize("side", ["small", "large"])
def test_gather_over_chunks_keeps_the_ordinals_order(low_constant, side):
    whole = low_constant(_collection(seed=21))
    cuts = [0, 700, 701, 2500, N]
    chunks = [whole.take(np.arange(a, b)) for a, b in zip(cuts, cuts[1:])]
    ds = DataStore()
    ds.create_schema(whole.sft)
    rng = np.random.default_rng(4)
    ordinals = rng.integers(0, N, 12 if side == "small" else 6 * AT)
    got = ds.gather("t", ordinals, chunks=chunks)
    _check(whole, ordinals, got)
    assert got.gathered_native == (side == "large")
    one = ds.gather("t", np.sort(ordinals)[:5] % 700, chunks=chunks)
    _check(whole, np.sort(ordinals)[:5] % 700, one)


@pytest.mark.parametrize("side", ["small", "large"])
def test_eight_threads_take_at_once(collections, low_constant, side):
    fc = low_constant(collections["U"])
    rng = np.random.default_rng(8)
    jobs = [rng.integers(0, N, (AT // 2) if side == "small" else 8 * AT) for _ in range(8)]
    results, errors = [None] * 8, []

    def work(k):
        try:
            for _ in range(20):
                results[k] = fc.take(jobs[k])
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    for k in range(8):
        _check(fc, jobs[k], results[k])


@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    conf.OBS_SLOW_MS.set(0.0)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    conf.OBS_SLOW_MS.clear()
    obs.install(obs.Tracer())


def _view(traces):
    """The window's spans as benchmark/harness/instrument.py lists them."""
    return {"spans": [
        {"trace": tr.trace_id, "root": tr.name, "id": s.span_id, "parent": s.parent_id,
         "name": s.name, "t0": s.t0, "dur_s": s.dur_s, "self_s": s.dur_s,
         "attrs": dict(s.attrs or {})}
        for tr in traces for s in [tr.root] + list(tr.spans)
    ]}


def test_the_decode_span_says_which_side_served(traced, monkeypatch):
    read = _bench("_take_paths_reader", "layer_metrics", "gather_native_pct.py").read
    fc = _gdelt_collection(1 << 13)
    fc.sft.user_data["geomesa.indices.enabled"] = "z3,z2"
    ds = DataStore()
    ds.create_schema(fc.sft)
    ds.write(fc.sft.name, fc, check_ids=False)
    t0, t1 = (int(v) for v in np.quantile(fc.columns["dtg"], (0.1, 0.9)))
    iso = [str(np.datetime64(t, "ms")) + "Z" for t in (t0, t1)]
    box = f"BBOX(geom, -90, -45, 90, 45) AND dtg DURING {iso[0]}/{iso[1]}"
    for constant, want in ((1, 1), (1 << 62, 0), (1, 1), (1, 1)):
        monkeypatch.setattr(features, "NATIVE_TAKE_BYTES", constant)
        got = ds.query(fc.sft.name, box)
        assert len(got) > 100
        decode = [s for s in traced()[-1].spans if s.name == "decode"]
        assert [s.attrs["gather_native"] for s in decode] == [want]
        assert decode[0].attrs["candidates"] >= len(got)
    assert read(_view(traced())) == 75.0
    assert read(_view(traced()[1:2])) == 0.0
    bare = _view(traced())
    for s in bare["spans"]:
        s["attrs"].pop("gather_native", None)  # the parent's spans
    assert read(bare) is None and read({"spans": []}) is None
    many = ds.query_many(fc.sft.name, [box, box.replace("-90", "-60")])
    assert [len(m) > 50 for m in many] == [True, True]
    decode = [s for s in traced()[-1].spans if s.name == "decode"]
    assert sorted((s.attrs["member"], s.attrs["gather_native"]) for s in decode) == [(0, 1), (1, 1)]


def test_the_metric_is_every_cells(traced):
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    store = {}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            store[c["name"]] = json.load(fh)["store"]
    entry = next(m for m in bench["per_layer"] if m["name"] == "gather_native_pct")
    assert entry == {
        "name": "gather_native_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tables and native tier",
        "moves": "queries_per_s",
        # every cell whose store answers rows; a store of joins (PR 41:
        # ``stores/datastore_join.py``) answers pairs and opens no ``decode``
        "workloads": [w["name"] for w in bench["workloads"]
                      if store[w["config"]] != "datastore_join"],
    }
