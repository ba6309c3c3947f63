"""The Arrow table build's two routes give the same bytes (io/arrow.py):
the whole record batch out of ONE call of ``native.arrow_batch``, handed
to Arrow through its C data interface, where every column can be read
without the interpreter, an array a pyarrow call where one cannot. The
oracle is the pyarrow route as it stood before the native one
(``_pyarrow_table``), written through the writer ``arrow_stream`` uses.

- the benchmark's ``gdelt`` type on the benchmark's own rows and every
  supported dtype alone, at 0, 1, 9, 43 and 5,000 rows;
- strings: several ``<U`` widths, non-ASCII of two to four UTF-8 bytes,
  repeated and all-distinct values (dictionary order is first
  appearance), empty cells, a cell cut at its first NUL as pyarrow cuts;
- ``<U`` and int64 ids, no geometry, ``dictionary=False``, strided and
  read-only columns, a table that outlives its columns, eight threads;
- each fallback (an object column with ``None``, ``Bytes``, a packed
  geometry, NaT, a byte-swapped column, a String held as numbers, a
  surrogate, no native library) builds the old table and round-trips
  through ``read_arrow``;
- ``ArrowChunks``: any page size concatenates to ``arrow_stream`` with
  the same batch rows; one page is one chunk, a longer answer a page a
  chunk; served, the ``encode`` span says which route.
"""

import gc
import json
import os
import sys
import threading
import types

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from geomesa_tpu import conf, geometry as geo, native, obs  # noqa: E402
from geomesa_tpu.datastore import DataStore  # noqa: E402
from geomesa_tpu.features import FeatureCollection  # noqa: E402
from geomesa_tpu.filter.predicates import PointColumn  # noqa: E402
from geomesa_tpu.io import arrow  # noqa: E402
from geomesa_tpu.io.arrow import (  # noqa: E402
    ArrowChunks,
    arrow_stream,
    read_arrow,
    to_arrow_table,
)
from geomesa_tpu.serving import DataClient  # noqa: E402
from geomesa_tpu.sft import FeatureType  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BENCH_PACKAGES = ("harness", "datagen", "layer_metrics")
SEED = 4_300_000_019
ROWS = (0, 1, 9, 43, 5000)

pytestmark = pytest.mark.skipif(not native.available(), reason="no native tier")


def _stream(table, batch_rows=arrow.BATCH_ROWS) -> bytes:
    """``arrow_stream``'s writer over a table that is already built."""
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, table.schema) as w:
        for batch in table.to_batches(max_chunksize=batch_rows):
            w.write_batch(batch)
    return sink.getvalue().to_pybytes()


def _same(fc, native_route=True, dictionary=True, batch_rows=64):
    """Both routes' tables and streams agree; which route built it."""
    want = arrow._pyarrow_table(pa, fc, dictionary)
    batch = arrow._native_batch(pa, fc, dictionary)
    assert (batch is not None) is native_route
    got = to_arrow_table(fc, dictionary=dictionary)
    assert got.schema.equals(want.schema, check_metadata=True)
    assert _stream(got) == _stream(want)
    for rows in (batch_rows, arrow.BATCH_ROWS):
        assert arrow_stream(fc, dictionary=dictionary, batch_rows=rows) == _stream(want, rows)
    return got


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator and the new readers, imported as the
    benchmark imports them."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import gdelt
        from layer_metrics import arrow_native_pct, encode_arrow_ms, encode_native_pct

        yield types.SimpleNamespace(
            make=gdelt.make, arrow_native_pct=arrow_native_pct,
            encode_arrow_ms=encode_arrow_ms, encode_native_pct=encode_native_pct)
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def gdelt(bench):
    """5,000 of the dashboard cell's rows as ``stores/datastore.py`` loads them."""
    with open(os.path.join(BENCH, "configs", "gdelt-events-1chip.json")) as fh:
        cfg = json.load(fh)
    n = max(ROWS)
    cols = bench.make(cfg, n, SEED)
    sft = FeatureType.from_spec(cfg["type_name"], cfg["schema"])
    columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
    return FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), columns)


def _points(n, seed=3):
    rng = np.random.default_rng(seed)
    return PointColumn(rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))


def _one(spec_type, values, name="v", ids=None, geom=True):
    """A collection of one attribute (and a point) over ``values``."""
    n = len(values)
    spec = f"{name}:{spec_type}" + (",*geom:Point:srid=4326" if geom else "")
    columns = {name: values}
    if geom:
        columns["geom"] = _points(n)
    ids = np.arange(n, dtype=np.int64) if ids is None else ids
    return FeatureCollection(FeatureType.from_spec("t", spec), ids, columns)


# -- the benchmark's type ---------------------------------------------------

@pytest.mark.parametrize("n", ROWS)
def test_the_gdelt_type_at_the_cells_answer_sizes(gdelt, n):
    fc = gdelt.take(np.arange(n))
    table = _same(fc)
    assert table.num_rows == n and table.num_columns == 28
    assert pa.types.is_dictionary(table.schema.field("actor1Name").type)
    assert table.schema.field("dtg").type == pa.timestamp("ms")
    assert table.schema.field("geom").type == pa.list_(pa.float64(), 2)


def test_the_gdelt_type_reads_back(gdelt):
    fc = gdelt.take(np.arange(43))
    back = read_arrow(arrow_stream(fc))
    assert back.ids.tolist() == fc.ids.tolist()
    for a in fc.sft.attributes:
        if a.name == "geom":
            assert np.array_equal(back.columns["geom"].x, fc.columns["geom"].x)
            assert np.array_equal(back.columns["geom"].y, fc.columns["geom"].y)
        else:
            assert back.columns[a.name].tolist() == fc.columns[a.name].tolist(), a.name


# -- every supported dtype, alone --------------------------------------------

TEXT = ["", "a", "Zürich", "Ελλάδα", "日本語", "\U0001f600 smile", 'q"uote\\', "tab\there", "x" * 24]


def _strings(n, width, distinct):
    if distinct:
        return np.array([f"{i:x}" for i in range(n)], dtype=f"<U{width}")
    rng = np.random.default_rng(n + width)
    return np.array(TEXT, dtype=f"<U{width}")[rng.integers(0, len(TEXT), n)]


COLUMNS = {
    "U1": ("String", lambda n: _strings(n, 1, False)),
    "U5": ("String", lambda n: _strings(n, 5, False)),
    "U24": ("String", lambda n: _strings(n, 24, False)),
    "U64-distinct": ("String", lambda n: _strings(n, 64, True)),
    "uuid": ("UUID", lambda n: _strings(n, 36, True)),
    "U-not-a-string": ("Integer", lambda n: _strings(n, 8, False)),
    "bool": ("Boolean", lambda n: np.random.default_rng(n).random(n) < 0.4),
    "int8": ("Integer", lambda n: np.arange(n).astype(np.int8)),
    "int16": ("Integer", lambda n: (np.arange(n) * 7 - 9).astype(np.int16)),
    "int32": ("Integer", lambda n: np.random.default_rng(n).integers(-2**31, 2**31 - 1, n).astype(np.int32)),
    "int64": ("Long", lambda n: np.random.default_rng(n).integers(-2**63, 2**63 - 1, n)),
    "uint8": ("Integer", lambda n: np.arange(n).astype(np.uint8)),
    "uint32": ("Long", lambda n: (np.arange(n) * 1_000_003).astype(np.uint32)),
    "uint64": ("Long", lambda n: (np.arange(n, dtype=np.uint64) * np.uint64(2**60 + 7))),
    "f32": ("Float", lambda n: np.random.default_rng(n).normal(size=n).astype(np.float32)),
    "f64": ("Double", lambda n: np.where(np.arange(n) % 11 == 3, np.nan, np.random.default_rng(n).normal(size=n))),
    "f64-inf": ("Double", lambda n: np.where(np.arange(n) % 2 == 0, np.inf, -0.0)),
    "date": ("Date", lambda n: np.random.default_rng(n).integers(-10**12, 2 * 10**12, n)),
}


@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("case", list(COLUMNS))
def test_every_supported_dtype(case, n):
    spec_type, make = COLUMNS[case]
    _same(_one(spec_type, make(n), name="dtg" if spec_type == "Date" else "v"))


@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("ids", ["int64", "U", "U-wide"])
def test_ids(ids, n):
    values = {"int64": np.arange(n, dtype=np.int64) * 2**40 - 5,
              "U": np.array([f"f{i}" for i in range(n)], dtype="<U8"),
              "U-wide": _strings(n, 40, False)}[ids]
    table = _same(_one("Integer", np.arange(n, dtype=np.int32), ids=values))
    assert table.column("id").to_pylist() == [s if ids == "int64" else str(s) for s in values.tolist()]


def test_a_type_with_no_geometry_and_one_with_nothing_else():
    _same(_one("Integer", np.arange(5, dtype=np.int32), geom=False))
    sft = FeatureType.from_spec("t", "*geom:Point:srid=4326")
    for n in (0, 3):
        _same(FeatureCollection(sft, np.arange(n, dtype=np.int64), {"geom": _points(n)}))


@pytest.mark.parametrize("n", ROWS)
def test_dictionary_values_come_in_order_of_first_appearance(n):
    rng = np.random.default_rng(7)
    vocab = np.array(["zz", "", "b", "ä", "a" * 9, "Z"], dtype="<U9")
    col = vocab[rng.integers(0, len(vocab), n)]
    table = _same(_one("String", col))
    d = table.column("v").chunk(0)
    first = list(dict.fromkeys(col.tolist()))
    assert d.dictionary.to_pylist() == first
    assert d.indices.to_pylist() == [first.index(v) for v in col.tolist()]
    distinct = _same(_one("String", _strings(n, 12, True))).column("v").chunk(0)
    assert len(distinct.dictionary) == n


@pytest.mark.parametrize("n", ROWS)
def test_plain_strings_where_no_dictionary_is_asked(gdelt, n):
    table = _same(gdelt.take(np.arange(n)), dictionary=False)
    assert table.schema.field("actor1Name").type == pa.string()


def test_a_cell_ends_at_its_first_nul_as_pyarrows_does():
    col = np.array(["a\0b", "ab\0", "\0x", "abc"], dtype="<U3")
    table = _same(_one("String", col, ids=col))
    assert table.column("id").to_pylist() == ["a", "ab", "", "abc"]


def test_strided_and_read_only_columns():
    v = np.arange(40, dtype=np.int32)[::2]
    s = np.array([f"s{i % 7}" for i in range(40)])[::-2]
    d = np.linspace(0, 1, 20)
    d.setflags(write=False)
    b = (np.arange(60) % 3 == 0)[::3]
    t = np.arange(40, dtype=np.int64)[::2] * 86_400_000
    sft = FeatureType.from_spec("t", "a:Integer,b:String,c:Double,d:Boolean,dtg:Date,*geom:Point:srid=4326")
    x = np.linspace(-10, 10, 60)
    _same(FeatureCollection(sft, np.arange(100, 140, 2, dtype=np.int64),
                            {"a": v, "b": s, "c": d, "d": b, "dtg": t,
                             "geom": PointColumn(x[::3], x[40:])}))


def test_the_geometry_keeps_its_place_among_the_attributes():
    sft = FeatureType.from_spec("t", "a:Integer,*geom:Point:srid=4326,b:String,dtg:Date")
    n = 9
    fc = FeatureCollection(sft, np.arange(n, dtype=np.int64), {
        "a": np.arange(n, dtype=np.int32), "geom": _points(n),
        "b": _strings(n, 5, False), "dtg": np.arange(n, dtype=np.int64)})
    assert _same(fc).column_names == ["id", "a", "geom", "b", "dtg"]


def test_the_table_owns_its_buffers(gdelt):
    fc = gdelt.take(np.arange(300))
    want = arrow._pyarrow_table(pa, fc, True).to_pydict()
    table = to_arrow_table(fc)
    del fc
    gc.collect()
    np.zeros(1 << 22)  # a fresh allocation over whatever was freed
    assert table.to_pydict() == want
    part = table.column("actor1Name").chunk(0).dictionary
    del table
    gc.collect()
    assert part.to_pylist() == list(dict.fromkeys(want["actor1Name"]))


def test_eight_threads_build_their_own(gdelt):
    want = [_stream(arrow._pyarrow_table(pa, gdelt.take(np.arange(k, k + 40)), True)) for k in range(8)]
    got, errors = [None] * 8, []

    def work(k):
        try:
            sub = gdelt.take(np.arange(k, k + 40))
            for _ in range(50):
                got[k] = b"".join(ArrowChunks(sub, 16))
                assert got[k] == _stream(arrow._pyarrow_table(pa, sub, True), 16)
            got[k] = arrow_stream(sub)
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == [] and got == want


# -- what the native route leaves to pyarrow -----------------------------------

def _objects(values):
    out = np.empty(len(values), dtype=object)
    out[:] = values
    return out


def _packed():
    sft = FeatureType.from_spec("t", "name:String,*geom:Polygon:srid=4326")
    polys = [geo.Polygon(np.array([[0, 0], [1 + i, 0], [1 + i, 1], [0, 0]], dtype=float)) for i in range(3)]
    return FeatureCollection.from_columns(sft, np.arange(3, dtype=np.int64), {
        "name": np.array(["a", "b", "a"]), "geom": geo.PackedGeometryColumn.from_geometries(polys)})


FALLBACKS = {
    "object-column-with-none": lambda: _one("String", _objects(["a", None, "b", None])),
    "bytes": lambda: _one("Bytes", _objects([b"\x00\x01", b"", b"xyz"])),
    "packed-geometry": _packed,
    "nat": lambda: _one("Date", np.array([0, np.iinfo(np.int64).min, 5]), name="dtg"),
    "string-held-as-ints": lambda: _one("String", np.arange(4, dtype=np.int64)),
    "int32-date": lambda: _one("Date", np.array([0, 1], dtype=np.int32), name="dtg"),
    "half-floats": lambda: _one("Float", np.array([0.5, 1.5], dtype=np.float16)),
    "object-ids": lambda: _one("Integer", np.arange(2, dtype=np.int32), ids=_objects(["a", "b"])),
    "int32-ids": lambda: _one("Integer", np.arange(2, dtype=np.int32), ids=np.arange(2, dtype=np.int32)),
    "float32-point": lambda: FeatureCollection(
        FeatureType.from_spec("t", "*geom:Point:srid=4326"), np.arange(2, dtype=np.int64),
        {"geom": PointColumn(np.zeros(2, np.float32), np.ones(2, np.float32))}),
}


@pytest.mark.parametrize("case", list(FALLBACKS))
def test_what_the_native_route_leaves_to_pyarrow(case):
    fc = FALLBACKS[case]()
    table = _same(fc, native_route=False)
    chunks = ArrowChunks(fc, 2)
    payload = b"".join(chunks)
    assert chunks.arrow_native is False and payload == _stream(table, 2)
    back = read_arrow(payload)
    assert len(back) == len(fc) and list(map(str, back.ids.tolist())) == list(map(str, np.asarray(fc.ids).tolist()))
    for a in fc.sft.attributes:
        if a.name != fc.sft.geom_field and a.type in ("String", "Bytes"):
            assert back.columns[a.name].tolist() == [
                v if v is None or a.type == "Bytes" else str(v) for v in np.asarray(fc.columns[a.name]).tolist()]


def test_a_code_point_pyarrow_refuses_is_still_pyarrows_to_refuse():
    for bad in (0xD800, 0x110000):
        col = np.array([ord("a"), bad], dtype=np.uint32).view("<U1")
        fc = _one("String", col)
        assert arrow._native_batch(pa, fc, True) is None
        assert arrow._native_batch(pa, _one("Integer", np.arange(2, dtype=np.int32), ids=col), True) is None
        with pytest.raises(UnicodeDecodeError):
            to_arrow_table(fc)


def test_a_byte_swapped_column_is_still_pyarrows_to_refuse():
    fc = _one("Long", np.arange(3, dtype=">i8"))
    assert arrow._native_batch(pa, fc, True) is None
    with pytest.raises(pa.ArrowNotImplementedError):
        to_arrow_table(fc)


def test_an_attribute_called_id_keeps_the_old_table():
    fc = _one("String", _strings(4, 5, False), name="id")
    assert arrow._native_batch(pa, fc, True) is None
    assert to_arrow_table(fc).column_names == ["id", "geom"]


def test_without_the_native_tier(gdelt, monkeypatch):
    monkeypatch.setattr(native, "_lib", False)
    assert not native.available()
    fc = gdelt.take(np.arange(9))
    _same(fc, native_route=False)
    chunks = ArrowChunks(fc)
    assert read_arrow(b"".join(chunks)).ids.tolist() == fc.ids.tolist()
    assert chunks.arrow_native is False


def test_without_the_c_data_import(gdelt, monkeypatch):
    monkeypatch.setattr(arrow, "_pa", lambda: types.SimpleNamespace(
        **{k: getattr(pa, k) for k in dir(pa) if not k.startswith("_") and k != "RecordBatch"},
        RecordBatch=types.SimpleNamespace()))
    fc = gdelt.take(np.arange(9))
    chunks = ArrowChunks(fc)
    assert b"".join(chunks) == _stream(arrow._pyarrow_table(pa, fc, True))
    assert chunks.arrow_native is False


def test_an_import_that_fails_gives_the_buffers_back(gdelt, monkeypatch):
    calls = []
    real = native._load().arrow_batch_release
    monkeypatch.setattr(native._load(), "arrow_batch_release", lambda a: calls.append(a) or real(a))

    def refuse(address):
        raise MemoryError("no room")

    fc = gdelt.take(np.arange(9))
    table = native.GeoJSONColumns.of(fc.ids, None, [])
    with pytest.raises(MemoryError):
        native.arrow_batch(table, [native.AR_COPY], [0], refuse)
    assert len(calls) == 1
    held = native.arrow_batch(table, [native.AR_COPY], [0], lambda at: pa.RecordBatch._import_from_c(
        at, pa.schema([pa.field("id", pa.int64())])))
    assert len(calls) == 1 and held.column(0).to_pylist() == fc.ids.tolist()


# -- the chunks of one stream --------------------------------------------------

@pytest.mark.parametrize("n", ROWS)
@pytest.mark.parametrize("page", [1, 64, "rows", 4096])
def test_any_page_size_is_the_one_shot_stream(gdelt, n, page):
    if page == 1 and n > 100:
        n = 100
    fc = gdelt.take(np.arange(n))
    page = max(n, 1) if page == "rows" else page
    chunks = ArrowChunks(fc, page)
    assert chunks.arrow_native is None  # lazy: nothing is built before the first pull
    parts = list(chunks)
    assert chunks.arrow_native is True and chunks.py_writes == 0
    assert all(type(p) is bytes for p in parts)
    assert b"".join(parts) == arrow_stream(fc, batch_rows=page)
    pages = -(-n // page)
    assert len(parts) == (1 if pages < 2 else pages + 1)


def test_a_long_answer_holds_a_page_of_bytes_at_a_time(gdelt):
    fc = gdelt.take(np.arange(5000))
    parts = list(ArrowChunks(fc, 500))
    whole = arrow_stream(fc, batch_rows=500)
    assert b"".join(parts) == whole and len(parts) == 11
    assert parts[-1] == b"\xff\xff\xff\xff\x00\x00\x00\x00"
    # the first chunk carries the schema and the whole answer's dictionaries
    assert max(map(len, parts[1:])) < len(whole) / 10 and len(parts[0]) < len(whole) / 3
    assert pa.ipc.open_stream(whole).read_all().num_rows == 5000


def test_pyarrow_missing_raises_before_any_chunk(monkeypatch, gdelt):
    def gone():
        raise RuntimeError("arrow export requires pyarrow, which is not installed")

    monkeypatch.setattr(arrow, "_pa", gone)
    with pytest.raises(RuntimeError):
        ArrowChunks(gdelt.take(np.arange(3)))


# -- served: the ``encode`` span says which route ----------------------------

@pytest.fixture()
def traced():
    obs.install(obs.Tracer())
    conf.OBS_TRACE_SAMPLE.set(1)
    yield lambda: obs.tracer().traces()
    conf.OBS_TRACE_SAMPLE.clear()
    obs.install(obs.Tracer())


def _view(traces):
    """The window's spans as benchmark/harness/instrument.py lists them."""
    return {"spans": [
        {"trace": tr.trace_id, "root": tr.name, "id": s.span_id, "parent": s.parent_id,
         "name": s.name, "t0": s.t0, "dur_s": s.dur_s, "self_s": s.dur_s,
         "attrs": dict(s.attrs or {})}
        for tr in traces for s in [tr.root] + list(tr.spans)
    ]}


def _encodes(traces):
    return [s for tr in traces if tr.name == "http" for s in tr.spans if s.name == "encode"]


def test_the_encode_span_of_an_arrow_answer_says_which_route(traced, bench):
    n = 200
    rng = np.random.default_rng(2)
    labels = _objects([None if i % 3 else "admin" for i in range(n)])
    sft = FeatureType.from_spec("t", "name:String,score:Double,dtg:Date,*geom:Point:srid=4326")
    loose = FeatureType.from_spec("u", "name:String,label:String,*geom:Point:srid=4326")
    geom = (rng.uniform(-50, 50, n), rng.uniform(-40, 40, n))
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    ds.create_schema(loose)
    names = np.array([f"n{i % 17} é" for i in range(n)])
    ds.write("t", FeatureCollection.from_columns(sft, [f"f{i}" for i in range(n)], {
        "name": names, "score": rng.normal(size=n),
        "dtg": 1_704_067_200_000 + rng.integers(0, 10 ** 9, n), "geom": geom}))
    ds.write("u", FeatureCollection.from_columns(loose, [f"g{i}" for i in range(n)], {
        "name": names, "label": labels, "geom": geom}))
    srv = ds.serve(port=0)
    try:
        client = DataClient(srv.url)
        box = "BBOX(geom, -60, -45, 60, 45)"
        for type_name in ("t", "u"):
            direct = ds.query(type_name, box)
            assert len(direct) == n
            for page in (64, 4096):
                raw = client.query(type_name, cql=box, fmt="arrow", page_rows=page)
                assert raw == arrow_stream(direct, batch_rows=page)
        client.query("t", cql=box)  # GeoJSON
        empty = client.query("t", cql="BBOX(geom, 170, 80, 171, 81)", fmt="arrow")
        assert pa.ipc.open_stream(empty).read_all().num_rows == 0
    finally:
        ds.close()
    spans = _encodes(traced())
    assert [s.attrs.get("arrow_native") for s in spans] == [1, 1, 0, 0, None, 1]
    assert [s.attrs.get("py_writes") for s in spans] == [0, 0, 0, 0, None, 0]
    assert [s.attrs.get("native") for s in spans] == [None, None, None, None, 1, None]
    assert [s.attrs["chunks"] for s in spans] == [5, 1, 5, 1, 1, 1]
    assert all({"bytes", "chunks", "write_s"} <= set(s.attrs) for s in spans)
    view = _view(traced())
    assert bench.arrow_native_pct.read(view) == 60.0
    assert bench.encode_native_pct.read(view) == 100.0  # the GeoJSON answers alone
    arrow_ms = sorted(s.dur_s * 1e3 for s in spans if "arrow_native" in s.attrs)
    assert bench.encode_arrow_ms.read(view) == pytest.approx(arrow_ms[2])
    for s in view["spans"]:  # the parent's spans
        s["attrs"].pop("arrow_native", None)
        s["attrs"].pop("py_writes", None)
    assert bench.arrow_native_pct.read(view) is None
    assert bench.encode_arrow_ms.read(view) == pytest.approx(arrow_ms[2])
    assert bench.arrow_native_pct.read({"spans": []}) is None
    assert bench.encode_arrow_ms.read({"spans": []}) is None


def test_an_untraced_arrow_request_builds_no_span():
    obs.install(obs.Tracer())
    n = 50
    sft = FeatureType.from_spec("t", "name:String,*geom:Point:srid=4326")
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    ds.write("t", FeatureCollection.from_columns(sft, [f"f{i}" for i in range(n)], {
        "name": np.array([f"n{i}" for i in range(n)]),
        "geom": (np.linspace(-5, 5, n), np.linspace(-4, 4, n))}))
    srv = ds.serve(port=0)
    try:
        raw = DataClient(srv.url).query("t", cql="INCLUDE", fmt="arrow")
        assert raw == arrow_stream(ds.query("t", "INCLUDE"), batch_rows=srv.page_rows)
    finally:
        ds.close()
    assert [tr for tr in obs.tracer().traces() if tr.name == "http"] == []
