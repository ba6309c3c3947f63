"""The GeoJSON serializer's two routes write the same bytes
(io/exporters.py ``GeoJSONChunks``): a page by one call of
``native.geojson_features`` where every column can be read without the
interpreter, ``json.dumps`` over a dict a feature where one cannot. The
oracle is the per-feature route as it stood before the native one:
``json.dumps`` of the FeatureCollection dict over ``geojson_features``.

- every attribute of the benchmark's ``gdelt`` and ``osm-gpx`` schemas,
  alone and together, on the benchmark's own rows;
- doubles: 10^5 random bit patterns and the edges of ``float.__repr__``'s
  layout; Float widened to double; ints at their limits; Boolean;
- strings: quotes, backslashes, control characters, DEL, non-ASCII,
  non-BMP, a lone surrogate, empty and full-width ``<U`` cells, NULs;
- dates at the epoch, before it, with and without milliseconds, at the
  ends of the years numpy writes with four digits;
- int64 and ``<U`` ids; 0, 1, 7 and a page + 1 rows, any page size;
- each fallback (an object column with ``None``, a packed geometry, NaN,
  an infinity, a year past 9999, a byte-swapped column, no native
  library) writes the old bytes and says ``native`` False;
- served: the ``encode`` span of a retained trace carries ``native``.
"""

import json
import os
import sys
import types

import numpy as np
import pytest

from geomesa_tpu import conf, geometry as geo, native, obs
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter.predicates import PointColumn
from geomesa_tpu.io import exporters
from geomesa_tpu.io.exporters import GeoJSONChunks, _geojson, geojson_crs, geojson_features
from geomesa_tpu.serving import DataClient
from geomesa_tpu.sft import FeatureType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
BENCH_PACKAGES = ("harness", "datagen", "layer_metrics")
SEED = 2_500_000_011
N = 96


def _configs():
    out = {}
    for name in ("gdelt-events-1chip", "osm-gpx-1chip"):
        with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
            out[name] = json.load(fh)
    return out


CONFIGS = _configs()
ATTRIBUTES = [
    (cfg, a.name)
    for cfg, c in CONFIGS.items()
    for a in FeatureType.from_spec(c["type_name"], c["schema"]).attributes
]


def _reference(fc) -> bytes:
    """The document as the per-feature route alone wrote it."""
    out = {"type": "FeatureCollection", "features": list(geojson_features(fc))}
    crs = geojson_crs(fc)
    if crs is not None:
        out["crs"] = crs
    return json.dumps(out).encode()


def _same(fc, native_route=True, page_rows=None):
    """Both routes' bytes agree, one-shot and paged; which route served."""
    want = _reference(fc)
    assert _geojson(fc).encode() == want
    chunks = GeoJSONChunks(fc) if page_rows is None else GeoJSONChunks(fc, page_rows)
    assert chunks.native is None  # lazy: nothing is encoded before the first pull
    assert b"".join(chunks) == want
    assert chunks.native is native_route
    return want


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generators and the new reader, imported as the
    benchmark imports them."""
    held = {k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES}
    sys.path.insert(0, BENCH)
    try:
        from datagen import gdelt, osm_gpx
        from layer_metrics import encode_native_pct

        yield types.SimpleNamespace(
            make={"gdelt-events-1chip": gdelt.make, "osm-gpx-1chip": osm_gpx.make},
            reader=encode_native_pct)
    finally:
        sys.path.remove(BENCH)
        for k in [k for k in sys.modules if k.split(".")[0] in BENCH_PACKAGES and k not in held]:
            del sys.modules[k]


@pytest.fixture(scope="module")
def collections(bench):
    """The two schemas' rows as ``stores/datastore.py`` loads them."""
    out = {}
    for name, cfg in CONFIGS.items():
        cols = bench.make[name](cfg, N, SEED)
        sft = FeatureType.from_spec(cfg["type_name"], cfg["schema"])
        columns = dict(cols.attrs, **{cols.dtg: cols.t, cols.geom: (cols.x.copy(), cols.y.copy())})
        out[name] = FeatureCollection.from_columns(sft, np.arange(N, dtype=np.int64), columns)
    return out


def _points(n, seed=3):
    rng = np.random.default_rng(seed)
    return PointColumn(rng.uniform(-180, 180, n), rng.uniform(-90, 90, n))


def _one(spec_type, values, name="v", ids=None, geom=True):
    """A collection of one attribute (and a point) over ``values``."""
    values = np.asarray(values)
    n = len(values)
    spec = f"{name}:{spec_type}" + (",*geom:Point:srid=4326" if geom else "")
    sft = FeatureType.from_spec("t", spec)
    cols = {name: values}
    if geom:
        cols["geom"] = _points(n)
    return FeatureCollection(sft, np.arange(n, dtype=np.int64) if ids is None else ids, cols)


# -- the schemas of the benchmark ------------------------------------------

@pytest.mark.parametrize("config,attribute", ATTRIBUTES, ids=[f"{c}-{a}" for c, a in ATTRIBUTES])
def test_every_attribute_of_the_benchmarks_schemas(collections, config, attribute):
    fc = collections[config]
    sub = fc.project([attribute, fc.sft.geom_field])
    text = _same(sub)
    feats = json.loads(text)["features"]
    assert len(feats) == N and feats[0]["id"] == "0"
    if attribute != fc.sft.geom_field:
        assert list(feats[0]["properties"]) == [attribute]


@pytest.mark.parametrize("config", list(CONFIGS))
@pytest.mark.parametrize("rows", [0, 1, 7, 33])
def test_whole_schemas_at_sizes_round_a_page(collections, config, rows):
    fc = collections[config].take(np.arange(rows))
    for page_rows in (1, 7, 32, 4096):
        _same(fc, page_rows=page_rows)


def test_a_page_and_one_row_more_at_the_default_page(collections):
    fc = collections["gdelt-events-1chip"]
    big = fc.take(np.arange(exporters.PAGE_ROWS + 1) % N)
    chunks = list(GeoJSONChunks(big))
    assert len(chunks) == 2 and chunks[1].startswith(b', {"type": "Feature"')
    assert b"".join(chunks) == _reference(big)
    assert exporters.PAGE_ROWS == conf.SERVE_PAGE_ROWS.get()


def test_one_call_a_page_and_none_for_an_empty_answer(collections, monkeypatch):
    calls = []
    real = native.geojson_features
    monkeypatch.setattr(native, "geojson_features",
                        lambda t, lo, hi: calls.append((lo, hi)) or real(t, lo, hi))
    fc = collections["gdelt-events-1chip"]
    assert len(list(GeoJSONChunks(fc, 40))) == 3 and calls == [(0, 40), (40, 80), (80, 120)]
    del calls[:]
    empty = GeoJSONChunks(fc.take(np.arange(0)))
    assert list(empty) == [b'{"type": "FeatureCollection", "features": []}']
    assert calls == [] and empty.native is True  # the route is the columns', not the rows'


# -- numbers ------------------------------------------------------------------

EDGES = [
    0.0, -0.0, 1.0, -1.0, 1e-5, 1e-4, 9.999999999999999e-5, 0.00010000000000000002, 1e15, 1e16,
    9999999999999998.0, 1.0000000000000002e16, 1e22, 1e23, 1.7976931348623157e308,
    2.2250738585072014e-308, 5e-324, -5e-324, 2.225073858507201e-308, 123456789012345.6, 0.1, 0.3,
    2.0 ** 53, 2.0 ** 53 + 2, 1 / 3, 100.0, 1e100, 1.5e-7, 123e-20, 4.35, 0.30000000000000004,
]


def test_doubles_as_float_repr_writes_them():
    rng = np.random.default_rng(38)
    v = rng.integers(0, 1 << 64, 100_000, dtype=np.uint64).view(np.float64)
    v = np.concatenate([v[np.isfinite(v)], EDGES, np.round(rng.normal(scale=1e4, size=2000), 3),
                        rng.integers(-10 ** 15, 10 ** 15, 2000).astype(np.float64)])
    text = _same(_one("Double", v, geom=False))
    got = [f["properties"]["v"] for f in json.loads(text)["features"]]
    assert np.array_equal(np.array(got), v) and np.array_equal(np.signbit(got), np.signbit(v))


@pytest.mark.parametrize("value", EDGES, ids=[repr(v) for v in EDGES])
def test_the_edges_of_the_layout(value):
    text = _same(_one("Double", [value]))
    assert (b'"v": ' + repr(value).encode() + b"}}") in text


def test_floats_are_widened_to_double():
    rng = np.random.default_rng(5)
    v = np.concatenate([
        rng.integers(0, 1 << 32, 50_000, dtype=np.uint32).view(np.float32),
        np.array([0.1, -0.0, 1e-5, 16777216.0, 3.4028235e38, 1e-45], dtype=np.float32)])
    v = v[np.isfinite(v)]
    text = _same(_one("Float", v, geom=False))
    assert repr(float(np.float32(0.1))).encode() in text


@pytest.mark.parametrize("spec_type,dtype", [
    ("Integer", np.int32), ("Long", np.int64), ("Long", np.uint64), ("Integer", np.int8),
    ("Integer", np.int16), ("Integer", np.uint8), ("Integer", np.uint16), ("Long", np.uint32),
])
def test_ints_at_their_limits(spec_type, dtype):
    info = np.iinfo(dtype)
    v = np.array([info.min, info.max, 0, 1, info.max // 10, info.min // 10 + 1], dtype=dtype)
    text = _same(_one(spec_type, v))
    assert str(info.min).encode() in text and str(info.max).encode() in text


def test_booleans():
    text = _same(_one("Boolean", np.array([True, False, True])))
    assert text.count(b'"v": true') == 2 and text.count(b'"v": false') == 1


def test_the_points_coordinates_are_doubles_too():
    fc = _one("Integer", np.arange(4, dtype=np.int32))
    fc.columns["geom"] = PointColumn(np.array([0.0, -0.0, 1e-7, 179.99999999999997]),
                                     np.array([90.0, -90.0, 1e16, 5e-324]))
    text = _same(fc)
    assert b'"coordinates": [-0.0, -90.0]' in text and b"[1e-07, 1e+16]" in text


# -- strings ------------------------------------------------------------------

STRINGS = {
    "plain": "Tbilisi",
    "empty": "",
    "quote": 'say "hi"',
    "backslash": "a\\b\\\\c",
    "short-escapes": "\b\f\n\r\t",
    "controls": "".join(map(chr, range(1, 32))),
    "del-and-tilde": "~\x7f\x80",
    "latin": "Zürich São Tomé",
    "bmp": "Владивосток 東京  ￿",
    "non-bmp": "\U0001f600 \U00010000 \U0010ffff",
    "lone-surrogate": "\ud83d x \udc00",
    "inner-nul": "a\x00b",
    "full-width": "x" * 24,
    "full-width-non-bmp": "\U0001f30d" * 24,
    "solidus": "</script> /",
}


@pytest.mark.parametrize("case", list(STRINGS))
def test_strings_as_json_escapes_them(case):
    v = np.array([STRINGS[case], "", "pad"], dtype=f"<U{max(24, len(STRINGS[case]))}")
    text = _same(_one("String", v))
    assert json.loads(text)["features"][0]["properties"]["v"] == STRINGS[case]
    assert text.isascii()


def test_a_cell_is_cut_at_its_trailing_nuls_only():
    v = np.zeros(3, dtype="<U6")
    v[0], v[2] = "ab", "abcdef"
    raw = v.view(np.uint32).reshape(3, 6)
    raw[1] = [ord("x"), 0, ord("y"), 0, 0, 0]
    text = _same(_one("String", v))
    assert [f["properties"]["v"] for f in json.loads(text)["features"]] == ["ab", "x\x00y", "abcdef"]


def test_every_code_point_of_the_low_planes():
    cps = np.concatenate([np.arange(1, 0x3000), np.arange(0xD7F0, 0xE010), np.arange(0xFFF0, 0x10010),
                          np.arange(0x10FFF0, 0x110000)]).astype(np.uint32)
    v = np.zeros(len(cps), dtype="<U2")
    v.view(np.uint32).reshape(-1, 2)[:, 0] = cps
    _same(_one("String", v, geom=False))


def test_attribute_names_are_json_strings_too():
    sft = FeatureType.from_spec("t", "n:Integer,*geom:Point:srid=4326")
    fc = FeatureCollection(sft, np.arange(2, dtype=np.int64), {
        'a "b"': np.array([1, 2], dtype=np.int32), "geom": _points(2), "é": np.array(["x", "y"])})
    text = _same(fc)
    assert list(json.loads(text)["features"][0]["properties"]) == ['a "b"', "é"]


# -- dates, ids, geometry -----------------------------------------------------

DATES = {
    "epoch": 0, "a-millisecond": 1, "before-the-epoch": -1, "a-day-before": -86_400_000,
    "1969-with-millis": -123_456_789, "whole-second": 1_704_067_200_000,
    "with-millis": 1_704_067_200_123, "leap-day": 1_709_164_800_000 + 86_399_999,
    "year-0001": -62_135_596_800_000, "year-0999": -30_610_224_000_001,
    "year-9999": 253_402_300_799_999, "1900-not-leap": -2_203_891_200_000,
}


@pytest.mark.parametrize("case", list(DATES))
def test_dates_as_numpy_writes_them(case):
    v = np.array([DATES[case], 0], dtype=np.int64)
    text = _same(_one("Date", v, name="dtg"))
    assert (str(np.datetime64(DATES[case], "ms")) + "Z").encode() in text


def test_dates_over_every_day_of_four_centuries():
    days = np.arange(-146_097, 146_097 * 3, 37, dtype=np.int64)
    _same(_one("Date", days * 86_400_000 + (days * 7_919) % 86_400_000, name="dtg", geom=False))


@pytest.mark.parametrize("ids", [
    np.array([0, -1, 7, np.iinfo(np.int64).max, np.iinfo(np.int64).min], dtype=np.int64),
    np.array(["a", 'q"uote', "", "\U0001f600", "x" * 12], dtype="<U12"),
    np.array(["f0", "f1", "f2", "f3", "f4"]),
], ids=["int64", "wide-str", "str"])
def test_ids(ids):
    text = _same(_one("Integer", np.arange(5, dtype=np.int32), ids=ids))
    assert [f["id"] for f in json.loads(text)["features"]] == [str(i) for i in ids]


def test_a_type_with_no_geometry_says_null():
    text = _same(_one("Integer", np.arange(3, dtype=np.int32), geom=False))
    assert text.count(b'"geometry": null') == 3


def test_no_properties_and_a_crs_member():
    sft = FeatureType.from_spec("t", "*geom:Point:srid=4326")
    sft.user_data["geomesa.crs"] = "EPSG:3857"
    fc = FeatureCollection(sft, np.arange(2, dtype=np.int64), {"geom": _points(2)})
    text = _same(fc)
    assert b'"properties": {}}' in text and text.endswith(b'EPSG::3857"}}}')


def test_strided_and_read_only_columns():
    v = np.arange(40, dtype=np.int32)[::2]
    s = np.array([f"s{i}" for i in range(40)])[::-2]
    d = np.linspace(0, 1, 20)
    d.setflags(write=False)
    sft = FeatureType.from_spec("t", "a:Integer,b:String,c:Double,*geom:Point:srid=4326")
    x = np.linspace(-10, 10, 60)
    fc = FeatureCollection(sft, np.arange(100, 120, dtype=np.int64),
                           {"a": v, "b": s, "c": d, "geom": PointColumn(x[::3], x[40:])})
    _same(fc)


# -- the fallbacks: the old bytes, native False ------------------------------

def _object_column():
    col = np.empty(4, dtype=object)
    col[:] = ["a", None, "c", "d"]
    return _one("String", col)


def _packed_geometry():
    sft = FeatureType.from_spec("t", "n:Integer,*area:Polygon:srid=4326")
    polys = [geo.Polygon([(0, 0), (1, 0), (1, 1), (0, 0)]) for _ in range(3)]
    return FeatureCollection.from_columns(sft, np.arange(3, dtype=np.int64),
                                          {"n": np.arange(3, dtype=np.int32), "area": polys})


def _nan_point():
    fc = _one("Integer", np.arange(3, dtype=np.int32))
    fc.columns["geom"].x[1] = np.nan
    return fc


def _list_column():
    return _one("String", np.arange(8, dtype=np.int32).reshape(4, 2), geom=False)


FALLBACKS = {
    "object-column-with-none": _object_column,
    "packed-geometry": _packed_geometry,
    "nan": lambda: _one("Double", [1.0, np.nan, 2.0]),
    "infinity": lambda: _one("Double", [1.0, 2.0, -np.inf]),
    "nan-point": _nan_point,
    "year-10000": lambda: _one("Date", np.array([0, 253_402_300_800_000]), name="dtg"),
    "year-0000": lambda: _one("Date", np.array([0, -62_135_596_800_001]), name="dtg"),
    "not-a-time": lambda: _one("Date", np.array([np.iinfo(np.int64).min, 0]), name="dtg"),
    "int32-date": lambda: _one("Date", np.array([0, 1], dtype=np.int32), name="dtg"),
    "byte-swapped": lambda: _one("Long", np.arange(3, dtype=">i8")),
    "half-floats": lambda: _one("Float", np.array([0.5, 1.5], dtype=np.float16)),
    "object-ids": lambda: _one("Integer", np.arange(2, dtype=np.int32),
                               ids=np.array(["a", "b"], dtype=object)),
    "float32-point": lambda: FeatureCollection(
        FeatureType.from_spec("t", "*geom:Point:srid=4326"), np.arange(2, dtype=np.int64),
        {"geom": PointColumn(np.zeros(2, np.float32), np.ones(2, np.float32))}),
}


@pytest.mark.parametrize("case", list(FALLBACKS))
def test_what_the_native_route_leaves_to_the_interpreter(case):
    _same(FALLBACKS[case](), native_route=False)


def test_a_two_dimensional_column_takes_the_old_route_and_its_error():
    # the per-feature route cannot write an array cell either: unchanged
    chunks = GeoJSONChunks(_list_column())
    with pytest.raises((TypeError, ValueError)):
        list(chunks)
    assert chunks.native is False


def test_an_undecided_value_on_a_later_page_keeps_the_earlier_pages():
    v = np.arange(10, dtype=np.float64)
    v[7] = np.nan
    fc = _one("Double", v)
    chunks = GeoJSONChunks(fc, 3)
    pages = list(chunks)
    assert b"".join(pages) == _reference(fc) and chunks.native is False
    assert len(pages) == 4


def test_without_the_native_tier(collections, monkeypatch):
    monkeypatch.setattr(native, "_lib", False)
    assert not native.available()
    for fc in collections.values():
        _same(fc.take(np.arange(9)), native_route=False)
    assert native.GeoJSONColumns.of(np.arange(1, dtype=np.int64), None, []) is None


def test_a_year_outside_numpys_four_digits_is_left_alone():
    for ms in (253_402_300_800_000, -62_135_596_800_001):
        t = native.GeoJSONColumns.of(np.arange(1, dtype=np.int64), None,
                                     [(b'"d": ', np.array([ms]), True)])
        assert native.geojson_features(t, 0, 1) is None
    ok = native.GeoJSONColumns.of(np.arange(1, dtype=np.int64), None,
                                  [(b'"d": ', np.array([253_402_300_799_999]), True)])
    assert native.geojson_features(ok, 0, 1).endswith(b'{"d": "9999-12-31T23:59:59.999Z"}}')


def test_eight_threads_share_no_buffer(collections):
    import threading

    fc = collections["gdelt-events-1chip"]
    want = [_reference(fc.take(np.arange(k, k + 40))) for k in range(8)]
    got, errors = [None] * 8, []

    def work(k):
        try:
            sub = fc.take(np.arange(k, k + 40))
            for _ in range(50):
                got[k] = b"".join(GeoJSONChunks(sub, 16))
                assert got[k] == want[k]
        except Exception as e:  # pragma: no cover - reported below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(k,)) for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == [] and got == want


# -- served: the ``encode`` span says which route ------------------------------

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


def _served(spec, columns, n):
    sft = FeatureType.from_spec("t", spec)
    ds = DataStore(tile=64)
    ds.create_schema(sft)
    ds.write("t", FeatureCollection.from_columns(sft, [f"f{i}" for i in range(n)], columns))
    return ds, ds.serve(port=0)


def _encodes(traces):
    return [s for tr in traces if tr.name == "http" for s in tr.spans if s.name == "encode"]


def test_the_encode_span_says_which_route_served(traced, bench):
    n = 200
    rng = np.random.default_rng(2)
    labels = np.empty(n, dtype=object)
    labels[:] = [None if i % 3 else "admin" for i in range(n)]
    ds, srv = _served(
        "name:String,score:Double,label:String,dtg:Date,*geom:Point:srid=4326",
        {"name": np.array([f"n{i}" for i in range(n)]), "score": rng.normal(size=n), "label": labels,
         "dtg": 1_704_067_200_000 + rng.integers(0, 10 ** 9, n),
         "geom": (rng.uniform(-50, 50, n), rng.uniform(-40, 40, n))}, n)
    try:
        client = DataClient(srv.url)
        box = "BBOX(geom, -60, -45, 60, 45)"
        path = "/query/t?cql=" + box.replace(" ", "%20")
        _, _, whole = client.request("GET", path)  # an object column: the per-feature route
        direct = ds.query("t", box)
        assert len(direct) == n and whole == _reference(direct)
        client.query("t", cql=box, fmt="arrow")
        _, _, none = client.request("GET", "/query/t?cql=BBOX(geom,%20170,%2080,%20171,%2081)")
        assert none == b'{"type": "FeatureCollection", "features": []}'
    finally:
        ds.close()
    spans = _encodes(traced())
    assert [s.attrs.get("native") for s in spans] == [0, None, 0]
    assert all({"bytes", "chunks", "write_s"} <= set(s.attrs) for s in spans)
    assert bench.reader.read(_view(traced())) == 0.0


def test_native_answers_one_chunk_and_the_reader(traced, bench):
    n = 300
    rng = np.random.default_rng(4)
    ds, srv = _served(
        "name:String,score:Double,hits:Integer,ok:Boolean,dtg:Date,*geom:Point:srid=4326",
        {"name": np.array([f"n{i} é" for i in range(n)]), "score": rng.normal(size=n),
         "hits": rng.integers(-9, 9, n).astype(np.int32), "ok": rng.random(n) < 0.5,
         "dtg": 1_704_067_200_000 + rng.integers(0, 10 ** 9, n),
         "geom": (rng.uniform(-50, 50, n), rng.uniform(-40, 40, n))}, n)
    try:
        client = DataClient(srv.url)
        box = "BBOX(geom, -60, -45, 60, 45)"
        path = "/query/t?cql=" + box.replace(" ", "%20")
        want = _reference(ds.query("t", box))
        for suffix in ("", "&page_rows=64", "&limit=5"):
            _, _, raw = client.request("GET", path + suffix)
            assert raw == (want if "limit" not in suffix else _reference(ds.query("t", box).take(np.arange(5))))
        client.query("t", cql=box, fmt="arrow")
    finally:
        ds.close()
    spans = _encodes(traced())
    assert [s.attrs.get("native") for s in spans] == [1, 1, 1, None]
    assert [s.attrs["chunks"] for s in spans[:3]] == [1, 5, 1]
    assert bench.reader.read(_view(traced())) == 100.0
    bare = _view(traced())
    for s in bare["spans"]:
        s["attrs"].pop("native", None)  # the parent's spans
    assert bench.reader.read(bare) is None and bench.reader.read({"spans": []}) is None


def test_an_untraced_request_builds_no_span_and_the_same_bytes():
    obs.install(obs.Tracer())
    n = 50
    ds, srv = _served("name:String,*geom:Point:srid=4326",
                      {"name": np.array([f"n{i}" for i in range(n)]),
                       "geom": (np.linspace(-5, 5, n), np.linspace(-4, 4, n))}, n)
    try:
        _, _, raw = DataClient(srv.url).request("GET", "/query/t?cql=INCLUDE")
        assert raw == _reference(ds.query("t", "INCLUDE"))
    finally:
        ds.close()
    assert [tr for tr in obs.tracer().traces() if tr.name == "http"] == []


def test_the_metric_is_the_served_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entry = next(m for m in bench["per_layer"] if m["name"] == "encode_native_pct")
    assert entry == {
        "name": "encode_native_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "entry points", "moves": "queries_per_s",
        "workloads": ["gdelt.dashboard", "gdelt.ingest-reads"],
    }
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", "encode_native_pct.py"))
