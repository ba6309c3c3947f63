"""The plan path keeps a filter's covering ranges in arrays from the
native tier to ``ScanConfig`` (curve/zranges.py ``zranges_arrays``, the
SFCs' ``ranges_arrays``): the list of ``IndexRange`` is a view of them.

- the arrays entry against the list view, element for element and dtype
  for dtype, native tier and plain-Python reference;
- ``Z3Index`` / ``Z2Index.scan_config`` against a reference built here
  from the LIST api the way the indexes built their configs before the
  arrays went straight through (the ranges cover the f32 mask's boxes,
  containment is the f64 boxes'): every array field equal, dtype included;
- planning a bbox + DURING filter constructs no ``IndexRange``.
"""

import importlib.util
import os

import numpy as np
import pytest

from geomesa_tpu import native
from geomesa_tpu.curve import Z2SFC, Z3SFC
from geomesa_tpu.curve import zranges as zr
from geomesa_tpu.curve.normalize import NormalizedLat, NormalizedLon, NormalizedTime
from geomesa_tpu.curve.zorder import Z2, Z3
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.filter import ecql
from geomesa_tpu.filter.extract import (
    extract_geometries, extract_intervals, geometry_bounds,
)
from geomesa_tpu.index.api import shrink_boxes, widen_boxes
from geomesa_tpu.index.z2 import Z2Index
from geomesa_tpu.index.z3 import (
    _OFFSET_UNIT_MS, WHOLE_WORLD, Z3Index, _bounds_only, clamp_bins,
)
from geomesa_tpu.sft import FeatureType

T0 = int(np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64))
DAY = 86_400_000
SPAN_MS = 16 * DAY
BOX = (10.0, 10.0, 15.0, 12.5)
WINDOW = (1000.0, 300_000.0)


def _bench_data():
    """benchmark/harness/data.py (imports NumPy alone), by path: the
    benchmark's directories are not packages of the program."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmark", "harness", "data.py")
    spec = importlib.util.spec_from_file_location("_bench_harness_data", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _iso(ms):
    return f"{np.datetime64(int(ms), 'ms')}Z"


def _bbox_during(box, win):
    return ("bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*(float(v) for v in box))
            + f" AND dtg DURING {_iso(win[0])}/{_iso(win[1])}")


# -- (a) the arrays entry against the list view ---------------------------

def _arrays_and_list(curve, budget, inner, empty):
    bounds = [] if empty else [BOX]
    if curve == "z2":
        sfc = Z2SFC()
        return (sfc.ranges_arrays(bounds, max_ranges=budget, inner=inner),
                sfc.ranges(bounds, max_ranges=budget, inner=inner))
    sfc = Z3SFC()
    return (sfc.ranges_arrays(bounds, [WINDOW], max_ranges=budget, inner=inner),
            sfc.ranges(bounds, [WINDOW], max_ranges=budget, inner=inner))


@pytest.mark.parametrize("tier", ["native", "python"])
@pytest.mark.parametrize("case", ["roomy", "exhausted", "empty"])
@pytest.mark.parametrize("inner", [False, True])
@pytest.mark.parametrize("curve", ["z2", "z3"])
def test_arrays_entry_equals_list_view(curve, inner, case, tier, monkeypatch):
    if tier == "native" and not native.available():
        pytest.skip("native tier not built")
    if tier == "python":
        monkeypatch.setattr(native, "zranges", lambda *a, **k: None)
    budget = 6 if case == "exhausted" else 120
    (lo, hi, cont), ranges = _arrays_and_list(curve, budget, inner, case == "empty")
    assert (lo.dtype, hi.dtype, cont.dtype) == (np.uint64, np.uint64, np.bool_)
    assert lo.ndim == hi.ndim == cont.ndim == 1
    assert len(lo) == len(hi) == len(cont) == len(ranges)
    if case == "empty":
        assert ranges == []
    else:
        assert 0 < len(ranges) <= budget
        if case == "exhausted":  # the budget did bind: a coarser cover
            roomy = _arrays_and_list(curve, 120, inner, False)[1]
            assert len(ranges) < len(roomy) and not cont.all()
    for k, r in enumerate(ranges):
        assert type(r) is zr.IndexRange
        assert (type(r.lower), type(r.upper), type(r.contained)) == (int, int, bool)
        assert (r.lower, r.upper, r.contained) == (int(lo[k]), int(hi[k]), bool(cont[k]))
    # and back: the arrays a caller would build from the objects
    blo, bhi, bcont = zr.ranges_to_arrays(ranges)
    for got, want in ((blo, lo), (bhi, hi), (bcont, cont)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("inner", [False, True])
def test_by_window_is_one_decomposition_a_window(inner):
    """``ranges_arrays_by_window`` (one native call) equals one
    ``ranges_arrays`` call a window, in the windows' order."""
    sfc = Z3SFC()
    bounds = [BOX, (-60.0, -20.0, -58.5, -19.0)]
    times = [(0.0, 604_799.0), WINDOW, (590_000.0, 604_799.0)]
    lo, hi, cont, counts = sfc.ranges_arrays_by_window(bounds, times, inner=inner)
    assert counts.dtype == np.int64 and counts.sum() == len(lo) == len(hi) == len(cont)
    end = 0
    for w, n in zip(times, counts.tolist()):
        want = sfc.ranges_arrays(bounds, [w], inner=inner)
        start, end = end, end + n
        for got, ref in zip((lo[start:end], hi[start:end], cont[start:end]), want):
            assert got.dtype == ref.dtype and np.array_equal(got, ref)


def test_native_and_python_tiers_agree_on_arrays(monkeypatch):
    """Uncapped, the two tiers give the same arrays (capped, they may
    close different equal gaps: tests/test_native.py)."""
    if not native.available():
        pytest.skip("native tier not built")
    mins = np.array([[3, 5, 2], [40, 41, 0]], dtype=np.uint64)
    maxes = np.array([[9, 12, 7], [44, 47, 3]], dtype=np.uint64)
    nat = zr.zranges_arrays(Z3, mins, maxes, max_ranges=100_000, max_recurse=32)
    monkeypatch.setattr(native, "zranges", lambda *a, **k: None)
    ref = zr.zranges_arrays(Z3, mins, maxes, max_ranges=100_000, max_recurse=32)
    assert len(nat[0]) > 1
    for got, want in zip(nat, ref):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_inverted_box_raises_from_the_arrays_core():
    mins = np.array([[5, 9]], dtype=np.uint64)
    maxes = np.array([[7, 8]], dtype=np.uint64)
    with pytest.raises(ValueError, match="inverted box on dim 1"):
        zr.zranges_arrays(Z2, mins, maxes)


@pytest.mark.parametrize("dim", [
    NormalizedLon(31), NormalizedLat(31), NormalizedLon(21), NormalizedLat(21),
    NormalizedTime(21, 604_800.0), NormalizedTime(21, 86_400_000.0),
], ids=["lon31", "lat31", "lon21", "lat21", "week", "day"])
def test_normalize_one_is_normalize(dim):
    """The scalar form the SFCs normalize query corners with: the array
    form's ordinal for every float the array form defines."""
    rng = np.random.default_rng(7)
    cell = (dim.max - dim.min) / dim.bins
    vs = np.concatenate([
        rng.uniform(dim.min - 5, dim.max + 5, 20_000),
        dim.min + rng.integers(0, dim.bins, 20_000) * cell,  # on cell edges
        [dim.min, dim.max, np.nextafter(dim.max, -np.inf), np.nextafter(dim.min, np.inf),
         np.nextafter(dim.min, -np.inf), -0.0, 0.0, -1e9, 1e9],
    ])
    want = dim.normalize(vs)
    got = [dim.normalize_one(float(v)) for v in vs]
    assert all(type(g) is int for g in got)
    assert np.array_equal(np.array(got, dtype=np.int64), want)
    assert dim.normalize_one(float("nan")) == 0


# -- (b) scan_config against the list api ---------------------------------

def _sft():
    sft = FeatureType.from_spec("pts", "name:String,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.z3.interval"] = "week"
    return sft


def _mask_ranges(sfc, curve, bounds, window=None):
    """The list api over the boxes the device mask keeps (``widen_boxes``:
    an aggregation counts whatever the mask keeps, so the ranges have to
    reach it), containment two cells inside the f64 boxes as ``inner=True``
    has it."""
    def corners(boxes):
        lo, hi = [], []
        for xmin, ymin, xmax, ymax in boxes:
            a = [sfc.lon.normalize_one(xmin), sfc.lat.normalize_one(ymin)]
            z = [sfc.lon.normalize_one(xmax), sfc.lat.normalize_one(ymax)]
            if window is not None:
                a.append(sfc.time.normalize_one(window[0]))
                z.append(sfc.time.normalize_one(window[1]))
            lo.append(a)
            hi.append(z)
        return lo, hi

    lo, hi = corners(widen_boxes(bounds).astype(np.float64).tolist())
    ilo, ihi = corners(bounds)
    return zr.zranges(
        curve, [zr.ZBox(tuple(a), tuple(z)) for a, z in zip(lo, hi)],
        inner_boxes=[zr.ZBox(tuple(v + 2 for v in a), tuple(max(v, 2) - 2 for v in z))
                     for a, z in zip(ilo, ihi)])


def _z3_reference(idx: Z3Index, f):
    """``Z3Index.scan_config``'s array fields, from ``Z3SFC.ranges`` (the
    list of objects) with one call a distinct offset window, as the index
    built them before the arrays went straight through."""
    geoms = extract_geometries(f, idx.geom)
    intervals = extract_intervals(f, idx.dtg)
    bounds = geometry_bounds(geoms) if geoms.values else [WHOLE_WORLD]
    unit = _OFFSET_UNIT_MS[idx.period]
    cols = [[], [], [], [], []]
    for iv in intervals.values:
        b, lo, hi = idx.binner.bins_for_interval(iv.lo, iv.hi - 1)
        ilo, ihi = lo.copy(), hi.copy()
        if int(iv.lo) % unit != 0:
            ilo[0] += 1
        if int(iv.hi) % unit != 0:
            ihi[-1] -= 1
        b, rest = clamp_bins(idx.bin_range, b, lo, hi, ilo, ihi)
        for col, v in zip(cols, (b, *rest)):
            col.append(v)
    bins, los, his, ilos, ihis = (np.concatenate(c) for c in cols)
    range_bins, range_lo, range_hi, range_cont = [], [], [], []
    for lo_off, hi_off in set(zip(los.tolist(), his.tolist())):
        ranges = _mask_ranges(idx.sfc, Z3, bounds, (float(lo_off), float(hi_off)))
        if not ranges:
            continue
        rlo = np.array([r.lower for r in ranges], dtype=np.uint64)
        rhi = np.array([r.upper for r in ranges], dtype=np.uint64)
        rc = np.array([r.contained for r in ranges], dtype=bool)
        for k in np.flatnonzero((los == lo_off) & (his == hi_off)):
            range_bins.append(np.full(len(rlo), bins[k], dtype=np.int32))
            range_lo.append(rlo)
            range_hi.append(rhi)
            range_cont.append(rc)
    return {
        "range_bins": np.concatenate(range_bins),
        "range_lo": np.concatenate(range_lo),
        "range_hi": np.concatenate(range_hi),
        "range_contained": np.concatenate(range_cont),
        "boxes": widen_boxes(bounds),
        "boxes_inner": shrink_boxes(bounds),
        "windows": np.stack([bins, los, his], axis=1).astype(np.int64).astype(np.int32),
        "windows_inner": np.stack([bins, ilos, ihis], axis=1).astype(np.int64).astype(np.int32),
    }


def _z2_reference(idx: Z2Index, f):
    geoms = extract_geometries(f, idx.geom)
    bounds = geometry_bounds(geoms)
    ranges = _mask_ranges(idx.sfc, Z2, bounds)
    return {
        "range_bins": np.zeros(len(ranges), dtype=np.int32),
        "range_lo": np.array([r.lower for r in ranges], dtype=np.uint64),
        "range_hi": np.array([r.upper for r in ranges], dtype=np.uint64),
        "range_contained": np.array([r.contained for r in ranges], dtype=bool),
        "boxes": widen_boxes(bounds),
        "boxes_inner": shrink_boxes(bounds),
    }


def _assert_fields(cfg, want):
    for name, ref in want.items():
        got = getattr(cfg, name)
        assert isinstance(got, np.ndarray), name
        assert got.dtype == ref.dtype and got.shape == ref.shape, (name, got.dtype, ref.dtype)
        assert np.array_equal(got, ref), name
        assert got.flags.c_contiguous, name


def _cell_filters(shape, seed):
    data = _bench_data()
    rng = data.sub_rng(seed, 11)
    if shape == "analyst":  # analyst-notebook.json: 1-40 degrees, 6 h to 2 weeks
        boxes = data.box_queries(rng, 30)
        wins = data.time_windows(rng, 30, T0, SPAN_MS)
    else:  # map-viewports.json: 0.25-2 degrees, 6-168 h
        boxes = data.box_queries(rng, 30, widths=(0.25, 0.5, 1.0, 2.0))
        wins = data.time_windows(rng, 30, T0, SPAN_MS, hours=(6, 24, 72, 168))
    return boxes, wins


@pytest.mark.parametrize("seed", [2_600_000_011, 3_100_000_007])
@pytest.mark.parametrize("shape", ["analyst", "dashboard"])
def test_z3_scan_config_equals_list_api_reference(shape, seed):
    idx = Z3Index(_sft())
    idx.bin_range = (2817, 2820)  # the weeks the benchmark's 16 days span
    boxes, wins = _cell_filters(shape, seed)
    bins_met = set()
    for box, win in zip(boxes, wins):
        f = ecql.parse(_bbox_during(box, win))
        cfg = idx.scan_config(f)
        want = _z3_reference(idx, f)
        _assert_fields(cfg, want)
        assert cfg.contained_exact and not cfg.disjoint
        bins_met.add(len(np.unique(cfg.windows[:, 0])))
    # windows inside one week bin and windows that cross into the next
    assert 1 in bins_met and max(bins_met) >= 2


@pytest.mark.parametrize("n_intervals", [2, 4, 7])
def test_z3_scan_config_of_several_intervals_equals_list_api_reference(n_intervals):
    """Intervals OR'ed under one box: up to three distinct offset windows
    an interval, emitted in the order of the reference's set of them."""
    idx = Z3Index(_sft())
    idx.bin_range = (2817, 2823)
    rng = np.random.default_rng(n_intervals)
    wins = []
    for k in range(n_intervals):  # disjoint, each across a week's boundary or two
        start = T0 + k * 6 * DAY + int(rng.integers(0, DAY)) // 1000 * 1000
        wins.append((start, start + int(rng.integers(4 * DAY, 5 * DAY)) // 1000 * 1000))
    during = " OR ".join(f"dtg DURING {_iso(a)}/{_iso(z)}" for a, z in wins)
    f = ecql.parse("bbox(geom, 10.0, 10.0, 30.0, 20.0) AND (" + during + ")")
    cfg = idx.scan_config(f)
    _assert_fields(cfg, _z3_reference(idx, f))
    assert len({(lo, hi) for _, lo, hi in cfg.windows.tolist()}) >= min(2 * n_intervals, 5)


@pytest.mark.parametrize("seed", [2_600_000_011, 3_100_000_007])
def test_z2_scan_config_equals_list_api_reference(seed):
    idx = Z2Index(_sft())
    boxes, _ = _cell_filters("analyst", seed)
    for box in boxes:
        f = ecql.parse("bbox(geom, {!r}, {!r}, {!r}, {!r})".format(*box))
        _assert_fields(idx.scan_config(f), _z2_reference(idx, f))


def test_polygon_scan_configs_equal_list_api_reference():
    """A polygon under the raster tier's edge count: both indexes
    decompose its bbox (z3 under the window). Above it z2 takes the
    raster's ranges, which were arrays from the start."""
    ring = "10 10, 14 9, 17 12, 15 16, 11 15, 10 10"
    poly = f"INTERSECTS(geom, POLYGON(({ring})))"
    z3, z2 = Z3Index(_sft()), Z2Index(_sft())
    f3 = ecql.parse(f"{poly} AND dtg DURING {_iso(T0 + 5 * DAY)}/{_iso(T0 + 9 * DAY)}")
    cfg3 = z3.scan_config(f3)
    _assert_fields(cfg3, _z3_reference(z3, f3))
    assert not _bounds_only(extract_geometries(f3, "geom").values)
    assert cfg3.poly is not None and not cfg3.contained_exact
    f2 = ecql.parse(poly)
    cfg2 = z2.scan_config(f2)
    assert cfg2.rast is None and cfg2.poly is not None
    _assert_fields(cfg2, _z2_reference(z2, f2))
    gon = ", ".join(f"{12 + 4 * np.cos(a):.4f} {12 + 3 * np.sin(a):.4f}"
                    for a in np.linspace(0, 2 * np.pi, 13))
    rast = z2.scan_config(ecql.parse(f"INTERSECTS(geom, POLYGON(({gon})))"))
    assert rast.rast is not None and len(rast.range_lo) > 0
    assert (rast.range_lo.dtype, rast.range_contained.dtype) == (np.uint64, np.bool_)


# -- (c) the plan path builds no IndexRange -------------------------------

def test_plan_constructs_no_index_range(monkeypatch):
    if not native.available():
        pytest.skip("native tier not built")
    sft = _sft()
    ds = DataStore()
    ds.create_schema(sft)
    rng = np.random.default_rng(3)
    n = 4096
    fc = FeatureCollection.from_columns(sft, np.arange(n, dtype=np.int64), {
        "name": np.array(["a"] * n),
        "dtg": T0 + rng.integers(0, SPAN_MS, n),
        "geom": (rng.uniform(-180, 180, n), rng.uniform(-90, 90, n)),
    })
    ds.write("pts", fc)

    def boom(*a, **k):
        raise AssertionError("the plan path built an IndexRange")

    monkeypatch.setattr(zr, "IndexRange", boom)
    plan = ds.planner.plan(
        "pts", _bbox_during((10.0, 10.0, 30.0, 20.0), (T0 + 3 * DAY, T0 + 10 * DAY))
    )
    assert plan.index in ("z3", "z2") and len(plan.config.range_lo) > 0
    assert plan.config.range_lo.dtype == np.uint64
    with pytest.raises(AssertionError, match="built an IndexRange"):
        Z3SFC().ranges([BOX], [WINDOW])  # the patch does bite the list view
