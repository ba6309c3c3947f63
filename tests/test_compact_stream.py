"""Streamed compaction (`IndexTable._stream_cols`; docs/ingest.md
"Memory model"): the 1B-row code path pinned in tier-1 at CI scale.

Two contracts, both with ``geomesa.tpu.compact.span.rows`` forced small
so the bounded gather genuinely runs MANY spans per column:

- **exactness** — a compaction streamed through tiny spans produces a
  table bit-identical (counts, ids, sorted keys) to one built with the
  default span;
- **bounded memory** — compaction peak RSS stays under the DECLARED
  column-set multiple: ~one transient
  column family, never a doubled column set. Run in a fresh SUBPROCESS
  with a phase-scoped sampler, so other tests' allocator history can't
  pollute the measurement.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from geomesa_tpu import conf
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DAY = 86_400_000
T0 = 1_704_067_200_000

# the declared bound: store-attributable compaction peak over the full
# column set. The classic (pre-stream) build materialized a second
# sorted copy of every column at once (>= 2x + the device set); the
# streamed build holds ~one span + one column + the new device columns.
PEAK_OVER_COLUMN_SET_MAX = 2.0


def _store(n, seed=3, span_blocks=None):
    sft = FeatureType.from_spec("cmp", "val:Double,dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.indices.enabled"] = "z3"
    ds = DataStore()
    ds.create_schema(sft)
    rng = np.random.default_rng(seed)
    ds.write("cmp", FeatureCollection.from_columns(
        sft, np.arange(n, dtype=np.int64),
        {"val": rng.uniform(0, 1, n),
         "dtg": T0 + rng.integers(0, 40 * DAY, n),
         "geom": (rng.uniform(-70, 70, n), rng.uniform(-50, 50, n))},
    ), check_ids=False)
    return ds


def _fingerprint(ds):
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    for (tn, name), t in sorted(ds._tables.items()):
        h.update(f"{tn}/{name}/{t.n}/{t.n_blocks}".encode())
        h.update(np.ascontiguousarray(np.asarray(t.perm)).tobytes())
        h.update(np.ascontiguousarray(np.asarray(t.zs)).tobytes())
    return h.hexdigest()


class TestStreamedExactness:
    def test_tiny_spans_build_the_identical_table(self):
        """Force the span to ONE BLOCK of rows (the maximal span count)
        and compare against the default multi-million-row span: sorted
        keys, block layout and every query answer must be identical."""
        n = 120_000
        ref = _store(n)
        ref.compact("cmp")
        conf.COMPACT_SPAN_ROWS.set(1)  # clamps up to one block per span
        try:
            tiny = _store(n)
            tiny.compact("cmp")
        finally:
            conf.COMPACT_SPAN_ROWS.clear()
        assert _fingerprint(tiny) == _fingerprint(ref)
        queries = [
            "bbox(geom, -10, -10, 10, 10)",
            "bbox(geom, 5, 5, 40, 30) AND "
            "dtg DURING 2024-01-03T00:00:00Z/2024-01-19T00:00:00Z",
            "INCLUDE",
        ]
        for q in queries:
            a, b = tiny.query("cmp", q), ref.query("cmp", q)
            assert sorted(np.asarray(a.ids).tolist()) == \
                sorted(np.asarray(b.ids).tolist())
            assert tiny.count("cmp", q) == ref.count("cmp", q) == len(b)


_RSS_SCRIPT = r"""
import gc, json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{root!r}, os.path.join({root!r}, "tests")]
import numpy as np
from rss_probe import _RssSampler, _ingest_column_set_bytes, _malloc_trim, _rss_bytes
from geomesa_tpu import conf
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.sft import FeatureType

n = {n}
gc.collect(); _malloc_trim()
rss_baseline = _rss_bytes()  # bare process: interpreter + jax + XLA

sft = FeatureType.from_spec("cmp", "val:Double,dtg:Date,*geom:Point:srid=4326")
sft.user_data["geomesa.indices.enabled"] = "z3"
ds = DataStore()
ds.create_schema(sft)
rng = np.random.default_rng(7)
ds.write("cmp", FeatureCollection.from_columns(
    sft, np.arange(n, dtype=np.int64),
    {{"val": rng.uniform(0, 1, n),
      "dtg": 1_704_067_200_000 + rng.integers(0, 40 * 86_400_000, n),
      "geom": (rng.uniform(-70, 70, n), rng.uniform(-50, 50, n))}},
), check_ids=False)
probe_before = ds.count("cmp", "bbox(geom, -10, -10, 0, 0)")

# the CI-scale bounded-memory setting: many spans per column
conf.COMPACT_SPAN_ROWS.set({span_rows})
gc.collect(); _malloc_trim()
column_set = _ingest_column_set_bytes(ds, "cmp")
with _RssSampler() as rss:
    ds.compact("cmp")
peak_over_cs = (rss.peak - rss_baseline) / max(column_set, 1)
probe_after = ds.count("cmp", "bbox(geom, -10, -10, 0, 0)")
table = next(t for (tn, _), t in ds._tables.items() if tn == "cmp")
print(json.dumps({{
    "n": n,
    "span_rows": {span_rows},
    "spans_per_column": -(-table.n // max(table.block, {span_rows})),
    "block": table.block,
    "column_set_bytes": column_set,
    "rss_baseline_bytes": rss_baseline,
    "rss_peak_bytes": rss.peak,
    "peak_over_column_set": round(peak_over_cs, 3),
    "probe_before": int(probe_before),
    "probe_after": int(probe_after),
    "total": int(ds.count("cmp")),
}}))
"""


class TestBoundedRss:
    def test_compaction_peak_under_declared_column_set_multiple(self):
        """The 1B run's memory contract at CI scale: with the span
        forced to 64Ki rows (dozens of spans per column) the compaction
        peak stays under PEAK_OVER_COLUMN_SET_MAX x the column set —
        measured in a fresh subprocess whose RSS history is exactly
        (interpreter + jax + this store)."""
        n = 1_500_000
        out = subprocess.run(
            [sys.executable, "-c",
             _RSS_SCRIPT.format(root=ROOT, n=n, span_rows=65_536)],
            capture_output=True, text=True, timeout=420,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": ROOT, "XLA_FLAGS": ""},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        row = json.loads(out.stdout.splitlines()[-1])
        assert row["total"] == n
        assert row["probe_after"] == row["probe_before"] > 0  # exactness
        assert row["spans_per_column"] >= 10  # the bounded path REALLY ran
        assert row["peak_over_column_set"] < PEAK_OVER_COLUMN_SET_MAX, row
