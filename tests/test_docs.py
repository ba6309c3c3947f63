"""Docs stay honest: every API, knob, metric and rule id they name is real.

The guides promise a reference user that each named call is real; this
pins the exact surface so a rename breaks the build, not the reader.

Knob and metric NAME checks run against the static-analysis registries
(geomesa_tpu.analysis.registries) — the same single source of truth
scripts/check.py enforces — instead of parallel hand-kept lists: the
analyzer guarantees every doc-cited name resolves (doc-unknown-name)
and every knob is documented (knob-undocumented); these tests add the
per-subsystem completeness direction (each doc cites every knob/metric
of its area) and that the AST registry agrees with the runtime
conf.REGISTRY."""

import functools
import inspect
import os
import re

import pytest

_ROOT = os.path.join(os.path.dirname(__file__), "..")


@functools.lru_cache(maxsize=1)
def _registries():
    from geomesa_tpu.analysis.core import Project
    from geomesa_tpu.analysis.registries import Registries

    return Registries.of(Project.load(_ROOT))


def _area_names(prefix: str) -> tuple[list[str], list[str]]:
    """(knob names, metric names) of one geomesa.<area>. prefix, from
    the analyzer registries."""
    regs = _registries()
    knobs = sorted(k for k in regs.knobs.knobs if k.startswith(prefix))
    metrics = sorted(
        n for n in regs.metrics.names() if n.startswith(prefix)
    )
    return knobs, metrics


def _assert_documented(doc: str, names) -> None:
    text = open(os.path.join(_ROOT, "docs", doc)).read()
    missing = [n for n in names if n not in text]
    assert not missing, f"docs/{doc} does not cite: {missing}"


def _assert_runtime_declared(names) -> None:
    """The AST-extracted knob registry agrees with the runtime property
    tier (conf.REGISTRY): every name resolves to a live SystemProperty."""
    from geomesa_tpu import conf

    for name in names:
        assert name in conf.REGISTRY, name
        assert conf.REGISTRY[name].name == name


def test_migration_guide_apis_exist():
    from geomesa_tpu import process as P
    from geomesa_tpu import streaming as S
    from geomesa_tpu.audit import FileAuditWriter  # noqa: F401
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.parallel.mesh import make_multihost_mesh  # noqa: F401
    from geomesa_tpu.planning.hints import QueryHints
    from geomesa_tpu.sql import (  # noqa: F401
        FUNCTIONS,
        spatial_join,
        spatial_join_indexed,
        sql_query,
    )

    for m in [
        "write", "modify_features", "upsert", "delete_features", "age_off",
        "query", "query_many", "density", "stats_query", "bin_query",
        "bounds", "count", "explain", "stats_for", "analyze_stats",
    ]:
        assert hasattr(DataStore, m), m
    for fn in [
        "knn_search", "knn_many", "proximity_search", "route_search",
        "tube_select", "unique_values", "join_search", "point2point",
        "track_label", "date_offset", "bin_conversion", "arrow_conversion",
    ]:
        assert hasattr(P, fn), fn
    for c in ["StreamingFeatureCache", "FeatureStream", "LambdaStore"]:
        assert hasattr(S, c), c
    from geomesa_tpu.features import FeatureCollection
    from geomesa_tpu.sft import FeatureType

    assert hasattr(FeatureType, "from_spec")
    assert hasattr(FeatureCollection, "from_columns")
    assert len(FUNCTIONS) >= 83
    QueryHints(
        transforms=["a"], sort_by="x", offset=1, sample=0.5, sample_by="t",
        loose=True, timeout=1.0, reproject="EPSG:3857",
    )
    assert "limit" in inspect.signature(DataStore.query).parameters


def test_durability_doc_apis_exist():
    """docs/durability.md stays honest the same way: every durability/
    fault API it names is real."""
    from geomesa_tpu import fault
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.storage import persist
    from geomesa_tpu.streaming import LambdaStore

    for name in ("save", "load", "damage_report", "StoreCorruptionError",
                 "StoreHealth", "DamageRecord"):
        assert hasattr(persist, name), name
    for name in ("inject", "with_retries", "fault_point", "injector",
                 "InjectedCrash", "InjectedIOError", "chaos", "ChaosSpec"):
        assert hasattr(fault, name), name
    assert set(fault.KINDS) == {
        "io_error", "crash", "partial_write", "bit_flip", "latency",
    }
    assert isinstance(DataStore.store_health, property)
    for m in ("persist_hot", "checkpoint", "recover", "write", "delete",
              "expire"):
        assert hasattr(LambdaStore, m), m
    assert "on_damage" in inspect.signature(persist.load).parameters
    # the streaming WAL surface the doc's "Streaming WAL" section names
    from geomesa_tpu.streaming import WalConfig, WriteAheadLog

    for m in ("append", "sync", "replay", "checkpoint", "retire", "close"):
        assert hasattr(WriteAheadLog, m), m
    for f in ("sync", "sync_interval_ms", "segment_bytes"):
        assert f in WalConfig.__dataclass_fields__, f
    for p in ("wal", "wal_dir", "wal_config"):
        assert p in inspect.signature(LambdaStore.__init__).parameters, p
    for p in ("metrics", "rng"):
        assert p in inspect.signature(fault.with_retries).parameters, p
    for p in ("seed", "rate", "points", "kinds"):
        assert p in inspect.signature(fault.chaos).parameters, p


def test_migration_guide_dotted_names_resolve():
    """Every `process.X` / `streaming.X` / `sql.X` / `ds.X(...)` name the
    guide mentions in backticks resolves against the real modules."""
    import geomesa_tpu.process as P
    import geomesa_tpu.sql as Q
    import geomesa_tpu.streaming as S
    from geomesa_tpu.datastore import DataStore

    path = os.path.join(os.path.dirname(__file__), "..", "docs", "migration.md")
    text = open(path).read()
    mods = {"process": P, "streaming": S, "sql": Q}
    for mod, name in re.findall(r"`(process|streaming|sql)\.(\w+)", text):
        assert hasattr(mods[mod], name), f"{mod}.{name}"
    for name in re.findall(r"`ds\.(\w+)", text):
        assert hasattr(DataStore, name), f"ds.{name}"


def test_feature_expiry_user_data_key():
    """The guide's geomesa.feature.expiry claim: age_off with no ttl
    reads the schema key (reference age-off configuration)."""
    import numpy as np

    from geomesa_tpu import DataStore, FeatureCollection, FeatureType

    sft = FeatureType.from_spec("ev", "dtg:Date,*geom:Point:srid=4326")
    sft.user_data["geomesa.feature.expiry"] = "7 days"
    ds = DataStore()
    ds.create_schema(sft)
    now = np.datetime64("2024-02-01T00:00:00", "ms").astype(np.int64)
    t = np.array([now - 10 * 86_400_000, now - 86_400_000], dtype=np.int64)
    ds.write("ev", FeatureCollection.from_columns(
        sft, ["old", "new"], {"dtg": t, "geom": (np.zeros(2), np.zeros(2))}))
    removed = ds.age_off("ev", now_ms=int(now))
    assert removed == 1
    assert [str(i) for i in ds.query("ev", "INCLUDE").ids] == ["new"]

    from geomesa_tpu.datastore import parse_expiry_ms

    assert parse_expiry_ms("7 days") == 7 * 86_400_000
    assert parse_expiry_ms("24 hours") == 86_400_000
    assert parse_expiry_ms("30 minutes") == 1_800_000
    assert parse_expiry_ms("90 seconds") == 90_000
    assert parse_expiry_ms("1 week") == 7 * 86_400_000
    assert parse_expiry_ms("5000") == 5000
    assert parse_expiry_ms("dtg(2 days)") == 2 * 86_400_000
    assert parse_expiry_ms("dtg(2 days)", dtg_field="dtg") == 2 * 86_400_000
    import pytest

    with pytest.raises(ValueError, match="unparseable"):
        parse_expiry_ms("fortnight")
    with pytest.raises(ValueError, match="not the time attribute"):
        # attribute-based expiry on a non-default attribute must refuse,
        # never silently sweep by the wrong column
        parse_expiry_ms("updated(7 days)", dtg_field="dtg")
    with pytest.raises(ValueError, match="no ttl_ms"):
        ds2 = DataStore()
        s2 = FeatureType.from_spec("e2", "dtg:Date,*geom:Point:srid=4326")
        ds2.create_schema(s2)
        ds2.age_off("e2")


def test_serving_doc_apis_exist():
    """docs/serving.md stays honest the same way: every serving API,
    knob, metric, and dotted name it documents is real."""
    import inspect

    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.serving import (
        QueryScheduler, ServingConfig, ServingRejected,  # noqa: F401
    )

    assert hasattr(DataStore, "serve")
    for m in ("submit", "query", "start", "close", "closed", "window_s"):
        assert hasattr(QueryScheduler, m), m
    for f in ("window_ms", "queue_max", "batch_max"):
        assert f in ServingConfig.__dataclass_fields__, f
    assert "block" in inspect.signature(QueryScheduler.submit).parameters
    # every geomesa.serving.* knob and metric (analyzer registries, the
    # single source of truth) is declared at runtime and cited by the doc
    knobs, metrics = _area_names("geomesa.serving.")
    assert len(knobs) >= 3 and len(metrics) >= 6, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("serving.md", knobs + metrics)
    # the documented instrument kinds render through the registry,
    # including the histogram exposition the doc points operators at
    reg = MetricsRegistry()
    by_name = _registries().metrics.by_name()
    for n in metrics:
        kind = by_name[n][0].instrument
        if kind == "counter":
            reg.counter(n)
        elif kind == "gauge":
            reg.gauge(n, 0.0)
        elif kind == "histogram":
            reg.observe(n, 0.01)
        else:
            reg.timer_update(n, 0.01)
    text = reg.render_prometheus()
    assert "geomesa_serving_shed 1" in text
    # queue wait is a live histogram (docs/observability.md): proper
    # _bucket{le=...}/_sum/_count families
    assert 'geomesa_serving_queue_wait_seconds_bucket{le="' in text
    assert "geomesa_serving_queue_wait_seconds_count 1" in text
    # every `ds.X` / `sched.X` the guide mentions in backticks resolves
    path = os.path.join(os.path.dirname(__file__), "..", "docs", "serving.md")
    text = open(path).read()
    for name in re.findall(r"`ds\.(\w+)", text):
        assert hasattr(DataStore, name), f"ds.{name}"
    for name in re.findall(r"`sched\.(\w+)", text):
        assert hasattr(QueryScheduler, name), f"sched.{name}"


def test_data_plane_doc_honest():
    """docs/serving.md "The data plane" stays honest: the server and
    client APIs, the request/response headers, the status-code knobs
    and every geomesa.serve.* / geomesa.tenant.* name it documents are
    real, declared at runtime, and cited by both serving.md and the
    config.md knob index."""
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.serving import (
        DataClient, DataServer, ServeError, TenantRegistry,
    )
    from geomesa_tpu.serving import http as serve_http
    from geomesa_tpu.streaming.replica import ReplicaStore
    from geomesa_tpu.streaming.store import LambdaStore

    # serve(port=...) mounts the data plane on every tier the doc names
    for cls in (DataStore, LambdaStore, ReplicaStore):
        assert "port" in inspect.signature(cls.serve).parameters, cls
    for m in ("query", "ingest", "tenants", "health", "metrics_text",
              "request"):
        assert hasattr(DataClient, m), m
    for m in ("handle_get", "handle_post", "start", "close", "url",
              "port", "tenants"):
        assert hasattr(DataServer, m), m
    for m in ("tenant_of", "configure", "report", "weights", "queue_cap"):
        assert hasattr(TenantRegistry, m), m
    err = ServeError(429, "shed", retry_after=0.05)
    assert err.status == 429 and err.retry_after == 0.05
    assert hasattr(ReplicaStore, "tail_disk")
    # the documented headers are the module's constants, verbatim
    text = open(
        os.path.join(_ROOT, "docs", "serving.md")
    ).read()
    for h in (serve_http.AUTHS_HEADER, serve_http.TENANT_HEADER,
              serve_http.STALENESS_HEADER, serve_http.LEADER_HEADER,
              serve_http.ROWS_HEADER):
        assert h in text, h
    # knob/metric completeness, both directions, from the analyzer
    # registries (the single source of truth)
    serve_knobs, serve_metrics = _area_names("geomesa.serve.")
    tenant_knobs, tenant_metrics = _area_names("geomesa.tenant.")
    assert len(serve_knobs) == 4, serve_knobs
    assert len(tenant_knobs) == 3, tenant_knobs
    assert len(serve_metrics) >= 3, serve_metrics
    assert len(tenant_metrics) >= 4, tenant_metrics
    _assert_runtime_declared(serve_knobs + tenant_knobs)
    _assert_documented(
        "serving.md",
        serve_knobs + tenant_knobs + serve_metrics + tenant_metrics,
    )
    _assert_documented("config.md", serve_knobs + tenant_knobs)


def test_caching_doc_apis_exist():
    """docs/caching.md stays honest the same way: every cache API,
    knob, and metric name it documents is real."""
    from geomesa_tpu.cache import (  # noqa: F401
        BUCKET_MS,
        CacheConfig,
        GenerationTracker,
        KeyRange,
        QueryCache,
        ResultCache,
        TileAggregateCache,
        fingerprint,
        key_range_of,
        mutation_range,
    )
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.filter.predicates import canonical_key  # noqa: F401
    from geomesa_tpu.planning.hints import QueryHints
    from geomesa_tpu.storage import persist

    import inspect

    assert "cache" in inspect.signature(DataStore.__init__).parameters
    assert hasattr(DataStore, "attach_cache")
    # persist.load forwards store kwargs (including cache=) to DataStore
    assert any(
        p.kind is inspect.Parameter.VAR_KEYWORD
        for p in inspect.signature(persist.load).parameters.values()
    )
    QueryHints(cache="bypass")
    QueryHints(cache="pin")
    for m in ("fingerprint_plan", "key_range", "on_mutation",
              "on_schema_dropped", "on_quarantine", "stats"):
        assert hasattr(QueryCache, m), m
    # every geomesa.cache.* knob and metric (analyzer registries) is
    # declared at runtime and cited by the doc
    knobs, metrics = _area_names("geomesa.cache.")
    assert len(knobs) >= 6 and len(metrics) >= 12, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("caching.md", knobs + metrics)


def test_ingest_doc_apis_exist():
    """docs/ingest.md stays honest the same way: every pipeline API,
    knob, metric, and fault point it documents is real."""
    import inspect

    from geomesa_tpu.ingest import (  # noqa: F401
        BulkLoader,
        IngestError,
        IngestResult,
        PipelineConfig,
        SortRun,
        ingest_files,
        merge_runs,
        plan_splits,
        shard_runs,
    )
    from geomesa_tpu.metrics import MetricsRegistry

    for m in ("put", "close", "abort"):
        assert hasattr(BulkLoader, m), m
    for f in ("workers", "queue_depth", "chunk_rows", "merge_min_bins"):
        assert f in PipelineConfig.__dataclass_fields__, f
    assert hasattr(PipelineConfig, "from_properties")
    for f in ("written", "errors", "splits", "split_errors", "stage_seconds"):
        assert f in IngestResult.__dataclass_fields__, f
    for attr in ("split_index", "worker_traceback"):
        assert attr in inspect.signature(IngestError.__init__).parameters
    assert "workers" in inspect.signature(ingest_files).parameters
    # every geomesa.ingest.* knob and metric (analyzer registries) is
    # declared at runtime and cited by the doc; the span-rows compaction
    # knob the doc's memory model leans on rides along
    knobs, metrics = _area_names("geomesa.ingest.")
    assert len(knobs) >= 4 and len(metrics) >= 4, (knobs, metrics)
    _assert_runtime_declared(knobs + ["geomesa.tpu.compact.span.rows"])
    _assert_documented(
        "ingest.md", knobs + metrics + ["geomesa.tpu.compact.span.rows"]
    )
    # the documented metric names render, including the f-string stage
    # timer family the registry records as a geomesa.ingest.* prefix
    assert "geomesa.ingest." in _registries().metrics.prefixes()
    by_name = _registries().metrics.by_name()
    reg = MetricsRegistry()
    for n in metrics:
        kind = by_name[n][0].instrument
        if kind == "counter":
            reg.counter(n)
        elif kind == "gauge":
            reg.gauge(n, 0.0)
        else:
            reg.timer_update(n, 0.0)
    for t in ("parse", "keys", "sort", "commit", "finalize"):
        reg.timer_update(f"geomesa.ingest.{t}", 0.0)
    assert "geomesa_ingest_queue_full 1" in reg.render_prometheus()
    # the documented fault points exist in the pipeline source (the fault
    # registry is pattern-based, so presence is a source-level contract)
    import geomesa_tpu.ingest.pipeline as pl
    import geomesa_tpu.ingest.splits as sp

    src = inspect.getsource(pl) + inspect.getsource(sp)
    for point in ("ingest.split.read", "ingest.parse", "ingest.keys",
                  "ingest.sort", "ingest.commit", "ingest.finalize"):
        assert point in src, point
    # `ds.compact` / `ds.write` mentioned by the doc resolve, and compact
    # takes the presorted perms the pipeline feeds it
    from geomesa_tpu.datastore import DataStore

    assert "presorted" in inspect.signature(DataStore.compact).parameters
    # the doc's dotted `ds.X` mentions resolve
    import re as _re

    path = os.path.join(os.path.dirname(__file__), "..", "docs", "ingest.md")
    text = open(path).read()
    for name in _re.findall(r"`ds\.(\w+)", text):
        assert hasattr(DataStore, name), f"ds.{name}"


def test_fused_coverage_doc_honest():
    """docs/serving.md "Fused coverage" stays honest: every constant and
    API the matrix names is real and matches the code."""
    from geomesa_tpu.scan import block_kernels as bk
    from geomesa_tpu.storage.table import IndexTable
    from geomesa_tpu.parallel.dtable import DistributedIndexTable

    root = os.path.join(os.path.dirname(__file__), "..")
    text = open(os.path.join(root, "docs", "serving.md")).read()
    assert "Fused coverage" in text

    # the documented E ladder is the code's E ladder, and every
    # pack_edges polygon fits a fused bucket (the matrix's 256-edge row)
    assert f"FUSED_E_BUCKETS = {bk.FUSED_E_BUCKETS}" in text
    assert bk.FUSED_E_BUCKETS[-1] == bk.E_BUCKETS[-1]
    assert bk.fused_e_bucket(bk.E_BUCKETS[-1]) == bk.FUSED_E_BUCKETS[-1]

    # documented APIs: the fused seam, the wide-only chunk rule, warmup,
    # and the mesh override the matrix's shard_map row relies on
    for name in ("scan_submit_many", "_submit_fused_chunk", "fused_slots",
                 "warmup"):
        assert hasattr(IndexTable, name), name
    assert "skip_inner_plane" in text and hasattr(bk, "skip_inner_plane")
    assert (
        DistributedIndexTable._submit_fused_chunk
        is not IndexTable._submit_fused_chunk
    )
    # kernel-level contract the matrix documents: block_scan_multi takes
    # the edge stack + per-slot selector
    import inspect

    sig = inspect.signature(bk.block_scan_multi).parameters
    for p in ("edges", "spip", "n_edges"):
        assert p in sig, p


def test_processes_doc_honest():
    """docs/processes.md stays honest: every API, parameter, constant and
    span the kNN / tube doc names is real, and migration.md points to it."""
    import inspect

    from geomesa_tpu.filter import dnf
    from geomesa_tpu.process import knn, tube
    from geomesa_tpu.scan import block_kernels as bk

    root = os.path.join(os.path.dirname(__file__), "..")
    text = open(os.path.join(root, "docs", "processes.md")).read()
    for p in ("k", "estimated_distance_m", "max_distance_m", "filter"):
        assert p in inspect.signature(knn.knn_search).parameters and f"`{p}" in text, p
    for p in ("track_xy", "track_times_ms", "buffer_m", "bin_ms", "max_bins"):
        assert p in inspect.signature(tube.tube_select).parameters and p in text, p
    assert inspect.signature(tube.tube_select).parameters["max_bins"].default == 256
    assert knn.EARTH_RADIUS_M == 6_371_000.0 and "6,371,000" in text
    assert dnf.MAX_DISJUNCTS == 16 and "`filter.dnf.MAX_DISJUNCTS` = 16" in text
    assert hasattr(bk, "pack_boxes") and "pack_boxes" in text
    for fn in ("_estimate_radius_m", "_refine_radius_local", "_meters_to_degrees"):
        assert hasattr(knn, fn) and fn in text, fn
    src = inspect.getsource(knn) + inspect.getsource(tube)
    for span in ("knn", "knn.estimate", "knn.round", "knn.rank", "tube", "tube.bins",
                 "tube.refine"):
        assert f'"{span}"' in src and f"`{span}`" in text, span
    for attr in ("members", "rounds", "windows", "candidates", "returned", "short", "probes",
                 "pending", "radius_max_m", "in_radius", "waypoints", "bins", "boxes", "ranges",
                 "rows", "kept"):
        assert attr in src and f"`{attr}`" in text, attr
    assert "capture=False" in src and "capture=False" in text
    obs_text = open(os.path.join(root, "docs", "observability.md")).read()
    assert "| `knn` |" in obs_text and "| `tube` |" in obs_text
    mig = open(os.path.join(root, "docs", "migration.md")).read()
    assert mig.count("(processes.md)") == 2


def test_attribute_index_doc_honest():
    """docs/attribute-index.md stays honest: the names it gives are the
    code's, the multipliers it quotes are the planner's, and the README and
    the observability page point to it."""
    import inspect

    from geomesa_tpu.filter import extract
    from geomesa_tpu.index import api, attribute
    from geomesa_tpu.planning import planner
    from geomesa_tpu.storage import table
    from geomesa_tpu.utils import lexicode

    root = os.path.join(os.path.dirname(__file__), "..")
    text = open(os.path.join(root, "docs", "attribute-index.md")).read()
    for mod, names in ((lexicode, ("bounds_to_range", "lex_bounds", "lex_string_words",
                                   "MAX_SUB_WORDS")),
                       (extract, ("extract_attribute_bounds",)),
                       (table, ("_rows_in_spans",)),
                       (planner, ("mask_decides_filter", "INDEX_PRIORITY"))):
        for name in names:
            assert hasattr(mod, name) and name in text, name
    for field in ("clip_rows", "range_lo2", "range_hi2"):
        assert field in api.ScanConfig.__dataclass_fields__ and field in text, field
    assert "sub" in api.WriteKeys.__dataclass_fields__ and "WriteKeys.sub" in text
    for fn in ("_select_single", "_plan_arrays", "cost", "_post", "plan_many"):
        assert hasattr(planner.QueryPlanner, fn) and fn in text, fn
    assert hasattr(table.IndexTable, "_post_decode") and "_post_decode" in text
    pri = planner.INDEX_PRIORITY
    assert (pri["z3"], pri["z2"], pri["attr"]) == (1.1, 2.0, 2.5)
    assert "z3 1.1, z2 2.0, an attribute index 2.5" in text
    # "inside the array stages": the batched entry, and the one-member case through it
    assert hasattr(attribute.AttributeIndex, "scan_configs") and "scan_configs" in text
    assert "scan_configs" in inspect.getsource(attribute.AttributeIndex.scan_config)
    assert "filter" in extract.Extraction.__dataclass_fields__ and "Extraction" in text
    src = inspect.getsource(planner) + inspect.getsource(table)
    for attr in ("clip_in", "clip_kept", "residual_rows", "costed", "attr_offered", "attr_won"):
        assert f'"{attr}"' in src, attr
    for attr in ("clip_in", "clip_kept", "residual_rows", "costed"):
        assert f"`{attr}`" in text, attr
    assert '_ospan("sort"' in src and "`sort` span" in text
    obs_text = open(os.path.join(root, "docs", "observability.md")).read()
    for attr in ("index", "costed", "attr_offered", "attr_won", "clip_in", "clip_kept",
                 "residual_rows"):
        assert f"`{attr}`" in obs_text, attr
    assert "(attribute-index.md)" in obs_text
    for reader in ("attr_plan_ms", "plan_lost_ms", "attr_chosen_pct", "attr_scan_ms",
                   "attr_clip_keep_pct", "sort_ms"):
        assert f"`{reader}`" in obs_text, reader
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics", reader + ".py"))
    assert "(attribute-index.md)" in open(os.path.join(root, "docs", "README.md")).read()


def test_joins_doc_honest():
    """docs/joins.md stays honest: every API, knob, metric and constant
    the raster/adaptive-join doc names is real."""
    import inspect

    from geomesa_tpu import conf
    from geomesa_tpu import geometry as geo
    from geomesa_tpu.filter import raster as fr
    from geomesa_tpu.index.api import ScanConfig
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.scan import block_kernels as bk

    root = os.path.join(os.path.dirname(__file__), "..")
    text = open(os.path.join(root, "docs", "joins.md")).read()

    # the raster build surface + the conservative-margin contract
    assert hasattr(fr, "build_raster") and hasattr(fr, "raster_for")
    for m in ("zranges", "pack_block", "classify_points", "cell_counts",
              "boundary_fraction", "decided_fraction"):
        assert hasattr(fr.RasterApprox, m), m
    assert hasattr(geo, "classify_raster_cells")
    for c in ("RASTER_FULL", "RASTER_PARTIAL", "RASTER_OUT"):
        assert hasattr(geo, c), c
    assert "RASTER_MARGIN" in text and fr.RASTER_MARGIN > 0

    # kernel tier: the rast config field, the R ladder, the fused operand
    assert "rast" in ScanConfig.__dataclass_fields__
    assert hasattr(bk, "FUSED_R_BUCKETS") and hasattr(bk, "R_BUCKETS")
    sig = inspect.signature(bk.block_scan_multi).parameters
    for p in ("rasts", "n_rints"):
        assert p in sig, p
    sig1 = inspect.signature(bk.block_scan).parameters
    for p in ("rast", "n_rints"):
        assert p in sig1, p

    # every geomesa.raster.* / geomesa.join.* knob and metric (analyzer
    # registries) is declared at runtime and cited by this doc, at the
    # doc table's defaults
    raster_knobs, raster_metrics = _area_names("geomesa.raster.")
    join_knobs, join_metrics = _area_names("geomesa.join.")
    assert len(raster_knobs) >= 5 and len(join_knobs) >= 4
    assert len(join_metrics) >= 6, join_metrics
    _assert_runtime_declared(raster_knobs + join_knobs)
    _assert_documented(
        "joins.md",
        raster_knobs + join_knobs + raster_metrics + join_metrics,
    )
    for name, default in [
        ("geomesa.raster.enabled", True),
        ("geomesa.raster.max.cells", 16384),
        ("geomesa.raster.min.edges", 8),
        ("geomesa.raster.kernel.intervals", 16),
        ("geomesa.raster.residue", "host"),
        ("geomesa.join.adaptive", True),
        ("geomesa.join.sample", 512),
        ("geomesa.join.broad.fraction", 0.25),
        ("geomesa.join.in.selectivity", 0.5),
    ]:
        assert conf.REGISTRY[name].default == default, name

    # join surfaces: strategy args + the counter read path the doc names
    from geomesa_tpu.process.join import join_search
    from geomesa_tpu.sql.join import spatial_join, spatial_join_indexed

    assert "strategy" in inspect.signature(spatial_join).parameters
    assert "metrics" in inspect.signature(spatial_join_indexed).parameters
    for p in ("explain", "metrics"):
        assert p in inspect.signature(join_search).parameters, p
    reg = MetricsRegistry()
    for c in join_metrics:
        reg.counter(c)
    assert reg.counter_value("geomesa.join.in_cap_fallback") == 1

    # PR 52: who owns what an indexed join returns, and what its assembly counts
    from geomesa_tpu.sql import join as sj
    from geomesa_tpu.storage import table

    assert "## What an indexed join returns, and whose it is" in text
    assert hasattr(sj, "_assemble") and "`_assemble`" in text
    assert hasattr(table.IndexTable, "_post_decode") and "_post_decode" in text
    src = inspect.getsource(sj._join_indexed)
    obs_text = open(os.path.join(root, "docs", "observability.md")).read()
    row = next(line for line in obs_text.splitlines() if line.startswith("| `join` |"))
    assert '_ospan("join.assemble"' in src and "`join.assemble`" in text
    for attr in ("pairs", "sorted", "moved"):
        assert f"{attr}=" in src and f"`{attr}`" in text and f"`{attr}`" in row, attr
    for reader in ("join_assemble_ns_pair", "join_assemble_moved_pct"):
        assert f"`{reader}`" in obs_text, reader
        assert os.path.exists(os.path.join(root, "benchmark", "layer_metrics", reader + ".py"))


def test_analysis_rule_catalog_documented():
    """docs/analysis.md stays honest: every shipped rule id appears in
    its catalog, and the catalog names no phantom rules."""
    from geomesa_tpu import analysis

    text = open(os.path.join(_ROOT, "docs", "analysis.md")).read()
    ids = {r.id for r in analysis.ALL_RULES} | {"parse-error"}
    for rid in sorted(ids):
        assert f"`{rid}`" in text, f"rule {rid!r} missing from docs/analysis.md"
    for rid in re.findall(r"^\| `([a-z][a-z0-9-]+)` \|", text, re.MULTILINE):
        assert rid in ids, f"docs/analysis.md catalogs unknown rule {rid!r}"


def test_streaming_doc_honest():
    """docs/streaming.md: every API it names is real, and it cites every
    geomesa.stream.* knob and metric (the per-area completeness
    direction; name VALIDITY is analyzer-checked by doc-unknown-name)."""
    from geomesa_tpu import streaming as S
    from geomesa_tpu.datastore import DataStore

    for name in ("StreamingFeatureCache", "StreamFlusher", "StreamConfig",
                 "LambdaStore", "FeatureStream"):
        assert hasattr(S, name), name
    for m in ("write", "flush", "persist_hot", "checkpoint", "query",
              "count", "serve", "close"):
        assert hasattr(S.LambdaStore, m), m
    for m in ("upsert", "delete", "expire", "evict", "snapshot_rows",
              "query_shadow"):
        assert hasattr(S.StreamingFeatureCache, m), m
    assert hasattr(S.StreamFlusher, "flush")
    assert hasattr(DataStore, "fold_upsert")
    assert hasattr(DataStore, "id_exists_mask")
    knobs, metrics = _area_names("geomesa.stream.")
    assert len(knobs) >= 5, knobs
    _assert_documented("streaming.md", knobs + metrics)
    _assert_documented("config.md", knobs)
    _assert_runtime_declared(knobs)
    # the stage-timer family (an f-string prefix) is cited as a family
    text = open(os.path.join(_ROOT, "docs", "streaming.md")).read()
    assert "geomesa.stream.*" in text


def test_concurrency_doc_honest():
    """docs/concurrency.md stays honest BOTH directions, derived from
    the LOCKS registry (the knob/metric/fault convention): every
    registered lock appears in the doc's table with its exact rank and
    hot flag, the table names no phantom locks, and every witness API /
    knob the doc leans on is real."""
    import inspect

    from geomesa_tpu import conf, lockwitness
    from geomesa_tpu.analysis.lockmodel import (
        DECLARED_BLOCKING, DECLARED_EDGES, LOCKS,
    )

    text = open(os.path.join(_ROOT, "docs", "concurrency.md")).read()
    # parse the registry table: | `Class.attr` | rank | hot? | guards |
    doc_rows = {}
    for line in text.splitlines():
        m = re.match(r"^\| `([\w.]+)` \| (\d+) \| (hot)? ?\|", line)
        if m:
            doc_rows[m.group(1)] = (int(m.group(2)), bool(m.group(3)))
    assert doc_rows, "docs/concurrency.md lock table not found"
    for name, d in sorted(LOCKS.items()):
        assert name in doc_rows, f"LOCKS entry {name} missing from the doc"
        assert doc_rows[name] == (d.rank, d.hot), (
            f"{name}: doc says {doc_rows[name]}, registry says "
            f"{(d.rank, d.hot)}"
        )
    for name in doc_rows:
        assert name in LOCKS, f"doc table names phantom lock {name!r}"
    # guarded fields the table cites are the registry's
    for name, d in LOCKS.items():
        for f in d.fields:
            assert f"`{f}`" in text or f in text, (name, f)
    # the witness surface the doc describes is real
    for fn in ("witness", "enable", "disable", "dump", "note_blocking",
               "held_locks"):
        assert hasattr(lockwitness, fn), fn
    for m in ("cycle", "snapshot", "reset", "note_acquire"):
        assert hasattr(lockwitness.WitnessReport, m), m
    assert "path" in inspect.signature(lockwitness.dump).parameters
    # env gate mapping: the documented GEOMESA_TPU_LOCK_WITNESS really
    # is the knob's env key, and both knobs resolve at runtime
    assert conf.LOCK_WITNESS.env_key == "GEOMESA_TPU_LOCK_WITNESS"
    assert "GEOMESA_TPU_LOCK_WITNESS" in text
    assert conf.REGISTRY["geomesa.tpu.lock.witness"].default is False
    assert conf.REGISTRY["geomesa.tpu.lock.witness.artifact"].default == (
        "/tmp/lock_witness.json"
    )
    # declared exceptions carry justifications (they are doc-adjacent:
    # each is a visible, accepted design cost)
    for a, b, why in DECLARED_EDGES:
        assert why and a in LOCKS and b in LOCKS
    for lock, pat, why in DECLARED_BLOCKING:
        assert why and lock in LOCKS and pat


def test_observability_doc_honest():
    """docs/observability.md stays honest the registry way: every
    obs/tracing/SLO API it names is real, every geomesa.obs.* knob and
    metric is declared at runtime and cited by the doc (and the knobs
    by config.md), and the documented histogram exposition renders."""
    import inspect

    import pytest

    from geomesa_tpu import obs
    from geomesa_tpu.datastore import DataStore
    from geomesa_tpu.metrics import HIST_EDGES, Histogram, MetricsRegistry
    from geomesa_tpu.obs.trace import NULL_SPAN  # noqa: F401

    for name in ("Span", "Trace", "TraceBuffer", "Tracer", "SloObjective",
                 "SloTracker", "default_objectives", "install",
                 "phase_breakdown", "span", "tracer"):
        assert hasattr(obs, name), name
    for m in ("dump_trace", "slow_queries", "attach_slo", "slo_report"):
        assert hasattr(DataStore, m), m
    assert hasattr(DataStore, "slo")
    for m in ("begin", "end", "trace", "span", "activate", "add_span",
              "dump", "slow_queries", "traces", "reset", "armed"):
        assert hasattr(obs.Tracer, m), m
    for m in ("observe", "histogram_quantile"):
        assert hasattr(MetricsRegistry, m), m
    for f in ("name", "metric", "quantile", "threshold_s", "budget"):
        assert f in obs.SloObjective.__dataclass_fields__, f
    assert "objectives" in inspect.signature(
        DataStore.attach_slo
    ).parameters
    # the documented bucket ladder: sqrt-2 growth from 1 µs, 64 buckets
    assert len(HIST_EDGES) == 64 and HIST_EDGES[0] == 1e-6
    assert HIST_EDGES[2] / HIST_EDGES[0] == pytest.approx(2.0)
    assert Histogram().quantile(0.99) == 0.0
    # every geomesa.obs.* knob/metric resolves at runtime and is cited
    knobs, metrics = _area_names("geomesa.obs.")
    assert len(knobs) >= 12 and len(metrics) >= 3, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("observability.md", knobs + metrics)
    _assert_documented("config.md", knobs)
    # the ops plane (docs/observability.md "The ops plane"): the APIs
    # and endpoints the doc tables promise are real
    for name in ("OpsServer", "TelemetryRecorder", "HealthMonitor",
                 "EstimateAccuracy", "ops_report", "stats_payload",
                 "error_factor"):
        assert hasattr(obs, name), name
    for m in ("serve_ops", "close", "ops", "accuracy"):
        assert hasattr(DataStore, m), m
    for m in ("start", "close", "handle", "port", "url", "closed"):
        assert hasattr(obs.OpsServer, m), m
    for m in ("sample", "series", "start", "stop"):
        assert hasattr(obs.TelemetryRecorder, m), m
    assert hasattr(obs.HealthMonitor, "evaluate")
    for m in ("record", "report", "stale", "reset", "sample_count"):
        assert hasattr(obs.EstimateAccuracy, m), m
    assert hasattr(obs.Tracer, "chrome_payload")
    import geomesa_tpu.obs.ops as ops_mod

    doc_text = open(os.path.join(_ROOT, "docs", "observability.md")).read()
    for endpoint in ("/metrics", "/health", "/stats", "/debug/slow",
                     "/debug/trace", "/debug/vars", "/debug/audit"):
        assert endpoint in doc_text, endpoint
        assert endpoint in inspect.getsource(ops_mod.OpsRoutes.handle), endpoint
    # the route table is shared: both the ops server and the data plane
    # mount it (docs/serving.md "The data plane")
    assert hasattr(ops_mod.OpsServer, "routes") or "OpsRoutes" in (
        inspect.getsource(ops_mod.OpsServer.__init__)
    )
    # every documented health reason code is a literal the monitor adds
    monitor_src = inspect.getsource(ops_mod.HealthMonitor.evaluate)
    for code in ("store.quarantine", "wal.needs_recovery", "slo.breach",
                 "hot.occupancy", "scheduler.shedding", "scheduler.queue",
                 "scheduler.saturated", "standing.drops", "stats.stale",
                 "replica.staleness", "replica.ship.giveup"):
        assert code in doc_text, code
        assert code in monitor_src, code
    # estimate accountability: the geomesa.plan.* namespace is complete
    # both directions in both docs
    plan_knobs, plan_metrics = _area_names("geomesa.plan.")
    assert len(plan_knobs) == 4 and len(plan_metrics) >= 2, (
        plan_knobs, plan_metrics,
    )
    _assert_runtime_declared(plan_knobs)
    _assert_documented("observability.md", plan_knobs + plan_metrics)
    _assert_documented("config.md", plan_knobs)
    from geomesa_tpu.planning.planner import QueryPlan

    for f in ("estimated_rows", "actual_rows"):
        assert f in QueryPlan.__dataclass_fields__, f
    # the histogram metrics the doc tables promise render as histograms
    reg = MetricsRegistry()
    for n in ("geomesa.query.scan", "geomesa.serving.queue_wait",
              "geomesa.stream.fold.slice", "geomesa.stream.wal.fsync"):
        reg.observe(n, 0.01)
    text = reg.render_prometheus()
    for base in ("geomesa_query_scan", "geomesa_serving_queue_wait",
                 "geomesa_stream_fold_slice", "geomesa_stream_wal_fsync"):
        assert f"# TYPE {base}_seconds histogram" in text
        assert f'{base}_seconds_bucket{{le="+Inf"}} 1' in text
    # every `ds.X` the guide mentions in backticks resolves
    path = os.path.join(_ROOT, "docs", "observability.md")
    doc = open(path).read()
    for name in re.findall(r"`ds\.(\w+)", doc):
        assert hasattr(DataStore, name), f"ds.{name}"


def test_standing_doc_honest():
    """docs/standing.md stays honest the registry way: every standing
    API it names is real, every geomesa.standing.* knob and metric is
    declared at runtime and cited by the doc (knobs by config.md too),
    and the fault points exist in the source."""
    import inspect

    from geomesa_tpu import process as P
    from geomesa_tpu import streaming as S
    from geomesa_tpu.metrics import MetricsRegistry

    for name in ("Subscription", "SubscriptionIndex", "StandingConfig",
                 "StandingQueryEngine", "WindowSpec", "WindowedAggregator",
                 "AlertQueue"):
        assert hasattr(S, name), name
    for m in ("standing", "subscribe", "unsubscribe"):
        assert hasattr(S.LambdaStore, m), m
    for m in ("register", "unregister", "route", "kernel_block",
              "register_geofences", "subscription_ids"):
        assert hasattr(S.SubscriptionIndex, m), m
    for m in ("on_batch", "match_points", "register", "add_window",
              "attach_flusher"):
        assert hasattr(S.StandingQueryEngine, m), m
    for m in ("accept_rows", "value", "windows", "partials"):
        assert hasattr(S.WindowedAggregator, m), m
    for m in ("put_many", "drain"):
        assert hasattr(S.AlertQueue, m), m
    for fn in ("standing_proximity", "standing_tube"):
        assert hasattr(P, fn), fn
    # the kernel seam the doc names: segment-level packing + the fused
    # multi-scan's PIP leg
    from geomesa_tpu.scan import block_kernels as bk

    assert hasattr(bk, "pack_edge_segments")
    sig = inspect.signature(bk.block_scan_multi).parameters
    for p in ("edges", "spip", "n_edges"):
        assert p in sig, p
    # every geomesa.standing.* knob/metric resolves at runtime and is
    # cited by the doc; knobs ride config.md's complete index too
    knobs, metrics = _area_names("geomesa.standing.")
    assert len(knobs) >= 5 and len(metrics) >= 10, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("standing.md", knobs + metrics)
    _assert_documented("config.md", knobs)
    # the SLO knob the delivery section leans on
    _assert_runtime_declared(["geomesa.obs.slo.standing.p99.ms"])
    _assert_documented("standing.md", ["geomesa.obs.slo.standing.p99.ms"])
    # documented fault points exist at source level (the registry is
    # pattern-based, like the ingest fault points)
    import geomesa_tpu.streaming.standing as st

    src = inspect.getsource(st)
    for point in ("standing.match", "standing.deliver"):
        assert point in src, point
    for span in ("standing.route", "standing.match", "standing.deliver"):
        assert span in src, span
    # the documented metric kinds render through the registry
    by_name = _registries().metrics.by_name()
    reg = MetricsRegistry()
    for n in metrics:
        kind = by_name[n][0].instrument
        if kind == "counter":
            reg.counter(n)
        elif kind == "gauge":
            reg.gauge(n, 1.0)
        elif kind == "histogram":
            reg.observe(n, 0.01)
        else:
            reg.timer_update(n, 0.01)
    text = reg.render_prometheus()
    assert "geomesa_standing_subscriptions 1" in text
    assert 'geomesa_standing_latency_seconds_bucket{le="' in text
    doc = open(os.path.join(_ROOT, "docs", "standing.md")).read()
    # every `lam.X` / `engine.X` the doc mentions in backticks resolves
    for name in re.findall(r"`lam\.(\w+)", doc):
        assert hasattr(S.LambdaStore, name), f"lam.{name}"
    for name in re.findall(r"`engine\.(\w+)", doc):
        assert hasattr(S.StandingQueryEngine, name), f"engine.{name}"


def test_replication_doc_honest():
    """docs/replication.md stays honest the registry way: every
    replication API it names is real, every geomesa.replica.* knob and
    metric is declared at runtime and cited by the doc (knobs by
    config.md too), and the fault points and fencing hooks exist in the
    source."""
    import inspect

    from geomesa_tpu import streaming as S
    from geomesa_tpu.metrics import MetricsRegistry

    for name in ("SegmentShipper", "ReplicaStore", "PipeTransport",
                 "SocketTransport"):
        assert hasattr(S, name), name
    for m in ("attach", "detach", "pump", "start", "stop",
              "gave_up_report"):
        assert hasattr(S.SegmentShipper, m), m
    for m in ("poll", "drain", "start", "stop", "promote", "query",
              "staleness_ms", "close"):
        assert hasattr(S.ReplicaStore, m), m
    from geomesa_tpu.streaming.replica import ReplicaError, StaleRead

    assert issubclass(StaleRead, ReplicaError)
    # the WAL-side shipping hooks the doc leans on
    from geomesa_tpu.streaming.wal import WriteAheadLog

    for m in ("ship_state", "log_term"):
        assert hasattr(WriteAheadLog, m), m
    assert isinstance(WriteAheadLog.term, property)
    # every geomesa.replica.* knob/metric resolves at runtime and is
    # cited by the doc; knobs ride config.md's complete index too
    knobs, metrics = _area_names("geomesa.replica.")
    assert len(knobs) >= 4 and len(metrics) >= 8, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("replication.md", knobs + metrics)
    _assert_documented("config.md", knobs)
    # the staleness SLO knob the bounded-staleness section leans on
    _assert_runtime_declared(["geomesa.obs.slo.replica.staleness.p99.ms"])
    _assert_documented(
        "replication.md", ["geomesa.obs.slo.replica.staleness.p99.ms"]
    )
    # documented fault points exist at source level
    import geomesa_tpu.streaming.replica as rp

    src = inspect.getsource(rp)
    for point in ("replica.ship.segment", "replica.apply",
                  "replica.promote", "replica.fence"):
        assert point in src, point
    # the replay-progress gauge rides the recover() callback
    from geomesa_tpu.streaming.store import LambdaStore

    assert "on_progress" in inspect.signature(
        LambdaStore.recover
    ).parameters
    # the documented metric kinds render through the registry
    by_name = _registries().metrics.by_name()
    reg = MetricsRegistry()
    for n in metrics:
        kind = by_name[n][0].instrument
        if kind == "counter":
            reg.counter(n)
        elif kind == "gauge":
            reg.gauge(n, 1.0)
        elif kind == "histogram":
            reg.observe(n, 0.01)
        else:
            reg.timer_update(n, 0.01)
    text = reg.render_prometheus()
    assert 'geomesa_replica_staleness_ms_seconds_bucket{le="' in text
    doc = open(os.path.join(_ROOT, "docs", "replication.md")).read()
    # every `fol.X` / `ship.X` the doc mentions in backticks resolves
    for name in re.findall(r"`fol\.(\w+)", doc):
        assert hasattr(S.ReplicaStore, name), f"fol.{name}"
    for name in re.findall(r"`ship\.(\w+)", doc):
        assert hasattr(S.SegmentShipper, name), f"ship.{name}"


def test_config_doc_lists_every_knob():
    """docs/config.md is the complete operator-facing knob index (the
    knob-undocumented rule's backstop): every declared SystemProperty
    appears there by full name."""
    regs = _registries()
    assert len(regs.knobs.knobs) >= 25
    text = open(os.path.join(_ROOT, "docs", "config.md")).read()
    missing = [n for n in sorted(regs.knobs.knobs) if n not in text]
    assert not missing, f"docs/config.md does not list: {missing}"


def test_tiles_doc_honest():
    """docs/tiles.md stays honest the registry way: every tile API it
    names is real, every geomesa.tiles.* knob and metric is declared
    at runtime and cited by the doc (knobs by config.md too), the
    fault points exist in the source, and the documented endpoint and
    CLI wiring is real."""
    import inspect

    from geomesa_tpu import cli
    from geomesa_tpu.cache import QueryCache
    from geomesa_tpu.metrics import MetricsRegistry
    from geomesa_tpu.serving.http import DataClient, DataServer
    from geomesa_tpu.tiles import (
        KINDS, TileGrid, TileLattice, TilePyramid, TilesConfig,
        encode_png, render,
    )

    for m in ("fetch", "fresh", "peek", "note_delta", "invalidate_type",
              "sweep", "stats"):
        assert hasattr(TilePyramid, m), m
    for m in ("leaf_span", "tile_bbox", "bin_leaf", "children_of",
              "leaf_tiles_overlapping", "n_tiles", "valid"):
        assert hasattr(TileLattice, m), m
    for f in ("leaf_zoom", "px", "cache_max_bytes", "ttl_s",
              "ttl_jitter", "max_age_s"):
        assert f in TilesConfig.__dataclass_fields__, f
    for f in ("grid", "tick", "count"):
        assert f in TileGrid.__dataclass_fields__, f
    assert KINDS == ("density", "count", "heat")
    assert callable(encode_png) and callable(render)
    # the cache-tier seam: mutation hooks forward to an attached
    # pyramid, and its stats ride the cache tier's stats() payload
    assert hasattr(QueryCache, "attach_pyramid")
    assert hasattr(QueryCache, "stats")
    src = inspect.getsource(QueryCache)
    assert "pyramid" in src and "note_delta" in src
    # the documented HTTP surface: the server mounts /tiles/, answers
    # conditional GETs, and the stdlib client wraps it
    serve_src = inspect.getsource(DataServer)
    assert "/tiles/" in serve_src
    assert "If-None-Match" in serve_src
    assert "TilePyramid" in serve_src
    assert hasattr(DataClient, "tile")
    for p in ("fmt", "mode", "etag"):
        assert p in inspect.signature(DataClient.tile).parameters, p
    # the documented CLI command
    assert hasattr(cli, "cmd_tile")
    # every geomesa.tiles.* knob/metric resolves at runtime and is
    # cited by the doc; knobs ride config.md's complete index too
    knobs, metrics = _area_names("geomesa.tiles.")
    assert len(knobs) >= 5 and len(metrics) >= 7, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("tiles.md", knobs + metrics)
    _assert_documented("config.md", knobs)
    # the cross-area knobs the doc leans on: the shared TTL-jitter
    # spread and the tile-serving SLO objective
    _assert_runtime_declared(
        ["geomesa.cache.ttl.jitter", "geomesa.obs.slo.tiles.p99.ms"]
    )
    _assert_documented(
        "tiles.md",
        ["geomesa.cache.ttl.jitter", "geomesa.obs.slo.tiles.p99.ms"],
    )
    # documented fault points exist at source level
    import geomesa_tpu.tiles.pyramid as pyr

    src = inspect.getsource(pyr)
    for point in ("tiles.compose", "tiles.leaf.scan"):
        assert point in src, point
    # the documented metric kinds render through the registry
    by_name = _registries().metrics.by_name()
    reg = MetricsRegistry()
    for n in metrics:
        kind = by_name[n][0].instrument
        if kind == "counter":
            reg.counter(n)
        elif kind == "gauge":
            reg.gauge(n, 1.0)
        elif kind == "histogram":
            reg.observe(n, 0.01)
        else:
            reg.timer_update(n, 0.01)
    text = reg.render_prometheus()
    assert 'geomesa_tiles_fetch_seconds_bucket{le="' in text
    assert "geomesa_tiles_served 1" in text
    doc = open(os.path.join(_ROOT, "docs", "tiles.md")).read()
    # every `pyramid.X` the doc mentions in backticks resolves
    for name in re.findall(r"`pyramid\.(\w+)", doc):
        assert hasattr(TilePyramid, name), f"pyramid.{name}"


def test_distributed_doc_honest():
    """docs/distributed.md stays honest the registry way: every pod API
    it names is real, every geomesa.pod.* knob is declared at runtime
    and cited by the doc (and config.md's index), the fault points and
    locks exist in the source/registry, and the documented probe is
    real."""
    import inspect

    import geomesa_tpu.pod.store as pod_store
    import geomesa_tpu.pod.table as pod_table
    from geomesa_tpu import pod
    from geomesa_tpu.parallel.mesh import host_major_slices  # noqa: F401

    for name in ("HostGroup", "PodIndexTable", "PodStore",
                 "PodUnsupported", "make_host_group", "probe_capability"):
        assert hasattr(pod, name), name
    for m in ("mesh", "flat_mesh"):
        assert hasattr(pod.HostGroup, m), m
    for m in ("write", "delete", "bulk_load", "subscribe", "unsubscribe",
              "drain_alerts", "query", "count", "flush", "checkpoint",
              "kill", "rejoin", "owner", "close"):
        assert hasattr(pod.PodStore, m), m
    for m in ("_host_blocks", "_merge_host_rows", "_submit_fused_chunk"):
        assert hasattr(pod.PodIndexTable, m), m
    # rejoin rides the same replay-progress callback recover() exposes
    assert "on_progress" in inspect.signature(
        pod.PodStore.rejoin
    ).parameters
    # every geomesa.pod.* knob resolves at runtime and is cited by the
    # subsystem doc and the operator index (the pod tier declares no
    # metrics of its own — its shards report through the scan tier's)
    knobs, metrics = _area_names("geomesa.pod.")
    assert len(knobs) == 3 and not metrics, (knobs, metrics)
    _assert_runtime_declared(knobs)
    _assert_documented("distributed.md", knobs)
    _assert_documented("config.md", knobs)
    # documented fault points exist at source level on both seams
    src = inspect.getsource(pod_table) + inspect.getsource(pod_store)
    for point in ("pod.dispatch", "pod.join", "pod.wal.route",
                  "pod.wal.replay"):
        assert point in src, point
    # the pod locks the doc points at are registered with the ranks the
    # concurrency table shows (below every host store lock)
    from geomesa_tpu.analysis.lockmodel import LOCKS

    assert "PodStore._route_lock" in LOCKS
    assert (LOCKS["PodStore._route_lock"].rank
            < LOCKS["DataStore._write_lock"].rank)
    # the capability probe the doc points at
    doc = open(os.path.join(_ROOT, "docs", "distributed.md")).read()
    assert os.path.exists(
        os.path.join(_ROOT, "scripts", "probe_multiprocess.py")
    )
    # every `group.X` / `pod.X` the guide mentions in backticks resolves
    for name in re.findall(r"`group\.(\w+)", doc):
        assert hasattr(pod.HostGroup, name), f"group.{name}"
    from geomesa_tpu.analysis.registries import FAULT_POINTS

    fault_points = {p.split(".", 1)[1] for p in FAULT_POINTS if p.startswith("pod.")}
    for name in re.findall(r"`pod\.([\w.]+)`", doc):
        assert (
            hasattr(pod.PodStore, name.split(".", 1)[0]) or name in fault_points
        ), f"pod.{name}"


def _doc_pages():
    docs = os.path.join(_ROOT, "docs")
    pages = sorted(f"docs/{n}" for n in os.listdir(docs) if n.endswith(".md"))
    return pages + ["README.md"]


#: a repo path as a doc writes it: under one of the tree's directories,
#: or a bare file name - a file at the root (its records are upper-case:
#: ``BENCHMARK.json``; ``metadata.json`` is a saved store's own file) or
#: a module named without its package (``conf.py``)
_DOC_PATH = re.compile(
    r"^(?:\.\./|\./)*"
    r"((?:geomesa_tpu|tests|scripts|benchmark|docs|examples)/[\w./-]*"
    r"|[\w-]+\.(?:py|md)|[A-Z][A-Z0-9_]*\.jsonl?)"
    r"(?::\d+(?:-\d+)?)?$"
)


@functools.lru_cache(maxsize=1)
def _module_basenames():
    return {
        n for _, _, files in os.walk(os.path.join(_ROOT, "geomesa_tpu"))
        for n in files if n.endswith(".py")
    }


@pytest.mark.parametrize("page", _doc_pages())
def test_docs_name_only_files_that_exist(page):
    """Every repo path a page names in backticks or links to exists:
    a deleted file's last mention goes with the file. Globs and
    ``<rev>:path`` forms are not checked."""
    text = open(os.path.join(_ROOT, page)).read()
    here = os.path.dirname(os.path.join(_ROOT, page))
    named = re.findall(r"`([^`\n]+)`", text)
    named += [t.split("#")[0] for t in re.findall(r"\]\(([^)\s]+)\)", text)]
    missing = []
    for token in named:
        m = _DOC_PATH.match(token.strip())
        if m is None or "*" in token or "<" in token:
            continue
        path = m.group(1)
        # a link is relative to its page, a backticked path to the root
        if not (os.path.exists(os.path.join(_ROOT, path))
                or os.path.exists(os.path.join(here, path))
                or path in _module_basenames()):
            missing.append(token)
    assert not missing, f"{page} names files that do not exist: {missing}"
