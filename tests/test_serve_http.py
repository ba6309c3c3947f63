"""The data plane over real sockets (geomesa_tpu/serving/http.py,
docs/serving.md "The data plane"): every test round-trips through a
bound listener and the stdlib DataClient — no handler short-circuits.

The contracts pinned here:

- **wire == in-process**: streamed GeoJSON and Arrow IPC responses are
  BIT-IDENTICAL to the one-shot exporters over the same direct query;
- **paging is complete**: sort_by + offset/limit pages union to exactly
  the full result, no duplicates, no gaps;
- **ack == durable**: an ingest 200 on a WAL-backed store survives
  `wal.crash()` (kill -9) + `LambdaStore.recover`;
- **shed is visible**: admission pressure answers 429 + Retry-After
  (never silent queueing), per-tenant quotas isolate a flooding tenant
  from a compliant one, and `/tenants` accounts for both;
- **replicas are honest**: reads honor the max-staleness header (503 +
  Retry-After when unmeasured/stale), writes answer 403 + the leader
  address;
- **auths narrow, never widen**: requested auths beyond the server's
  are 403; a subset masks rows server-side;
- hostile payloads and hostile visibility expressions are counted 400s
  (plus direct parser fuzz), never worker tracebacks.
"""

import json
import threading

import numpy as np
import pytest

from geomesa_tpu import geometry as geo, security
from geomesa_tpu.datastore import DataStore
from geomesa_tpu.features import FeatureCollection
from geomesa_tpu.io.exporters import _geojson
from geomesa_tpu.metrics import MetricsRegistry
from geomesa_tpu.security import VIS_FIELD_KEY, VisibilityError
from geomesa_tpu.serving import (
    DataClient,
    QueryScheduler,
    ServeError,
    ServingConfig,
)
from geomesa_tpu.sft import FeatureType
from geomesa_tpu.storage import persist
from geomesa_tpu.streaming import (
    LambdaStore,
    PipeTransport,
    ReplicaStore,
    SegmentShipper,
    StreamConfig,
    WalConfig,
)

DAY = 86400_000
T0 = np.datetime64("2024-01-01T00:00:00", "ms").astype(np.int64)
Q = "BBOX(geom, -60, -45, 60, 45)"
SPEC = "name:String:index=true,dtg:Date,*geom:Point:srid=4326"


def _store(n=300, auths=None, spec=SPEC, type_name="t", extra_cols=None,
           user_data=None):
    sft = FeatureType.from_spec(type_name, spec)
    for k, v in (user_data or {}).items():
        sft.user_data[k] = v
    ds = DataStore(tile=64, auths=auths, metrics=MetricsRegistry())
    ds.create_schema(sft)
    rng = np.random.default_rng(11)
    cols = {
        "name": np.array([f"n{i:04d}" for i in range(n)]),
        "dtg": T0 + rng.integers(0, 20 * DAY, n),
        "geom": (rng.uniform(-50, 50, n), rng.uniform(-40, 40, n)),
    }
    cols.update(extra_cols or {})
    ds.write(type_name, FeatureCollection.from_columns(
        sft, [f"f{i}" for i in range(n)], cols,
    ))
    return ds


def _feature(fid, name, x=0.5, y=0.5, dtg=1704067200000, **props):
    props = dict({"name": name, "dtg": dtg}, **props)
    return {
        "type": "Feature", "id": fid,
        "geometry": {"type": "Point", "coordinates": [x, y]},
        "properties": props,
    }


def _payload(*features):
    return {"type": "FeatureCollection", "features": list(features)}


@pytest.fixture(scope="module")
def served():
    """(store, server, client) over one module-lifetime DataStore."""
    ds = _store()
    srv = ds.serve(port=0)
    try:
        yield ds, srv, DataClient(srv.url)
    finally:
        ds.close()


# -- wire formats: bit-identical to the in-process exporters ----------------

class TestWireFormats:
    def test_geojson_bytes_identical_to_export(self, served):
        ds, srv, client = served
        status, hdrs, raw = client.request(
            "GET", "/query/t?cql=" + Q.replace(" ", "%20")
        )
        direct = ds.query("t", Q)
        assert status == 200
        assert hdrs["Content-Type"] == "application/geo+json"
        assert hdrs["X-Geomesa-Rows"] == str(len(direct))
        assert raw == _geojson(direct).encode()

    def test_geojson_identity_across_page_sizes(self, served):
        """Chunk boundaries are a transport detail: any page_rows
        reassembles to the same bytes."""
        ds, srv, client = served
        want = _geojson(ds.query("t", Q)).encode()
        for rows in (1, 7, 100, 100000):
            _, _, raw = client.request(
                "GET",
                f"/query/t?cql={Q.replace(' ', '%20')}&page_rows={rows}",
            )
            assert raw == want, rows

    def test_arrow_bytes_identical_to_stream(self, served):
        from geomesa_tpu.io.arrow import arrow_stream, read_arrow

        ds, srv, client = served
        raw = client.query("t", cql=Q, fmt="arrow", page_rows=64)
        direct = ds.query("t", Q)
        assert raw == arrow_stream(direct, batch_rows=64)
        # and it decodes back to the same collection
        rt = read_arrow(raw, sft=ds.get_schema("t"))
        assert sorted(map(str, rt.ids.tolist())) == sorted(
            map(str, direct.ids.tolist())
        )

    @pytest.mark.parametrize("rows", [1, 64, "all", 4096])
    def test_arrow_identity_across_page_sizes(self, served, rows):
        """One record batch a page, whatever the page: the chunks of one
        page (schema, dictionaries, batch, end of stream) and of many
        (later pages as their own IPC messages) are the one-shot
        stream's bytes with the same batch rows."""
        from geomesa_tpu.io.arrow import arrow_stream

        ds, srv, client = served
        direct = ds.query("t", Q)
        rows = len(direct) if rows == "all" else rows
        raw = client.query("t", cql=Q, fmt="arrow", page_rows=rows)
        assert len(direct) > 64 and raw == arrow_stream(direct, batch_rows=rows)

    def test_keep_alive_connection_reused(self, served):
        ds, srv, client = served
        with DataClient(srv.url, keep_alive=True) as ka:
            first = ka.query("t", cql=Q, limit=5)
            conn = ka._conn
            assert conn is not None
            for _ in range(3):
                assert ka.query("t", cql=Q, limit=5) == first
                assert ka._conn is conn  # same socket the whole time
            # a dead socket is transparently reopened for GETs
            conn.close()
            assert ka.query("t", cql=Q, limit=5) == first
        assert ka._conn is None  # context exit dropped it

    def test_empty_result_both_formats(self, served):
        ds, srv, client = served
        none = "BBOX(geom, 170, 80, 171, 81)"
        out = client.query("t", cql=none)
        assert out["type"] == "FeatureCollection" and out["features"] == []
        raw = client.query("t", cql=none, fmt="arrow")
        from geomesa_tpu.io.arrow import read_arrow_table

        assert read_arrow_table(raw).num_rows == 0


# -- paging -----------------------------------------------------------------

class TestPaging:
    def test_paged_union_is_complete_and_duplicate_free(self, served):
        ds, srv, client = served
        page = 64
        got = []
        offset = 0
        while True:
            out = client.query(
                "t", cql=Q, sort_by="name", offset=offset, limit=page
            )
            feats = out["features"]
            got.extend(f["id"] for f in feats)
            offset += page
            if len(feats) < page:
                break
        full = ds.query("t", Q)
        assert len(got) == len(set(got)) == len(full)
        assert set(got) == set(map(str, full.ids.tolist()))
        # pages came out in one global sorted order, not per-page order
        names = {str(i): str(v) for i, v in zip(
            full.ids.tolist(), np.asarray(full.columns["name"]).tolist()
        )}
        assert [names[g] for g in got] == sorted(names[g] for g in got)

    def test_limit_caps_rows_and_header(self, served):
        ds, srv, client = served
        status, hdrs, raw = client.request(
            "GET", "/query/t?limit=10"
        )
        assert status == 200 and hdrs["X-Geomesa-Rows"] == "10"
        assert len(json.loads(raw)["features"]) == 10


# -- the error contract -----------------------------------------------------

class TestErrorContract:
    def test_statuses(self, served):
        ds, srv, client = served
        for path, want in (
            ("/query/nope", 404),          # unknown type
            ("/nope", 404),                # unknown path
            ("/query/t?fmt=csv", 400),     # unknown format
            ("/query/t?cql=NOT%20CQL(((", 400),  # ECQL parse error
            ("/query/t?limit=banana", 400),      # bad parameter
        ):
            status, hdrs, raw = client.request("GET", path)
            assert status == want, path
            assert "error" in json.loads(raw), path

    def test_bad_requests_counted_and_worker_survives(self, served):
        ds, srv, client = served
        before = ds.metrics.counters.get("geomesa.serve.badrequest", 0)
        with pytest.raises(ServeError) as e:
            client.query("t", cql="NOT CQL(((")
        assert e.value.status == 400
        assert ds.metrics.counters["geomesa.serve.badrequest"] > before
        assert client.health()["http_status"] == 200  # still serving

    def test_post_requires_length_and_bounds_body(self, served):
        ds, srv, client = served
        status, _, _ = client.request("POST", "/ingest/t")
        assert status == 411
        big = srv.max_body_bytes + 1
        status, _, raw = client.request(
            "POST", "/ingest/t",
            headers={"Content-Length": str(big)},
        )
        assert status == 413 and "bound" in json.loads(raw)["error"]


# -- ops endpoints ride the same port ---------------------------------------

class TestOpsMounted:
    def test_ops_surfaces_on_data_port(self, served):
        ds, srv, client = served
        h = client.health()
        assert h["http_status"] == 200 and h["status"] in (
            "ready", "degraded", "unhealthy"
        )
        assert "geomesa" in client.metrics_text()
        assert client.stats()  # non-empty stats payload
        rep = client.tenants()
        assert {"default_weight", "default_queue_max", "tenants"} <= set(rep)


# -- ingest -----------------------------------------------------------------

class TestIngest:
    def test_cold_store_ingest_roundtrip(self):
        ds = _store(n=10)
        with ds.serve(port=0) as srv:
            client = DataClient(srv.url)
            ack = client.ingest("t", _payload(
                _feature("in-0", "zz-a"), _feature("in-1", "zz-b", x=1.5),
            ))
            assert ack == {"acked": 2, "durable": False, "type": "t"}
            out = client.query("t", cql="name = 'zz-a'")
            assert [f["id"] for f in out["features"]] == ["in-0"]
        assert ds.metrics.counters["geomesa.serve.ingested"] == 2
        ds.close()

    def test_wal_ack_survives_crash_and_recover(self, tmp_path):
        """ack == durable: kill -9 after the 200, recover from disk,
        every acked id is back."""
        ds = _store(n=20)
        root = str(tmp_path / "s")
        persist.save(ds, root)
        lam = LambdaStore(
            ds, "t", config=StreamConfig(chunk_rows=64, fold_rows=4096),
            wal_dir=f"{root}/_wal",
            wal_config=WalConfig(sync="always", sync_interval_ms=1e9),
        )
        srv = lam.serve(port=0)
        client = DataClient(srv.url)
        ack = client.ingest("t", _payload(
            *(_feature(f"d{i}", f"dur-{i}", x=i * 0.01) for i in range(15))
        ))
        assert ack["acked"] == 15 and ack["durable"] is True
        srv.close()
        lam.wal.crash()  # kill -9: no close, no checkpoint
        rec = LambdaStore.recover(root)
        got = set(map(str, rec.query("INCLUDE").ids.tolist()))
        assert {f"d{i}" for i in range(15)} <= got
        lam.flusher.close()
        rec.close()

    def test_hostile_payloads_are_counted_400s(self, served):
        ds, srv, client = served
        before = ds.metrics.counters.get("geomesa.serve.badrequest", 0)
        cases = [
            (b'{"type": "FeatureCollection", "features": [{', "geojson"),
            (b"not json at all", "geojson"),
            (b'{"type": "Polygon"}', "geojson"),  # not a collection
            (b"\xff\xfe\x00garbage-ipc", "arrow"),
        ]
        for body, fmt in cases:
            with pytest.raises(ServeError) as e:
                client.ingest("t", body, fmt=fmt)
            assert e.value.status == 400, body
        assert (
            ds.metrics.counters["geomesa.serve.badrequest"]
            >= before + len(cases)
        )
        assert client.health()["http_status"] == 200  # workers alive

    def test_hostile_visibility_label_rejected_before_storage(self):
        ds = _store(
            n=10, auths=("admin",),
            spec=SPEC + ",vis:String",
            extra_cols={"vis": np.array([""] * 10)},
            user_data={VIS_FIELD_KEY: "vis"},
        )
        with ds.serve(port=0) as srv:
            client = DataClient(srv.url)
            with pytest.raises(ServeError) as e:
                client.ingest("t", _payload(
                    _feature("bad-0", "x", vis="admin & ((((("),
                ))
            assert e.value.status == 400
            assert "isibility" in e.value.body
            out = client.query("t", cql="name = 'x'")
            assert out["features"] == []  # nothing stored
        ds.close()


# -- admission control: shed is visible, tenants are isolated ---------------

class TestAdmission:
    def test_tenant_quota_sheds_429_with_retry_after(self):
        ds = _store(n=50)
        srv = ds.serve(port=0)
        srv.tenants.configure("flood", queue_max=0)
        flood = DataClient(srv.url, tenant="flood")
        calm = DataClient(srv.url, tenant="calm")
        with pytest.raises(ServeError) as e:
            flood.query("t", cql=Q)
        assert e.value.status == 429
        assert e.value.retry_after is not None and e.value.retry_after > 0
        # the compliant tenant is untouched by the flood tenant's quota
        out = calm.query("t", cql=Q, limit=5)
        assert len(out["features"]) == 5
        rep = srv.tenants.report()
        rows = {r["tenant"]: r for r in rep["tenants"]}
        assert rows["flood"]["shed"] >= 1 and rows["flood"]["served"] == 0
        assert rows["calm"]["served"] >= 1 and rows["calm"]["shed"] == 0
        ds.close()

    def test_shared_queue_full_sheds_429_not_silent_queueing(self):
        """A full admission queue answers 429 + Retry-After immediately
        — deterministic via an unstarted scheduler holding one queued
        submission."""
        ds = _store(n=50)
        sched = QueryScheduler(ds, ServingConfig(queue_max=1))
        ds.scheduler = sched  # serve() reuses the attached scheduler
        srv = ds.serve(port=0)
        sched.submit("t", Q, block=False)  # parks: dispatcher never ran
        client = DataClient(srv.url)
        with pytest.raises(ServeError) as e:
            client.query("t", cql=Q)
        assert e.value.status == 429 and e.value.retry_after is not None
        assert "Retry-After" in e.value.headers
        ds.close()

    def test_concurrent_mixed_tenants_all_accounted(self):
        ds = _store(n=100)
        srv = ds.serve(port=0)
        errs = []

        def worker(tenant, n=4):
            c = DataClient(srv.url, tenant=tenant)
            for _ in range(n):
                try:
                    c.query("t", cql=Q, limit=3)
                except Exception as e:  # noqa: BLE001 — collected below
                    errs.append(e)

        ts = [threading.Thread(target=worker, args=(f"w{i}",))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs
        rows = {r["tenant"]: r for r in srv.tenants.report()["tenants"]}
        for i in range(4):
            assert rows[f"w{i}"]["served"] == 4
        ds.close()


# -- auths: narrow, never widen ---------------------------------------------

class TestAuths:
    def _vis_store(self):
        n = 40
        return _store(
            n=n, auths=("admin", "user"),
            spec=SPEC + ",vis:String",
            extra_cols={"vis": np.array(["admin", "user"] * (n // 2))},
            user_data={VIS_FIELD_KEY: "vis"},
        )

    def test_subset_auths_mask_rows(self):
        ds = self._vis_store()
        with ds.serve(port=0) as srv:
            full = DataClient(srv.url, auths=("admin", "user"))
            narrow = DataClient(srv.url, auths=("user",))
            all_rows = full.query("t", cql=Q)["features"]
            user_rows = narrow.query("t", cql=Q)["features"]
            assert 0 < len(user_rows) < len(all_rows)
            assert all(
                f["properties"]["vis"] == "user" for f in user_rows
            )
        ds.close()

    def test_auths_beyond_server_403(self):
        ds = self._vis_store()
        with ds.serve(port=0) as srv:
            client = DataClient(srv.url, auths=("secret",))
            for call in (
                lambda: client.query("t", cql=Q),
                lambda: client.ingest("t", _payload(_feature("a", "x"))),
            ):
                with pytest.raises(ServeError) as e:
                    call()
                assert e.value.status == 403
                assert "not held" in e.value.body
        ds.close()


# -- replicas ---------------------------------------------------------------

def _leader(tmp_path, n=30):
    ds = _store(n=n)
    root = str(tmp_path / "s")
    persist.save(ds, root)
    lam = LambdaStore(
        ds, "t", config=StreamConfig(chunk_rows=64, fold_rows=4096),
        wal_dir=f"{root}/_wal",
        wal_config=WalConfig(sync="always", sync_interval_ms=1e9),
    )
    return root, lam


class TestReplica:
    def test_staleness_bound_and_follower_403(self, tmp_path):
        root, lam = _leader(tmp_path)
        a, b = PipeTransport.pair()
        fol = ReplicaStore(
            root, str(tmp_path / "f" / "_wal"), b, type_name="t",
            config=StreamConfig(chunk_rows=64, fold_rows=4096),
        )
        ship = SegmentShipper(lam)
        ship.attach(a)
        srv = fol.serve(port=0, leader_url="http://leader.example:8080")
        client = DataClient(srv.url)
        # unmeasured staleness: a bounded read answers 503 + Retry-After
        with pytest.raises(ServeError) as e:
            client.query("t", cql=Q, max_staleness_ms=1000)
        assert e.value.status == 503
        assert e.value.retry_after is not None
        # an unbounded read serves whatever the replica has
        assert len(client.query("t", cql=Q)["features"]) > 0
        # replicate a write, then the bounded read succeeds and sees it
        lam.write(
            [{"name": "repl-new", "dtg": int(T0), "geom": geo.Point(1.0, 1.0)}],
            ids=["r-new"],
        )
        ship.pump()
        fol.drain()
        out = client.query("t", cql="name = 'repl-new'",
                           max_staleness_ms=60_000)
        assert [f["id"] for f in out["features"]] == ["r-new"]
        # writes are refused with the leader's address
        with pytest.raises(ServeError) as e:
            client.ingest("t", _payload(_feature("w", "x")))
        assert e.value.status == 403
        assert e.value.headers.get("X-Geomesa-Leader") == (
            "http://leader.example:8080"
        )
        srv.close()
        fol.close()
        lam.close()

    def test_disk_tail_replica_measures_staleness(self, tmp_path):
        """The CLI topology: no live transport, just tail_disk() over
        the leader's WAL directory."""
        root, lam = _leader(tmp_path)
        lam.write(
            [{"name": "tailed", "dtg": int(T0), "geom": geo.Point(2.0, 2.0)}],
            ids=["t-new"],
        )

        class _NoTransport:
            def send(self, msg):
                pass

            def recv(self, timeout=0.0):
                return None

            def close(self):
                pass

        fol = ReplicaStore(
            root, str(tmp_path / "f2" / "_wal"), _NoTransport(),
            type_name="t",
            config=StreamConfig(chunk_rows=64, fold_rows=4096),
        )
        applied = fol.tail_disk(f"{root}/_wal")
        assert applied >= 1 and fol.staleness_ms() is not None
        with fol.serve(port=0) as srv:
            out = DataClient(srv.url).query(
                "t", cql="name = 'tailed'", max_staleness_ms=60_000
            )
            assert [f["id"] for f in out["features"]] == ["t-new"]
        fol.close()
        lam.close()


# -- the CLI ----------------------------------------------------------------

class TestCli:
    def test_serve_command_smoke(self, tmp_path, capsys):
        from geomesa_tpu.cli import build_parser, cmd_serve

        ds = _store(n=15)
        root = str(tmp_path / "cat")
        persist.save(ds, root)
        ds.close()
        args = build_parser().parse_args(["serve", "-c", root, "--port", "0"])
        srv = cmd_serve(args, hold=False)
        try:
            assert f"at {srv.url}" in capsys.readouterr().out
            out = DataClient(srv.url).query("t", cql=Q)
            assert len(out["features"]) == 15
        finally:
            srv.store.close()

    def test_serve_replica_command_smoke(self, tmp_path, capsys):
        from geomesa_tpu.cli import build_parser, cmd_serve

        root, lam = _leader(tmp_path, n=12)
        args = build_parser().parse_args([
            "serve", "-c", root, "-f", "t", "--port", "0",
            "--replica-of", f"{root}/_wal",
            "--replica-wal", str(tmp_path / "rw"),
            "--leader-url", "http://leader:1",
        ])
        srv = cmd_serve(args, hold=False)
        try:
            client = DataClient(srv.url)
            assert len(client.query("t", cql=Q)["features"]) == 12
            with pytest.raises(ServeError) as e:
                client.ingest("t", _payload(_feature("w", "x")))
            assert e.value.status == 403
            assert e.value.headers.get("X-Geomesa-Leader") == "http://leader:1"
        finally:
            srv.store.close()
            lam.close()


# -- the visibility parser under fire (security.py hardening) ---------------

class TestVisibilityFuzz:
    def test_random_garbage_raises_only_visibility_error(self):
        rng = np.random.default_rng(3)
        alphabet = list("abcXYZ01&|()!~ \t\"'\\,;%$#@在界") + ["&&", "||"]
        for _ in range(300):
            expr = "".join(
                rng.choice(alphabet)
                for _ in range(int(rng.integers(0, 40)))
            )
            try:
                security.validate(expr)
                security.visible(expr, frozenset({"a"}))
            except VisibilityError:
                pass  # the only acceptable failure

    def test_valid_expressions_still_pass(self):
        for expr, auths, want in (
            ("", frozenset(), True),
            ("a", {"a"}, True),
            ("a&b", {"a", "b"}, True),
            ("a&b", {"a"}, False),
            ("(a|b)&c", {"b", "c"}, True),
            ("((a))", {"a"}, True),
        ):
            security.validate(expr)
            assert security.visible(expr, frozenset(auths)) is want, expr

    def test_length_and_depth_bombs_bounded(self):
        too_long = "a&" * (security.MAX_EXPRESSION_LENGTH // 2) + "a&a"
        with pytest.raises(VisibilityError, match="chars"):
            security.validate(too_long)
        bomb = "(" * (security.MAX_EXPRESSION_DEPTH + 8) + "a" + ")" * (
            security.MAX_EXPRESSION_DEPTH + 8
        )
        with pytest.raises(VisibilityError):
            security.validate(bomb)
        # at-the-limit inputs parse fine (the bound is not off by a mile)
        ok_depth = "(" * 8 + "a" + ")" * 8
        security.validate(ok_depth)

    def test_mask_over_hostile_object_column(self):
        labels = np.array(
            ["a", "", None, "a&zzz", "a|b"], dtype=object
        )
        m = security.visibility_mask(labels, frozenset({"a"}))
        assert m.tolist() == [True, True, True, False, True]


# -- live map tiles over the wire (docs/tiles.md) ----------------------------

class TestTiles:
    """`GET /tiles/<type>/<kind>/{z}/{x}/{y}`: PNG/Arrow payloads,
    generation-derived ETags, 304 revalidation with zero aggregation
    work, and scoped invalidation observable over the socket."""

    def _tile_store(self, n=400):
        from geomesa_tpu.cache import CacheConfig

        sft = FeatureType.from_spec("t", SPEC)
        ds = DataStore(
            tile=64, metrics=MetricsRegistry(),
            cache=CacheConfig(max_bytes=1 << 22),
        )
        ds.create_schema(sft)
        rng = np.random.default_rng(21)
        ds.write("t", FeatureCollection.from_columns(
            sft, [f"f{i}" for i in range(n)],
            {"name": np.array([f"n{i}" for i in range(n)]),
             "dtg": T0 + rng.integers(0, 20 * DAY, n),
             "geom": (rng.uniform(-170, 170, n), rng.uniform(-80, 80, n))},
        ))
        return ds

    def _agg_work(self, ds):
        """Counter snapshot of every code path that aggregates or
        composes — the 304 path must move NONE of them."""
        return tuple(
            ds.metrics.counter_value(n) for n in (
                "geomesa.tiles.compose", "geomesa.tiles.leaf.scan",
                "geomesa.tiles.fresh",
            )
        )

    def test_png_etag_304_roundtrip(self):
        ds = self._tile_store()
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            st, h, body = c.tile("t", "density", 1, 1, 0)
            assert st == 200
            assert h["Content-Type"] == "image/png"
            assert body[:8] == b"\x89PNG\r\n\x1a\n"
            assert h["Cache-Control"] == "no-cache"
            etag = h["ETag"]
            assert etag.startswith('"t') and etag.endswith('"')
            # revalidation: 304, empty body, same etag, NO aggregation
            # or render work, counted by geomesa.tiles.not_modified
            work0 = self._agg_work(ds)
            nm0 = ds.metrics.counter_value("geomesa.tiles.not_modified")
            st2, h2, b2 = c.tile("t", "density", 1, 1, 0, etag=etag)
            assert (st2, b2) == (304, b"")
            assert h2["ETag"] == etag
            assert self._agg_work(ds) == work0
            assert ds.metrics.counter_value(
                "geomesa.tiles.not_modified"
            ) == nm0 + 1
            # a stale etag re-serves the body
            st3, h3, b3 = c.tile("t", "density", 1, 1, 0, etag='"t999"')
            assert st3 == 200 and b3 == body
        ds.close()

    def test_warm_bit_identical_to_fresh_mode(self):
        pytest.importorskip("pyarrow")
        ds = self._tile_store()
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            for z, x, y in ((0, 0, 0), (1, 3, 1), (2, 5, 2), (3, 11, 4)):
                _st, _h, warm = c.tile("t", "count", z, x, y, fmt="arrow")
                _st, _h, oracle = c.tile(
                    "t", "count", z, x, y, fmt="arrow", mode="fresh"
                )
                assert warm == oracle, (z, x, y)
        ds.close()

    def test_arrow_grid_decodes(self):
        pa = pytest.importorskip("pyarrow")
        ds = self._tile_store(n=100)
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            _st, _h, data = c.tile("t", "count", 0, 0, 0, fmt="arrow")
            table = pa.ipc.open_stream(data).read_all()
            meta = table.schema.metadata
            h_, w_ = int(meta[b"rows"]), int(meta[b"cols"])
            grid = np.asarray(table["count"]).reshape(h_, w_)
            assert grid.shape == (256, 256)
            # the wire grid IS the pyramid grid
            assert np.array_equal(grid, srv.tiles.fetch("t", 0, 0, 0).grid)
        ds.close()

    def test_ingest_invalidates_scoped_over_http(self):
        ds = self._tile_store()
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            z = srv.tiles.lattice.leaf_zoom
            # two leaf tiles far apart: one will be written into
            _st, th, _b = c.tile("t", "density", z, 8, 3)   # near (8, 8)
            _st, fh, _b = c.tile("t", "density", z, 0, 0)   # far west
            ack = c.ingest("t", _payload(_feature("new-0", "x", 8.0, 8.0)))
            assert ack["acked"] == 1
            # touched tile: the old etag misses and a NEW etag arrives
            st, h2, _b = c.tile("t", "density", z, 8, 3, etag=th["ETag"])
            assert st == 200 and h2["ETag"] != th["ETag"]
            # far tile: still 304 off its old etag (stayed warm)
            st, _h, _b = c.tile("t", "density", z, 0, 0, etag=fh["ETag"])
            assert st == 304
        ds.close()

    def test_error_statuses(self):
        ds = self._tile_store(n=20)
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            for args, kwargs, want in (
                (("t", "viridis", 0, 0, 0), {}, 400),       # unknown kind
                (("t", "density", 9, 0, 0), {}, 400),       # beyond leaf zoom
                (("t", "density", 0, 5, 0), {}, 400),       # x out of range
                (("zz", "density", 0, 0, 0), {}, 404),      # unknown type
                (("t", "density", 0, 0, 0), {"fmt": "bmp"}, 400),
            ):
                with pytest.raises(ServeError) as ei:
                    c.tile(*args, **kwargs)
                assert ei.value.status == want, (args, kwargs)
            # malformed path shape: 404, counted, no traceback
            status, _h, _b = c.request("GET", "/tiles/t/density/1/2")
            assert status == 404
        ds.close()

    def test_visibility_labeled_schema_narrowed_auths_403(self):
        from geomesa_tpu.cache import CacheConfig

        sft = FeatureType.from_spec("t", SPEC + ",vis:String")
        sft.user_data[VIS_FIELD_KEY] = "vis"
        ds = DataStore(
            tile=64, auths=("admin", "user"), metrics=MetricsRegistry(),
            cache=CacheConfig(max_bytes=1 << 22),
        )
        ds.create_schema(sft)
        ds.write("t", FeatureCollection.from_columns(
            sft, ["a", "b"],
            {"name": np.array(["x", "y"]),
             "dtg": np.full(2, T0, dtype=np.int64),
             "geom": (np.array([1.0, 2.0]), np.array([1.0, 2.0])),
             "vis": np.array(["admin", "user"])},
        ))
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            # un-narrowed: tiles serve (the process's full view)
            st, _h, _b = c.tile("t", "density", 0, 0, 0)
            assert st == 200
            # narrowed auths cannot read whole-store densities
            with pytest.raises(ServeError) as ei:
                c.tile("t", "density", 0, 0, 0, auths=("user",))
            assert ei.value.status == 403
        ds.close()

    def test_tile_latency_histogram_records(self):
        ds = self._tile_store(n=50)
        with ds.serve(port=0) as srv:
            c = DataClient(srv.url)
            c.tile("t", "heat", 1, 0, 0)
            st, h, _b = c.tile("t", "heat", 1, 0, 0)
            c.tile("t", "heat", 1, 0, 0, etag=h["ETag"])
            text = c.metrics_text()
            assert "geomesa_tiles_fetch_seconds_bucket" in text
            assert "geomesa_tiles_served 2" in text
        ds.close()
