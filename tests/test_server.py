"""Server E2E: boot the app, drive it over HTTP against in-memory storage.

Mirrors ``ITZipkinServer`` (SURVEY.md §4). The first test is BASELINE
config[0]: POST the canonical 3-service TRACE, query it back exactly.
"""

import asyncio
import gzip
import json

from aiohttp.test_utils import TestClient, TestServer

from tests.fixtures import TRACE, TODAY
from zipkin_tpu.model import json_v2, proto3
from zipkin_tpu.server.app import ZipkinServer, parse_annotation_query
from zipkin_tpu.server.config import ServerConfig

DAY_MS = 86_400_000
QUERY_TS = TODAY + 3_600_000


def run(scenario):
    async def wrapper():
        server = ZipkinServer(
            ServerConfig(autocomplete_keys=("env",), default_lookback=DAY_MS)
        )
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await scenario(client)
        finally:
            await client.close()

    asyncio.run(wrapper())


def post_trace_body():
    return json_v2.encode_span_list(TRACE)


class TestIngestAndQuery:
    def test_baseline_config0_post_trace_and_read_back(self):
        async def scenario(client):
            resp = await client.post(
                "/api/v2/spans", data=post_trace_body(),
                headers={"Content-Type": "application/json"},
            )
            assert resp.status == 202
            resp = await client.get(f"/api/v2/trace/{TRACE[0].trace_id}")
            assert resp.status == 200
            spans = json_v2.decode_span_list(await resp.read())
            assert sorted(spans, key=lambda s: (s.id, bool(s.shared))) == sorted(
                TRACE, key=lambda s: (s.id, bool(s.shared))
            )

        run(scenario)

    def test_post_gzip(self):
        async def scenario(client):
            resp = await client.post(
                "/api/v2/spans", data=gzip.compress(post_trace_body()),
                headers={"Content-Encoding": "gzip"},
            )
            assert resp.status == 202
            resp = await client.get(f"/api/v2/trace/{TRACE[0].trace_id}")
            assert resp.status == 200

        run(scenario)

    def test_post_proto3(self):
        async def scenario(client):
            resp = await client.post(
                "/api/v2/spans", data=proto3.encode_span_list(TRACE),
                headers={"Content-Type": "application/x-protobuf"},
            )
            assert resp.status == 202
            resp = await client.get(f"/api/v2/trace/{TRACE[0].trace_id}")
            assert resp.status == 200

        run(scenario)

    def test_post_v1_json(self):
        async def scenario(client):
            from zipkin_tpu.model import json_v1

            resp = await client.post(
                "/api/v1/spans", data=json_v1.encode_v1_span_list(TRACE),
            )
            assert resp.status == 202
            resp = await client.get("/api/v2/services")
            assert "frontend" in await resp.json()

        run(scenario)

    def test_post_malformed_is_400(self):
        async def scenario(client):
            resp = await client.post("/api/v2/spans", data=b"\xffnot-spans")
            assert resp.status == 400
            resp = await client.post("/api/v2/spans", data=b'[{"traceId":"x!"}]')
            assert resp.status == 400

        run(scenario)

    def test_search_traces(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            resp = await client.get(
                "/api/v2/traces",
                params={"serviceName": "backend", "endTs": str(QUERY_TS),
                        "lookback": str(DAY_MS)},
            )
            assert resp.status == 200
            traces = await resp.json()
            assert len(traces) == 1 and len(traces[0]) == len(TRACE)
            resp = await client.get(
                "/api/v2/traces",
                params={"serviceName": "nope", "endTs": str(QUERY_TS)},
            )
            assert await resp.json() == []

        run(scenario)

    def test_search_by_annotation_query(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            resp = await client.get(
                "/api/v2/traces",
                params={"annotationQuery": "error", "endTs": str(QUERY_TS)},
            )
            assert len(await resp.json()) == 1

        run(scenario)

    def test_trace_not_found_404_and_bad_id_400(self):
        async def scenario(client):
            resp = await client.get("/api/v2/trace/feed")
            assert resp.status == 404
            resp = await client.get("/api/v2/trace/nothex!")
            assert resp.status == 400

        run(scenario)

    def test_trace_many(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            resp = await client.get(
                "/api/v2/traceMany",
                params={"traceIds": f"{TRACE[0].trace_id},feed"},
            )
            assert len(await resp.json()) == 1
            resp = await client.get("/api/v2/traceMany")
            assert resp.status == 400

        run(scenario)

    def test_names_endpoints(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            assert await (await client.get("/api/v2/services")).json() == [
                "backend", "frontend",
            ]
            assert await (
                await client.get("/api/v2/spans", params={"serviceName": "frontend"})
            ).json() == ["get /", "get /api"]
            assert await (
                await client.get(
                    "/api/v2/remoteServices", params={"serviceName": "backend"}
                )
            ).json() == ["mysql"]

        run(scenario)

    def test_dependencies(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            resp = await client.get(
                "/api/v2/dependencies",
                params={"endTs": str(QUERY_TS), "lookback": str(DAY_MS)},
            )
            links = sorted(await resp.json(), key=lambda x: x["parent"])
            assert links == [
                {"parent": "backend", "child": "mysql", "callCount": 1,
                 "errorCount": 1},
                {"parent": "frontend", "child": "backend", "callCount": 1},
            ]
            resp = await client.get("/api/v2/dependencies")
            assert resp.status == 400

        run(scenario)

    def test_autocomplete(self):
        async def scenario(client):
            span = dict(json_v2.span_to_dict(TRACE[0]))
            span["tags"] = {"env": "prod"}
            await client.post("/api/v2/spans", data=json.dumps([span]).encode())
            assert await (await client.get("/api/v2/autocompleteKeys")).json() == [
                "env"
            ]
            assert await (
                await client.get("/api/v2/autocompleteValues", params={"key": "env"})
            ).json() == ["prod"]
            resp = await client.get("/api/v2/autocompleteValues")
            assert resp.status == 400

        run(scenario)


class TestOps:
    def test_health(self):
        async def scenario(client):
            resp = await client.get("/health")
            assert resp.status == 200
            body = await resp.json()
            assert body["status"] == "UP"
            assert body["zipkin"]["mem"]["status"] == "UP"

        run(scenario)

    def test_info_and_ui_config(self):
        async def scenario(client):
            body = await (await client.get("/info")).json()
            assert "version" in body["zipkin"]
            ui = await (await client.get("/config.json")).json()
            assert ui["defaultLookback"] == DAY_MS

        run(scenario)

    def test_metrics_catalogue(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=post_trace_body())
            body = await (await client.get("/metrics")).json()
            assert body["counter.zipkin_collector.messages.http"] == 1
            assert body["counter.zipkin_collector.spans.http"] == len(TRACE)
            text = await (await client.get("/prometheus")).text()
            assert 'zipkin_collector_spans_total{transport="http"}' in text

        run(scenario)

    def test_metrics_count_drops(self):
        async def scenario(client):
            await client.post("/api/v2/spans", data=b"\xffgarbage")
            body = await (await client.get("/metrics")).json()
            assert body["counter.zipkin_collector.messages_dropped.http"] == 1

        run(scenario)


class TestAnnotationQueryGrammar:
    def test_parse(self):
        assert parse_annotation_query("error and http.method=GET") == {
            "error": "",
            "http.method": "GET",
        }
        assert parse_annotation_query(None) == {}
        assert parse_annotation_query("a=1 and a=2") == {"a": "2"}


class TestSampling:
    def test_sample_rate_zero_drops_all_but_debug(self):
        async def scenario(client):
            pass

        # direct collector-level test (deterministic)
        from zipkin_tpu.collector.core import Collector, CollectorSampler
        from zipkin_tpu.storage.memory import InMemoryStorage
        from zipkin_tpu.model.span import Span

        storage = InMemoryStorage()
        collector = Collector(storage, sampler=CollectorSampler(0.0))
        normal = Span.create("cafe", "1", timestamp=1, duration=1)
        debug = Span.create("feed", "2", timestamp=1, duration=1, debug=True)
        assert collector.accept([normal, debug]) == 1
        assert storage.span_count == 1

    def test_sampler_is_consistent_per_trace(self):
        from zipkin_tpu.collector.core import CollectorSampler

        sampler = CollectorSampler(0.5)
        for trace_id in (0x123456789ABCDEF0, 0xFEDCBA9876543210, 1, 2**63 + 5):
            assert sampler.is_sampled(trace_id) == sampler.is_sampled(trace_id)

    def test_sampler_rate_validated(self):
        import pytest
        from zipkin_tpu.collector.core import CollectorSampler

        with pytest.raises(ValueError):
            CollectorSampler(1.5)


class TestThrottle:
    def test_throttle_passes_through(self):
        from zipkin_tpu.storage.memory import InMemoryStorage
        from zipkin_tpu.storage.throttle import ThrottledStorage

        storage = ThrottledStorage(InMemoryStorage())
        storage.span_consumer().accept(TRACE).execute()
        spans = storage.span_store().get_trace(TRACE[0].trace_id).execute()
        assert len(spans) == len(TRACE)
        assert storage.check().ok

    def test_throttle_sheds_when_queue_full(self):
        import threading
        from zipkin_tpu.storage.memory import InMemoryStorage
        from zipkin_tpu.storage.throttle import (
            RejectedExecutionError,
            ThrottledStorage,
        )

        inner = InMemoryStorage()
        storage = ThrottledStorage(inner, max_concurrency=1, max_queue=1)
        gate = threading.Event()
        release = threading.Event()

        original = inner.span_consumer().accept

        class SlowConsumer:
            def accept(self, spans):
                call = original(spans)

                def slow():
                    gate.set()
                    release.wait(5)
                    return call.execute()

                from zipkin_tpu.utils.call import Call

                return Call.of(slow)

        storage.delegate.span_consumer = lambda: SlowConsumer()  # type: ignore
        throttled = storage.span_consumer()
        t = threading.Thread(
            target=lambda: throttled.accept(TRACE).execute(), daemon=True
        )
        t.start()
        gate.wait(5)
        # queue slot taken by the running call; next one must be rejected
        try:
            throttled.accept(TRACE).execute()
            rejected = False
        except RejectedExecutionError:
            rejected = True
        release.set()
        t.join(5)
        assert rejected

    def test_server_boots_with_throttle_enabled(self):
        async def scenario():
            server = ZipkinServer(ServerConfig(throttle_enabled=True))
            client = TestClient(TestServer(server.make_app()))
            await client.start_server()
            try:
                resp = await client.post("/api/v2/spans", data=post_trace_body())
                assert resp.status == 202
                resp = await client.get("/health")
                assert resp.status == 200
            finally:
                await client.close()

        asyncio.run(scenario())


class TestUiPage:
    def test_ui_index_loads_app_bundle(self):
        async def scenario(client):
            resp = await client.get("/zipkin/")
            assert resp.status == 200
            page = await resp.text()
            for marker in (
                'id="spanpanel"', 'id="view"', "/zipkin/static/app.js",
                "/zipkin/static/style.css", 'data-nav="dependencies"',
                'data-nav="sketches"',
            ):
                assert marker in page, marker

        run(scenario)

    def test_ui_app_js_has_all_views(self):
        async def scenario(client):
            resp = await client.get("/zipkin/static/app.js")
            assert resp.status == 200
            assert "javascript" in resp.headers["Content-Type"]
            js = await resp.text()
            # the r3/r4 feature set survives the SPA split: span-detail
            # panel + percentile context + dep graph + tree nesting,
            # plus the r5 views (collapse, minimap, service detail,
            # sketches panel)
            for marker in (
                "spanDetail(", "vs p99", "loadPctCtx", "depGraph(",
                "treeOrder(", "VIEWS.set('discover'", "VIEWS.set('trace'",
                "VIEWS.set('dependencies'", "VIEWS.set('sketches'",
                "drawMinimap(", "subtreeEnd(", "serviceDetail(",
            ):
                assert marker in js, marker

        run(scenario)

    def test_ui_style_css_served(self):
        async def scenario(client):
            resp = await client.get("/zipkin/static/style.css")
            assert resp.status == 200
            assert "css" in resp.headers["Content-Type"]
            css = await resp.text()
            assert ".bar.err" in css and "#spanpanel" in css

        run(scenario)

    def test_ui_asset_allowlist_blocks_traversal(self):
        async def scenario(client):
            # the asset route resolves names through a fixed allowlist,
            # never the filesystem — traversal shapes must 404
            for name in ("ui.py", "..%2Fui.py", "nope.js"):
                resp = await client.get(f"/zipkin/static/{name}")
                assert resp.status == 404, name

        run(scenario)

    def test_ui_responses_carry_csp(self):
        async def scenario(client):
            for path in ("/zipkin/", "/zipkin/static/app.js"):
                resp = await client.get(path)
                csp = resp.headers.get("Content-Security-Policy", "")
                assert "script-src 'self'" in csp, path
                assert "frame-ancestors 'none'" in csp, path
            # API responses are data, not documents — no CSP there
            resp = await client.get("/api/v2/services")
            assert "Content-Security-Policy" not in resp.headers

        run(scenario)


class TestFanoutBackpressure:
    def test_full_worker_queues_map_to_429(self):
        """IngestBackpressure from the parse fan-out tier is the
        client's retry-after-backoff signal (429), distinct from the
        reader-throttle's 503 — a load balancer must be able to tell
        "slow down" from "node unhealthy"."""
        from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

        async def wrapper():
            server = ZipkinServer(ServerConfig())

            def pushback(body, encoding=None):
                raise IngestBackpressure(
                    "every parse-worker queue is full (2 workers x depth 2)"
                )

            server.collector.accept_spans_bytes = pushback
            client = TestClient(TestServer(server.make_app()))
            await client.start_server()
            try:
                resp = await client.post(
                    "/api/v2/spans", data=post_trace_body(),
                    headers={"Content-Type": "application/json"},
                )
                assert resp.status == 429
                assert "queue is full" in await resp.text()
            finally:
                await client.close()

        asyncio.run(wrapper())
