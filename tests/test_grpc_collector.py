"""gRPC collector E2E: Report spans over a real channel, read them back
(mirrors ITZipkinGrpcCollector, SURVEY.md §2.4)."""

import asyncio

import grpc
import grpc.aio
import pytest

from tests.fixtures import TRACE
from zipkin_tpu.collector.core import Collector
from zipkin_tpu.model import proto3
from zipkin_tpu.server.grpc import METHOD, GrpcCollectorServer
from zipkin_tpu.storage.memory import InMemoryStorage


def test_report_roundtrip():
    async def scenario():
        storage = InMemoryStorage()
        server = GrpcCollectorServer(Collector(storage), host="127.0.0.1", port=0)
        await server.start()
        try:
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                method = ch.unary_unary(METHOD)
                body = proto3.encode_span_list(TRACE)
                resp = await method(body)
                assert resp == b""
            trace = storage.get_trace(TRACE[0].trace_id).execute()
            assert len(trace) == len(TRACE)
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_report_malformed_invalid_argument():
    async def scenario():
        storage = InMemoryStorage()
        server = GrpcCollectorServer(Collector(storage), host="127.0.0.1", port=0)
        await server.start()
        try:
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                method = ch.unary_unary(METHOD)
                with pytest.raises(grpc.aio.AioRpcError) as err:
                    await method(b"\xff\xff\xff")
                assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_unknown_method_unimplemented():
    async def scenario():
        storage = InMemoryStorage()
        server = GrpcCollectorServer(Collector(storage), host="127.0.0.1", port=0)
        await server.start()
        try:
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                method = ch.unary_unary("/zipkin.proto3.SpanService/Nope")
                with pytest.raises(grpc.aio.AioRpcError) as err:
                    await method(b"")
                assert err.value.code() == grpc.StatusCode.UNIMPLEMENTED
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_server_config_enables_grpc():
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig

    async def scenario():
        server = ZipkinServer(
            ServerConfig(
                port=0, grpc_collector_enabled=True, grpc_port=0,
            ),
            storage=InMemoryStorage(),
        )
        await server.start()
        try:
            gport = server._grpc.port
            async with grpc.aio.insecure_channel(f"127.0.0.1:{gport}") as ch:
                await ch.unary_unary(METHOD)(proto3.encode_span_list(TRACE))
            trace = server.storage.get_trace(TRACE[0].trace_id).execute()
            assert len(trace) == len(TRACE)
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_server_grpc_collector_gets_fast_ingest():
    """The gRPC tier's Collector must carry the fast-ingest flag: without
    it proto3 Report payloads decode on the Python object path (~15k
    spans/s measured) while HTTP rides the native parser (an r5
    finding)."""
    import asyncio as _asyncio

    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig

    class _FastStorage(InMemoryStorage):
        def ingest_json_fast(self, data, sampler):  # pragma: no cover
            raise NotImplementedError

    async def scenario():
        server = ZipkinServer(
            ServerConfig(
                storage_type="mem", port=0, tpu_fast_ingest=True,
                grpc_collector_enabled=True, grpc_port=0,
            ),
            storage=_FastStorage(),
        )
        await server.start()
        try:
            assert server.collector.fast_ingest  # HTTP tier (sanity)
            assert server._grpc._collector.fast_ingest  # gRPC tier
        finally:
            await server.stop()

    _asyncio.run(scenario())


def test_report_backpressure_maps_to_resource_exhausted():
    """The fan-out tier's IngestBackpressure must surface as the gRPC
    twin of HTTP 429 — RESOURCE_EXHAUSTED, the code grpc clients treat
    as retry-after-backoff — not as an INTERNAL failure."""
    from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

    class PushbackCollector(Collector):
        def accept_spans_bytes(self, data, encoding=None):
            raise IngestBackpressure("every parse-worker queue is full")

    async def scenario():
        server = GrpcCollectorServer(
            PushbackCollector(InMemoryStorage()), host="127.0.0.1", port=0
        )
        await server.start()
        try:
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                with pytest.raises(grpc.aio.AioRpcError) as err:
                    await ch.unary_unary(METHOD)(proto3.encode_span_list(TRACE))
                assert err.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_report_records_grpc_boundary_stage():
    """Report must time its boundary under the obs catalogue's
    grpc_boundary stage — parity with the HTTP tier's http_boundary."""
    from zipkin_tpu import obs

    async def scenario():
        storage = InMemoryStorage()
        server = GrpcCollectorServer(Collector(storage), host="127.0.0.1", port=0)
        await server.start()
        try:
            before = obs.RECORDER.snapshot().stage("grpc_boundary").count
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                assert await ch.unary_unary(METHOD)(
                    proto3.encode_span_list(TRACE)
                ) == b""
            after = obs.RECORDER.snapshot().stage("grpc_boundary").count
            assert after == before + 1
        finally:
            await server.stop()

    asyncio.run(scenario())


def test_report_b3_metadata_links_slow_dispatch_spans():
    """B3 propagation parity with the HTTP middleware: x-b3-* request
    metadata must be visible as CURRENT_B3 for the duration of the
    accept (so slow-dispatch self-spans link to the caller's trace),
    and x-b3-sampled: 0 must suppress the linkage per the B3 spec."""
    from zipkin_tpu.obs.selfspans import CURRENT_B3

    seen = []

    class CapturingCollector(Collector):
        def accept_spans_bytes(self, data, encoding=None):
            seen.append(CURRENT_B3.get())
            return super().accept_spans_bytes(data, encoding)

    async def scenario():
        storage = InMemoryStorage()
        server = GrpcCollectorServer(
            CapturingCollector(storage), host="127.0.0.1", port=0
        )
        await server.start()
        try:
            async with grpc.aio.insecure_channel(f"127.0.0.1:{server.port}") as ch:
                method = ch.unary_unary(METHOD)
                body = proto3.encode_span_list(TRACE)
                await method(
                    body,
                    metadata=(
                        ("x-b3-traceid", "cafecafecafecafe"),
                        ("x-b3-spanid", "beefbeefbeefbeef"),
                        ("x-b3-sampled", "1"),
                    ),
                )
                await method(
                    body,
                    metadata=(
                        ("x-b3-traceid", "cafecafecafecafe"),
                        ("x-b3-spanid", "beefbeefbeefbeef"),
                        ("x-b3-sampled", "0"),
                    ),
                )
                await method(body)  # no metadata at all
        finally:
            await server.stop()

    asyncio.run(scenario())
    assert seen == [("cafecafecafecafe", "beefbeefbeefbeef"), None, None]
