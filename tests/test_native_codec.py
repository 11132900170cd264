"""Native C columnar parser: field parity against the Python codec, the
fallback contract, and the fast ingest path (SURVEY.md §7 hard-part 1)."""

import dataclasses
import json

import numpy as np
import pytest

from tests.fixtures import TODAY, TODAY_US, TRACE, lots_of_spans
from zipkin_tpu import native
from zipkin_tpu.model import json_v1, json_v2, proto3
from zipkin_tpu.tpu.columnar import KIND_TO_ID, Vocab, pack_parsed, pack_spans

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C toolchain for the native codec"
)


def parse(spans):
    data = json_v2.encode_span_list(spans)
    parsed = native.parse_spans(data)
    assert parsed is not None, "native parse refused a canonical payload"
    return data, parsed


class TestParseParity:
    def test_canonical_trace_fields(self):
        _, p = parse(TRACE)
        assert p.n == len(TRACE)
        for i, s in enumerate(TRACE):
            full = int(s.trace_id, 16)
            lo, hi = full & (2**64 - 1), full >> 64
            assert p.tl0[i] == lo & 0xFFFFFFFF and p.tl1[i] == lo >> 32
            assert p.th0[i] == hi & 0xFFFFFFFF and p.th1[i] == hi >> 32
            sid = int(s.id, 16)
            assert p.s0[i] == sid & 0xFFFFFFFF and p.s1[i] == sid >> 32
            pid = int(s.parent_id, 16) if s.parent_id else 0
            assert p.p0[i] == pid & 0xFFFFFFFF and p.p1[i] == pid >> 32
            assert p.kind[i] == KIND_TO_ID[s.kind]
            assert bool(p.shared[i]) == bool(s.shared)
            assert bool(p.err[i]) == s.is_error
            assert p.ts_us[i] == (s.timestamp or 0)
            assert p.dur_us[i] == (s.duration or 0)
            assert bool(p.has_dur[i]) == (s.duration is not None)

    def test_string_slices(self):
        data, p = parse(TRACE)
        for i, s in enumerate(TRACE):
            svc = bytes(data[p.svc_off[i] : p.svc_off[i] + p.svc_len[i]]).decode()
            assert svc == (s.local_service_name or "")
            name = bytes(data[p.name_off[i] : p.name_off[i] + p.name_len[i]]).decode()
            assert name == (s.name or "")

    def test_packed_columns_match_object_path(self):
        spans = lots_of_spans(1000, seed=13)
        data = json_v2.encode_span_list(spans)
        va, vb = Vocab(256, 1024), Vocab(256, 1024)
        cols_obj = pack_spans(spans, va, pad_to_multiple=256)
        parsed = native.parse_spans(data)
        cols_fast = pack_parsed(parsed, vb, pad_to_multiple=256)
        for field in cols_obj._fields:
            np.testing.assert_array_equal(
                getattr(cols_obj, field), getattr(cols_fast, field), err_msg=field
            )
        assert va.services._names == vb.services._names
        assert va._key_list == vb._key_list

    def test_whitespace_and_unknown_keys_ok(self):
        doc = json.dumps(
            [{
                "traceId": "000000000000000a", "id": "000000000000000b",
                "name": "x", "newField": {"nested": [1, 2, {"a": "b"}]},
                "timestamp": 5, "duration": 7,
                "localEndpoint": {"serviceName": "s", "ipv4": "1.2.3.4", "port": 80},
            }],
            indent=2,
        ).encode()
        p = native.parse_spans(doc)
        assert p is not None and p.n == 1
        assert p.dur_us[0] == 7 and p.has_dur[0]

    def test_escaped_strings_fall_back(self):
        doc = b'[{"traceId":"a","id":"b","name":"we\\"ird"}]'
        assert native.parse_spans(doc) is None  # python codec takes over

    def test_malformed_falls_back(self):
        assert native.parse_spans(b'[{"traceId": }]') is None
        assert native.parse_spans(b"{") is None
        assert native.parse_spans(b"[]").n == 0

    def test_huge_duration_clamps(self):
        doc = b'[{"traceId":"a","id":"b","duration":99999999999999}]'
        p = native.parse_spans(doc)
        assert p.n == 1 and p.dur_us[0] == 0xFFFFFFFF


class TestFastIngest:
    def test_fast_path_matches_object_path_aggregates(self):
        from zipkin_tpu.tpu.state import AggConfig
        from zipkin_tpu.tpu.store import TpuStorage

        cfg = AggConfig(max_services=64, max_keys=256, hll_precision=9,
                        digest_centroids=16, digest_buffer=4096,
                        ring_capacity=1 << 13)
        spans = lots_of_spans(3000, seed=14)
        data = json_v2.encode_span_list(spans)

        slow = TpuStorage(config=cfg, pad_to_multiple=256)
        slow.accept(spans).execute()
        fast = TpuStorage(config=cfg, pad_to_multiple=256)
        accepted, dropped = fast.ingest_json_fast(data)
        assert (accepted, dropped) == (len(spans), 0)

        end_ts, lookback = 2**40, 2**40 - 60_000
        want = sorted(
            (l.parent, l.child, l.call_count, l.error_count)
            for l in slow.get_dependencies(end_ts, lookback).execute())
        got = sorted(
            (l.parent, l.child, l.call_count, l.error_count)
            for l in fast.get_dependencies(end_ts, lookback).execute())
        assert got == want
        assert fast.ingest_counters()["spans"] == len(spans)
        h_slow, r_slow, _ = slow.agg.merged_sketches()
        h_fast, r_fast, _ = fast.agg.merged_sketches()
        np.testing.assert_array_equal(h_slow, h_fast)
        np.testing.assert_array_equal(r_slow, r_fast)

    @staticmethod
    def _collector(rate):
        from zipkin_tpu.collector.core import Collector, CollectorSampler
        from zipkin_tpu.collector.core import InMemoryCollectorMetrics
        from zipkin_tpu.tpu.state import AggConfig
        from zipkin_tpu.tpu.store import TpuStorage

        cfg = AggConfig(max_services=64, max_keys=256, hll_precision=9,
                        digest_centroids=16, digest_buffer=4096,
                        ring_capacity=1 << 13)
        store = TpuStorage(config=cfg, pad_to_multiple=256)
        metrics = InMemoryCollectorMetrics()
        collector = Collector(
            store, sampler=CollectorSampler(rate),
            metrics=metrics.for_transport("http"), fast_ingest=True,
        )
        return store, metrics, collector

    def test_collector_uses_fast_path_and_samples(self):
        from zipkin_tpu.collector.core import CollectorSampler

        _, metrics, collector = self._collector(0.2)
        spans = lots_of_spans(2000, seed=15)
        data = json_v2.encode_span_list(spans)
        accepted = collector.accept_spans_bytes(data)
        dropped = metrics.get("spans_dropped", "http")
        assert accepted + dropped == len(spans)
        assert 0 < accepted < len(spans)  # ~20% sampled in
        # sampling must agree exactly with the scalar sampler
        want = sum(1 for s in spans if CollectorSampler(0.2).test(s))
        assert accepted == want

    def test_payload_the_parser_refuses_is_sampled_too(self):
        """An escaped string sends the payload down the object path, which
        must sample like the fast path: at rate 0 nothing is ingested."""
        store, metrics, collector = self._collector(0.0)
        data = json_v2.encode_span_list(TRACE).replace(
            b"get /", b"get \\u002f")
        assert store.ingest_json_fast(data) is None  # the parser's bail-out
        assert collector.accept_spans_bytes(data) == 0
        assert metrics.get("spans", "http") == len(TRACE)
        assert metrics.get("spans_dropped", "http") == len(TRACE)
        assert store.ingest_counters()["spans"] == 0
        assert store.get_trace(TRACE[0].trace_id).execute() == []


class TestMixedPathCoherence:
    def test_object_then_fast_then_object_ids_stay_coherent(self):
        from zipkin_tpu.tpu.state import AggConfig
        from zipkin_tpu.tpu.store import TpuStorage

        cfg = AggConfig(max_services=64, max_keys=256, hll_precision=9,
                        digest_centroids=16, digest_buffer=4096,
                        ring_capacity=1 << 13)
        store = TpuStorage(config=cfg, pad_to_multiple=256)
        a = lots_of_spans(300, seed=31, services=3, span_names=4)
        b = lots_of_spans(300, seed=32, services=6, span_names=8)
        c = lots_of_spans(300, seed=33, services=9, span_names=12)
        store.accept(a).execute()                       # python interning
        store.ingest_json_fast(json_v2.encode_span_list(b))  # C interning
        store.accept(c).execute()                       # python again
        store.ingest_json_fast(json_v2.encode_span_list(a))  # C again

        # replaying everything through a fresh pure-python vocab must give
        # the identical id assignment (same first-seen order)
        ref = Vocab(64, 256)
        for spans in (a, b, c, a):
            pack_spans(spans, ref, pad_to_multiple=256)
        assert store.vocab.services._names == ref.services._names
        assert store.vocab.span_names._names == ref.span_names._names
        assert store.vocab._key_list == ref._key_list

        rows = store.latency_quantiles([0.5], use_digest=False)
        svcs = {r["serviceName"] for r in rows}
        assert {"svc00", "svc08"} <= svcs  # both paths' data queryable


# -- where the fast path decides the JSON dialect ---------------------------
#
# The native tokenizer tells v1 from v2 in its own pass (span_json.c,
# ERR_V1); nothing on the fast path scans the body for v1 markers. Each
# case goes through Collector.accept_spans_bytes with fast ingest, and must
# leave the store as the object path leaves it when fed the spans the
# payload's own dialect decodes to.

def _fast_store():
    from zipkin_tpu.tpu.state import AggConfig
    from zipkin_tpu.tpu.store import TpuStorage

    cfg = AggConfig(max_services=64, max_keys=256, hll_precision=9,
                    digest_centroids=16, digest_buffer=4096,
                    ring_capacity=1 << 13)
    # every trace archived, so the name lists see all of both paths' spans
    return TpuStorage(config=cfg, pad_to_multiple=256, fast_archive_sample=1)


def _v1_body(n, *, binary=(), endpoint_annotations=()):
    """n v1 spans in traces of three; the spans whose index is in
    ``binary`` carry a local-component binary annotation, those in
    ``endpoint_annotations`` server annotations with an endpoint, the rest
    neither (and so read alike in either dialect)."""
    out = []
    for i in range(n):
        ep = {"serviceName": f"svc{i % 3}", "ipv4": f"10.0.0.{i % 3 + 1}"}
        ts = TODAY_US + i * 1000
        span = {"traceId": f"{0x100 + i // 3:016x}", "id": f"{i + 1:016x}",
                "name": f"op{i % 4}", "timestamp": ts, "duration": 500 + i}
        if i % 3:
            span["parentId"] = f"{i:016x}"
        if i in endpoint_annotations:
            span["annotations"] = [
                {"timestamp": ts, "value": "sr", "endpoint": ep},
                {"timestamp": ts + 500 + i, "value": "ss", "endpoint": ep},
            ]
        if i in binary:
            span["binaryAnnotations"] = [
                {"key": "lc", "value": "", "endpoint": ep}]
        out.append(span)
    return json.dumps(out).encode()


def _v2_annotated(tags=None):
    from zipkin_tpu.model.span import Span

    return [
        Span.create(
            s.trace_id, s.id, parent_id=s.parent_id, kind=s.kind,
            name=s.name, timestamp=s.timestamp, duration=s.duration,
            local_endpoint=s.local_endpoint,
            remote_endpoint=s.remote_endpoint,
            annotations=[(s.timestamp + 1, "ws"), (s.timestamp + 2, "wr")],
            tags={**s.tags, **(tags or {})},
        )
        for s in lots_of_spans(240, seed=43)
    ]


def _proto3_0x5b():
    """A ListOfSpans whose first span is 0x5B ('[') bytes long."""
    from zipkin_tpu.model.span import Endpoint, Span

    base = dict(trace_id="000000000000000a", timestamp=1_000_000,
                duration=10, local_endpoint=Endpoint.create("svc"))
    for pad in range(1, 60):
        s = Span.create(id="000000000000000b", name="n" * pad, **base)
        if len(proto3.encode_span(s)) == 0x5B:
            return proto3.encode_span_list([s])
    raise AssertionError("could not synthesize an 0x5B-byte span")


def _v2_with_tag(key):
    spans = lots_of_spans(120, seed=47)
    spans[7] = dataclasses.replace(spans[7], tags={key: "x"})
    return json_v2.encode_span_list(spans)


def _v2(body):
    return body, json_v2.decode_span_list, False


def _v1(body):
    return body, json_v1.decode_v1_span_list, True


def _p3(body):
    return body, proto3.decode_span_list, False


#: case -> () -> (payload, the decoder of its own dialect, True for JSON v1)
_DIALECT_CASES = {
    "v2": lambda: _v2(json_v2.encode_span_list(lots_of_spans(300, seed=41))),
    "v2_annotations": lambda: _v2(json_v2.encode_span_list(_v2_annotated())),
    "v2_newline_led": lambda: _v2(
        b"\n" + json_v2.encode_span_list(lots_of_spans(90, seed=44)) + b"\n"),
    "v2_space_led": lambda: _v2(
        b"  \r\n" + json_v2.encode_span_list(lots_of_spans(90, seed=45))),
    # test_codecs' 0x0A-led JSON: no proto3 frame, so it is the tokenizer's
    "v2_newline_ambiguity": lambda: _v2(b'\n  [{"traceId":"a","id":"b"}]\n'),
    "v1_binary_annotations": lambda: _v1(_v1_body(12, binary=range(12))),
    "v1_endpoint_annotations_first": lambda: _v1(
        _v1_body(12, endpoint_annotations={0})),
    "v1_endpoint_annotations_last": lambda: _v1(
        _v1_body(12, endpoint_annotations={11})),
    "v1_canonical": lambda: _v1(json_v1.encode_v1_span_list(TRACE)),
    "proto3": lambda: _p3(
        proto3.encode_span_list(lots_of_spans(300, seed=46))),
    "proto3_first_span_0x5b": lambda: _p3(_proto3_0x5b()),
    # the two bodies the old substring sniff took for v1: they are v2
    "v2_tag_key_binaryAnnotations": lambda: _v2(
        _v2_with_tag("binaryAnnotations")),
    "v2_annotations_and_tag_key_endpoint": lambda: _v2(
        json_v2.encode_span_list(_v2_annotated(tags={"endpoint": "/api"}))),
}


def _store_view(store, window=(TODAY + 86_400_000, 30 * 86_400_000)):
    svcs = store.get_service_names().execute()
    return {
        "services": svcs,
        "span_names": {s: store.get_span_names(s).execute() for s in svcs},
        "links": sorted(
            (l.parent, l.child, l.call_count, l.error_count)
            for l in store.get_dependencies(*window).execute()),
        "spans": store.ingest_counters()["spans"],
    }


@pytest.mark.parametrize("case", list(_DIALECT_CASES))
def test_fast_path_dialect(case, monkeypatch):
    from zipkin_tpu.collector.core import Collector, CollectorSampler
    from zipkin_tpu.collector.core import InMemoryCollectorMetrics

    data, decode, is_v1 = _DIALECT_CASES[case]()
    spans = decode(data)
    assert spans
    # every dependency read fresh: the stores are read twice, seconds apart
    monkeypatch.setenv("TPU_DEPS_MAX_STALE_MS", "0")
    fast, ref = _fast_store(), _fast_store()
    handed = []
    native_fast = fast.ingest_json_fast

    def spy(body, sampler=None):
        handed.append(native_fast(body, sampler))
        return handed[-1]

    fast.ingest_json_fast = spy
    collector = Collector(
        fast, sampler=CollectorSampler(1.0),
        metrics=InMemoryCollectorMetrics().for_transport("http"),
        fast_ingest=True,
    )
    assert collector.accept_spans_bytes(data) == len(spans)
    assert len(handed) == 1 and (handed[0] is None) == is_v1
    ref.accept(spans).execute()
    assert _store_view(fast) == _store_view(ref)
    assert fast.ingest_counters()["fastIngestDialectFallbacks"] == int(is_v1)

    # the payload after it interns in step, whichever path took the first
    follow = lots_of_spans(200, seed=48, services=12, span_names=9)
    assert collector.accept_spans_bytes(
        json_v2.encode_span_list(follow)) == len(follow)
    ref.accept(follow).execute()
    assert _store_view(fast) == _store_view(ref)
    v = fast.vocab
    assert fast._nvocab.counts() == (
        len(v.services) - 1, len(v.span_names) - 1, v.num_keys - 1)
    assert v.services._names == ref.vocab.services._names
    assert v.span_names._names == ref.vocab.span_names._names
    assert v._key_list == ref.vocab._key_list
    assert fast.ingest_counters()["fastIngestDialectFallbacks"] == int(is_v1)


def test_fast_path_never_sniffs_the_body(monkeypatch):
    """A v2 POST through the fast path calls no body-wide v1 sniff: with
    the sniff made to raise, the payload is still accepted there."""
    from zipkin_tpu.collector.core import Collector, CollectorSampler
    from zipkin_tpu.collector.core import InMemoryCollectorMetrics
    from zipkin_tpu.model import codec

    def sniff(data):
        raise AssertionError("the fast path scanned the body for v1")

    monkeypatch.setattr(codec, "_looks_like_v1_json", sniff)
    store = _fast_store()
    collector = Collector(
        store, sampler=CollectorSampler(1.0),
        metrics=InMemoryCollectorMetrics().for_transport("http"),
        fast_ingest=True,
    )
    spans = lots_of_spans(300, seed=49)
    assert collector.accept_spans_bytes(
        json_v2.encode_span_list(spans)) == len(spans)
    assert store.ingest_counters()["spans"] == len(spans)
