"""Time-bucketed retention: links must survive ring eviction, and
percentile queries must be windowable.

The reference's retention story is daily ES indices and the daily
cassandra ``dependency`` table written by the zipkin-dependencies job
(SURVEY.md §2.3, §3.5); the TPU analog is the rollup program
(zipkin_tpu.tpu.ingest.rollup_step) that links the about-to-be-evicted
half-ring into per-time-bucket matrices, plus time-sliced histograms for
windowed percentiles. These tests force heavy ring eviction with tiny
rings and assert parity against the in-memory oracle, which retains
everything.
"""

from __future__ import annotations

import numpy as np
import pytest

from tests.fixtures import TODAY_US, lots_of_spans
from zipkin_tpu.model.span import Endpoint, Kind, Span
from zipkin_tpu.parallel.mesh import make_mesh
from zipkin_tpu.storage.memory import InMemoryStorage
from zipkin_tpu.tpu.state import AggConfig
from zipkin_tpu.tpu.store import TpuStorage

DAY_MS = 86_400_000
WIDE_LOOKBACK = 1000 * DAY_MS

SMALL = AggConfig(
    max_services=32, max_keys=128, hll_precision=8, digest_centroids=16,
    digest_buffer=2048, ring_capacity=1024,
    link_buckets=8, bucket_minutes=60, hist_slices=4, hist_slice_minutes=60,
)


def link_set(storage, end_ts, lookback):
    return sorted(
        (l.parent, l.child, l.call_count, l.error_count)
        for l in storage.get_dependencies(end_ts, lookback).execute()
    )


def drive(store, oracle, spans, chunk=1000):
    for lo in range(0, len(spans), chunk):
        store.accept(spans[lo : lo + chunk]).execute()
        if oracle is not None:
            oracle.accept(spans[lo : lo + chunk]).execute()


class TestLinksSurviveEviction:
    def test_exact_parity_through_heavy_eviction_8shards(self):
        """20k spans through a 1024-slot/shard ring: most of the corpus is
        evicted; dependency counts must still match the oracle exactly."""
        store = TpuStorage(config=SMALL, mesh=make_mesh(8), pad_to_multiple=256)
        oracle = InMemoryStorage(max_span_count=500_000)
        spans = lots_of_spans(20_000, seed=11, services=6, span_names=10)
        drive(store, oracle, spans)
        end_ts = max(s.timestamp for s in spans if s.timestamp) // 1000 + 3_600_000
        assert link_set(store, end_ts, WIDE_LOOKBACK) == link_set(
            oracle, end_ts, WIDE_LOOKBACK
        )

    def test_links_survive_total_ring_wrap_single_shard(self):
        """Ingest >> ring capacity on ONE shard, then verify the early
        traces' links are still answered (from rollups, not the ring)."""
        store = TpuStorage(config=SMALL, mesh=make_mesh(1), pad_to_multiple=256)
        oracle = InMemoryStorage(max_span_count=500_000)
        spans = lots_of_spans(6_000, seed=4, services=4, span_names=6)
        drive(store, oracle, spans, chunk=500)
        # the single-shard ring holds 1024 spans; 6000 went through
        live = int(np.asarray(store.agg.state.r_valid).sum())
        assert live <= SMALL.ring_capacity
        end_ts = max(s.timestamp for s in spans if s.timestamp) // 1000 + 3_600_000
        got = link_set(store, end_ts, WIDE_LOOKBACK)
        want = link_set(oracle, end_ts, WIDE_LOOKBACK)
        assert got == want
        total_calls = sum(c for _, _, c, _ in got)
        assert total_calls > SMALL.ring_capacity  # provably beyond the ring

    @pytest.mark.xfail(
        strict=True,
        reason="known gap (PR 22, ROADMAP 'link exactness across "
        "eviction'): a rolled lane may be overwritten while a tree "
        "neighbour is still unrolled, and that neighbour then links "
        "without it",
    )
    @pytest.mark.parametrize(
        "order",
        [(0, 1, 2), (1, 0, 2, 3)],
        ids=["rolled-region-edge-cuts-a-trace", "child-batch-before-parent-batch"],
    )
    def test_links_exact_when_a_relative_is_evicted_first(self, order):
        """Batches of exactly rollup_segment lanes, every batch boundary
        between a parent and its child. In arrival order the parent is
        rolled (correctly silent: it has a child) and then overwritten by
        a full-segment write before the child is rolled, so the child's
        rule-6b backfill is lost; with the child's batch first, the child
        is rolled and overwritten and the parent later counts as a leaf.
        chip_smoke.py met both through the MP tier at the default size
        (one edge off by one in about every second run); it now applies
        one POST at a time, which at its sizes never overwrites a rolled
        lane that an unrolled one still needs."""
        seg = SMALL.rollup_segment
        ep = [Endpoint.create(f"s{i}", f"10.0.0.{i + 1}") for i in range(3)]

        def client(tid, sid, parent, a, b):
            return Span.create(
                trace_id=f"{tid:016x}", id=f"{sid:016x}",
                parent_id=None if parent is None else f"{parent:016x}",
                kind=Kind.CLIENT, name="op", local_endpoint=ep[a],
                remote_endpoint=ep[b], timestamp=TODAY_US + tid, duration=10,
            )

        # one lone span, then parent/child pairs: every even lane
        # boundary, so every batch boundary, cuts a pair
        spans = [client(1, 1, None, 0, 1)]
        for t in range(2, seg * len(order)):
            spans.append(client(t, t << 4 | 1, None, 0, 1))
            spans.append(client(t, t << 4 | 2, t << 4 | 1, 1, 2))
        spans = spans[: seg * len(order)]
        batches = [spans[i : i + seg] for i in range(0, len(spans), seg)]
        store = TpuStorage(config=SMALL, mesh=make_mesh(1), pad_to_multiple=256)
        oracle = InMemoryStorage(max_span_count=500_000)
        oracle.accept(spans).execute()
        for i in order:
            store.accept(batches[i]).execute()
        end_ts = TODAY_US // 1000 + 3_600_000
        assert link_set(store, end_ts, WIDE_LOOKBACK) == link_set(
            oracle, end_ts, WIDE_LOOKBACK
        )


def _two_hour_spans():
    """Trace pairs in two distinct hours with distinct duration scales."""
    ep = Endpoint.create("svc-a", "10.0.0.1")
    spans = []
    hour0 = (TODAY_US // 3_600_000_000) * 3_600_000_000
    for i in range(200):
        spans.append(
            Span.create(
                trace_id=f"{(i + 1):016x}", id=f"{(i + 1):016x}",
                kind=None, name="op", local_endpoint=ep,
                timestamp=hour0 + i * 1000, duration=1000 + i,
            )
        )
    hour1 = hour0 + 3_600_000_000
    for i in range(200):
        spans.append(
            Span.create(
                trace_id=f"{(i + 1001):016x}", id=f"{(i + 1001):016x}",
                kind=None, name="op", local_endpoint=ep,
                timestamp=hour1 + i * 1000, duration=50_000 + i * 10,
            )
        )
    return spans, hour0, hour1


class TestWindowedPercentiles:
    @pytest.fixture(scope="class")
    def loaded(self):
        store = TpuStorage(config=SMALL, mesh=make_mesh(1), pad_to_multiple=256)
        spans, hour0, hour1 = _two_hour_spans()
        drive(store, None, spans, chunk=100)
        return store, hour0, hour1

    def test_window_selects_one_hour(self, loaded):
        store, hour0, hour1 = loaded
        # window covering ONLY the first hour: p50 ~ 1100, not ~51000
        end_ts = (hour0 + 3_599_000_000) // 1000
        rows = store.latency_quantiles([0.5], end_ts=end_ts, lookback=3_600_000)
        assert len(rows) == 1
        assert rows[0]["count"] == 200
        assert 1000 <= rows[0]["quantiles"][0.5] <= 1250

        # second hour only: the slow population. Window granularity is
        # whole slices, so keep the window strictly inside hour1 (a 1ms
        # underhang would pull in all of hour0's slice — the same
        # whole-day granularity the reference's daily indices give).
        end_ts2 = (hour1 + 3_599_000_000) // 1000
        rows2 = store.latency_quantiles([0.5], end_ts=end_ts2, lookback=3_500_000)
        assert rows2[0]["count"] == 200
        assert 48_000 <= rows2[0]["quantiles"][0.5] <= 56_000

    def test_window_spanning_both_hours_merges(self, loaded):
        store, hour0, hour1 = loaded
        end_ts = (hour1 + 3_599_000_000) // 1000
        rows = store.latency_quantiles([0.5], end_ts=end_ts, lookback=2 * 3_600_000)
        assert rows[0]["count"] == 400

    def test_alltime_path_unchanged(self, loaded):
        store, _, _ = loaded
        rows = store.latency_quantiles([0.5], use_digest=False)
        assert rows[0]["count"] == 400

    def test_digest_quantiles_flush_on_read_is_invisible(self, loaded):
        """r3: a digest read flushes the pending buffer opportunistically
        (QUERY_SLO r3: the pend-fold read variant cost the full
        compaction on EVERY query without advancing state) — the flush
        must be query-invisible: same answers, caches still valid."""
        store, _, _ = loaded
        assert store.agg._pend_lanes > 0
        v0 = store.agg.write_version
        first = store.latency_quantiles([0.5, 0.99])
        # the read flushed opportunistically...
        assert store.agg._pend_lanes == 0
        # ...without bumping write_version (flush changes no answer, so
        # cached reads and the link context stay valid)
        assert store.agg.write_version == v0
        store.agg.flush_now()  # an extra explicit flush: still a no-op
        store.invalidate_read_cache()
        assert store.latency_quantiles([0.5, 0.99]) == first

    def test_window_before_retention_is_empty(self, loaded):
        store, hour0, _ = loaded
        # a window 100 days before any data: no rows
        end_ts = hour0 // 1000 - 100 * DAY_MS
        rows = store.latency_quantiles([0.5], end_ts=end_ts, lookback=3_600_000)
        assert rows == []


class TestRollupSlotRecycling:
    def test_old_buckets_age_out_of_link_queries(self):
        """More distinct hours than link_buckets: the oldest hour's links
        are recycled away; recent hours stay queryable; a window over only
        recent hours excludes older ones."""
        cfg = AggConfig(
            max_services=16, max_keys=64, hll_precision=8, digest_centroids=16,
            digest_buffer=2048, ring_capacity=256,  # tiny: force rollups
            link_buckets=4, bucket_minutes=60, hist_slices=4,
            hist_slice_minutes=60,
        )
        store = TpuStorage(config=cfg, mesh=make_mesh(1), pad_to_multiple=128)
        parent_ep = Endpoint.create("parent-svc", "10.0.0.1")
        child_ep = Endpoint.create("child-svc", "10.0.0.2")
        hour0 = (TODAY_US // 3_600_000_000) * 3_600_000_000
        hours = 6  # > link_buckets
        per_hour = 300  # >> ring: forces eviction into rollups each hour
        for h in range(hours):
            spans = []
            for i in range(per_hour):
                tid = f"{(h * per_hour + i + 1):016x}"
                ts = hour0 + h * 3_600_000_000 + i * 1000
                spans.append(
                    Span.create(
                        trace_id=tid, id=tid, kind="CLIENT", name="call",
                        local_endpoint=parent_ep, remote_endpoint=child_ep,
                        timestamp=ts, duration=500,
                    )
                )
            drive(store, None, spans, chunk=100)
        store.agg.rollup_now()  # flush the live tail into buckets too

        end_ts = (hour0 + hours * 3_600_000_000) // 1000
        # whole range: only the last link_buckets hours can answer
        links = store.get_dependencies(end_ts, hours * 3_600_000).execute()
        assert len(links) == 1
        total = links[0].call_count
        assert total <= cfg.link_buckets * per_hour
        assert total >= (cfg.link_buckets - 1) * per_hour

        # a window over just the last two hours
        links2 = store.get_dependencies(end_ts, 2 * 3_600_000).execute()
        assert links2 and links2[0].call_count <= 2 * per_hour

    def test_rollup_is_idempotent_per_span(self):
        """Repeated rollup_now() calls must not double-count links."""
        store = TpuStorage(config=SMALL, mesh=make_mesh(1), pad_to_multiple=256)
        spans = lots_of_spans(500, seed=9, services=4, span_names=4)
        drive(store, None, spans)
        end_ts = max(s.timestamp for s in spans if s.timestamp) // 1000 + 3_600_000
        before = link_set(store, end_ts, WIDE_LOOKBACK)
        store.agg.rollup_now()
        store.agg.rollup_now()
        store.agg.rollup_now()
        # rollup_now bumps the aggregator write_version, so this read
        # recomputes on device rather than serving the cached result
        assert link_set(store, end_ts, WIDE_LOOKBACK) == before
