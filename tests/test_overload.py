"""Overload control plane (ISSUE 13): brownout ladder, value-class
admission, deadline propagation, backoff guidance, and the resource
fault family.

Three tiers of coverage:

- controller unit tests drive ``OverloadController.evaluate`` with
  synthetic counter ticks (the testable core — no server, no device);
- boundary tests run the real aiohttp server: deadline headers, 429
  Retry-After guidance, B3 admission by value class;
- the sustained-flood test pushes >= 3x the mp tier's queue capacity
  through the real HTTP boundary with injected device-feed latency AND
  a WAL ENOSPC mid-flood, then proves zero acked loss at durable
  parity (WAL/checkpoint replay matches every 202-acked span) and B0
  recovery within one long SLO window of the flood ending.

ENOSPC recovery is exercised per-site (WAL append, snapshot commit,
archive write) with the test_wal parity oracle: degraded-mode entry +
durability page + crash-free recovery to bit-identical state.
"""

from __future__ import annotations

import asyncio
import time
import types

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.fixtures import TODAY_US
from tests.test_wal import CFG, assert_query_parity, batches, make
from zipkin_tpu import faults
from zipkin_tpu.model import json_v2
from zipkin_tpu.model.span import Endpoint, Span
from zipkin_tpu.obs.recorder import StageRecorder
from zipkin_tpu.obs.slo import SloWatchdog, default_specs
from zipkin_tpu.obs.windows import WindowedTelemetry
from zipkin_tpu.runtime.overload import (
    B0, B1, B2, B3, CLASS_BULK, CLASS_ERROR, OverloadController,
)
from zipkin_tpu.server.app import ZipkinServer
from zipkin_tpu.server.config import ServerConfig
from zipkin_tpu.storage.tpu import TpuStorage

DAY_MS = 86_400_000

# queue_saturation has a 0.9 design limit: a gauge of 0.9 is pressure
# 1.0, clearing every enter threshold
SATURATED = {"critpathQueueSaturation": 0.9}
CALM = {"critpathQueueSaturation": 0.0}


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


def ctl_with(**kw):
    kw.setdefault("seed", 7)
    kw.setdefault("hbm_stats", lambda: {})  # keep device gauges out
    return OverloadController(**kw)


def drive_to(ctl, level):
    """Saturate until the ladder reaches ``level`` (EMA needs a few
    ticks to converge on the raw signal)."""
    for _ in range(12):
        if ctl.evaluate(SATURATED) >= level:
            return
    raise AssertionError(f"never reached B{level}: load={ctl.load_index}")


def bulk_payload(i, per=40):
    """One payload of value-class BULK spans: unique trace id per
    payload, and no b"error" byte anywhere in the serialized form."""
    tid = f"{0xB000_0000 + i:016x}"
    ep = Endpoint.create(service_name=f"svc{i % 8:02d}", ip="10.0.0.9")
    spans = [
        Span.create(
            trace_id=tid, id=f"{(i << 16) + j + 1:016x}",
            name=f"op{j % 6:02d}", timestamp=TODAY_US + i * 1000 + j,
            duration=1000 + j, local_endpoint=ep,
        )
        for j in range(per)
    ]
    body = json_v2.encode_span_list(spans)
    assert b"error" not in body
    return body


def error_payload(i, per=4):
    """Essential-class payload: carries the literal "error" tag."""
    tid = f"{0xE000_0000 + i:016x}"
    ep = Endpoint.create(service_name="svc-err", ip="10.0.0.8")
    spans = [
        Span.create(
            trace_id=tid, id=f"{(i << 16) + j + 1:016x}",
            name="boom", timestamp=TODAY_US + j, duration=500,
            local_endpoint=ep, tags={"error": "true"},
        )
        for j in range(per)
    ]
    return json_v2.encode_span_list(spans)


# -- ladder unit tests ---------------------------------------------------


class TestLadder:
    def test_step_up_is_immediate_and_jumps(self):
        ctl = ctl_with(ema_alpha=1.0)  # no smoothing: load == raw
        assert ctl.level == B0
        assert ctl.evaluate(SATURATED) == B3  # B0 -> B3 in one tick
        assert ctl.transitions == 1
        assert ctl.level_name == "B3"

    def test_exit_margin_holds_level_below_enter_threshold(self):
        ctl = ctl_with(ema_alpha=1.0, dwell_ticks=3)
        drive_to(ctl, B3)
        # load just under the B3 enter threshold but above its exit
        # threshold (0.95 - 0.10): dwell long expired, still no descent
        hold = {"critpathQueueSaturation": 0.90 * 0.9}
        for _ in range(10):
            assert ctl.evaluate(hold) == B3  # hysteresis holds the level

    def test_step_down_is_one_level_per_dwell_window(self):
        ctl = ctl_with(ema_alpha=1.0, dwell_ticks=3)
        drive_to(ctl, B3)
        # each transition resets the dwell clock: exactly dwell_ticks
        # calm ticks per level on the way down, no level skipped
        levels = [ctl.evaluate(CALM) for _ in range(9)]
        assert levels == [B3, B3, B2, B2, B2, B1, B1, B1, B0]

    def test_transition_history_and_callbacks(self):
        seen = []
        ctl = ctl_with(ema_alpha=1.0, dwell_ticks=1)
        ctl.on_transition.append(seen.append)
        ctl.evaluate(SATURATED)
        for _ in range(10):
            ctl.evaluate(CALM)
        assert ctl.level == B0
        assert [e["to"] for e in seen] == ["B3", "B2", "B1", "B0"]
        assert all(e["topSignal"] == "queue_saturation" for e in seen[:1])
        assert list(ctl.history) == seen
        assert ctl.counters()["overloadTransitions"] == 4

    def test_ema_smooths_single_tick_noise(self):
        ctl = ctl_with(ema_alpha=0.3)
        # one saturated tick among calm ones must not reach B1
        ctl.evaluate(SATURATED)
        assert ctl.level == B0
        for _ in range(5):
            ctl.evaluate(CALM)
        assert ctl.level == B0

    def test_status_shape(self):
        ctl = ctl_with(ema_alpha=1.0)
        ctl.evaluate(SATURATED)
        st = ctl.status()
        assert st["levelName"] == "B3"
        assert st["readMode"] == "cache_only"
        assert st["topSignal"] == "queue_saturation"
        assert st["counters"]["transitions"] == 1
        assert st["enterThresholds"] == [0.70, 0.85, 0.95]
        assert st["history"][0]["from"] == "B0"


# -- admission unit tests ------------------------------------------------


class TestAdmission:
    def test_b0_admits_everything(self):
        ctl = ctl_with()
        for i in range(5):
            admitted, _ = ctl.admit_ingest(bulk_payload(i, per=2))
            assert admitted
        assert ctl.counters()["overloadAdmitted"] == 5
        assert ctl.counters()["overloadShedTotal"] == 0

    def test_classify_probes_unparsed_bytes(self):
        assert OverloadController.classify(error_payload(0)) == CLASS_ERROR
        assert OverloadController.classify(bulk_payload(0, per=2)) == CLASS_BULK

    def test_b3_admits_error_class_only(self):
        ctl = ctl_with(ema_alpha=1.0)
        drive_to(ctl, B3)
        admitted, cls = ctl.admit_ingest(error_payload(1))
        assert admitted and cls == CLASS_ERROR
        admitted, cls = ctl.admit_ingest(bulk_payload(1, per=2))
        assert not admitted and cls == CLASS_BULK
        c = ctl.counters()
        assert c["overloadAdmittedEssential"] == 1
        assert c["overloadShedBulk"] == 1

    def test_b2_fractional_credit_tracks_admit_rate_exactly(self):
        # park the load exactly halfway between the B2 and B3 enter
        # thresholds: bulk admit p = 0.5, so the credit scheduler must
        # admit exactly every 2nd bulk payload — no coin-flip variance
        ctl = ctl_with(ema_alpha=1.0)
        mid = (0.85 + 0.95) / 2.0
        ctl.evaluate({"critpathQueueSaturation": mid * 0.9})
        assert ctl.level == B2
        assert abs(ctl.status()["bulkAdmitP"] - 0.5) < 1e-6
        verdicts = [ctl.admit_ingest(bulk_payload(i, per=2))[0]
                    for i in range(10)]
        assert sum(verdicts) == 5
        # errors ride through untouched at B2
        assert ctl.admit_ingest(error_payload(2))[0]

    def test_bulk_shed_nudges_sampling_pressure_hook(self):
        rc = types.SimpleNamespace(calls=0)
        rc.note_pressure = lambda: setattr(rc, "calls", rc.calls + 1)
        ctl = ctl_with(ema_alpha=1.0, rate_controller=rc)
        drive_to(ctl, B3)
        for i in range(3):
            ctl.admit_ingest(bulk_payload(i, per=2))
        assert rc.calls == 3

    def test_retry_after_grows_with_pressure_and_stays_bounded(self):
        calm = ctl_with(seed=3)
        hot = ctl_with(seed=3, ema_alpha=1.0)
        drive_to(hot, B3)
        calm_mean = sum(calm.retry_after_s() for _ in range(50)) / 50
        hot_mean = sum(hot.retry_after_s() for _ in range(50)) / 50
        assert hot_mean > calm_mean * 3
        for _ in range(50):
            assert 0.05 <= hot.retry_after_s() <= 30.0
        # jitter decorrelates: not all draws identical
        assert len({round(hot.retry_after_s(), 6) for _ in range(20)}) > 1

    def test_deadline_counter(self):
        ctl = ctl_with()
        ctl.note_deadline_expired()
        ctl.note_deadline_expired(2)
        assert ctl.counters()["deadlineExpired"] == 3


# -- brownout read modes over the device read cache ----------------------


class _FakeCtl:
    def __init__(self, mode="normal", max_stale_ms=60_000):
        self.mode = mode
        self.max_stale_ms = max_stale_ms

    def read_mode(self):
        return self.mode


class TestBrownoutReads:
    def test_cache_first_serves_version_stale_within_bound(self, tmp_path):
        store = make(tmp_path, wal=False, checkpoint=False)
        calls = []
        compute = lambda: calls.append(1) or len(calls)  # noqa: E731
        assert store._cached_read("k", compute) == 1
        assert store._cached_read("k", compute) == 1  # plain hit
        store.agg.write_version += 1
        # normal mode: version advance drops the cache, recompute
        assert store._cached_read("k", compute) == 2
        # brownout: a version-stale entry within the bound still serves
        store.overload = _FakeCtl("cache_first")
        store.agg.write_version += 1
        assert store._cached_read("k", compute) == 2
        assert store.ingest_counters()["readCacheStaleServes"] == 1
        # beyond the staleness bound the device pull happens anyway
        store.overload.max_stale_ms = 0
        time.sleep(0.002)
        assert store._cached_read("k", compute) == 3
        store.close()

    def test_cache_only_serves_any_hit_but_computes_cold_keys(self, tmp_path):
        store = make(tmp_path, wal=False, checkpoint=False)
        calls = []
        compute = lambda: calls.append(1) or len(calls)  # noqa: E731
        store._cached_read("k", compute)
        store.overload = _FakeCtl("cache_only", max_stale_ms=0)
        store.agg.write_version += 5
        time.sleep(0.002)
        assert store._cached_read("k", compute) == 1  # arbitrarily stale
        # a cold key still computes: a brownout must not become an
        # outage for first-touch queries
        assert store._cached_read("k2", compute) == 2
        # recovery: the first normal-mode read purges stale entries
        store.overload = _FakeCtl("normal")
        assert store._cached_read("k", compute) == 3
        store.close()


# -- deadline propagation through the HTTP boundary ----------------------


def run_server(scenario, config=None, storage=None):
    async def wrapper():
        server = ZipkinServer(config or ServerConfig(), storage=storage)
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            await scenario(client, server)
        finally:
            await client.close()

    asyncio.run(wrapper())


class TestDeadlinePropagation:
    def test_expired_budget_dropped_before_dispatch(self):
        async def scenario(client, server):
            # zero budget: expired by the time the handler checks it
            resp = await client.post(
                "/api/v2/spans", data=bulk_payload(0, per=2),
                headers={"Content-Type": "application/json",
                         "X-Request-Timeout-Ms": "0"},
            )
            assert resp.status == 504
            assert resp.headers["X-Deadline-Expired"] == "1"
            resp = await client.get(
                "/api/v2/traces", headers={"X-Request-Timeout-Ms": "0"}
            )
            assert resp.status == 504
            # generous budget: normal service
            resp = await client.post(
                "/api/v2/spans", data=bulk_payload(1, per=2),
                headers={"Content-Type": "application/json",
                         "X-Request-Timeout-Ms": "60000"},
            )
            assert resp.status == 202
            metrics = await (await client.get("/metrics")).json()
            assert metrics["gauge.zipkin_tpu.deadlineExpired"] >= 2

        run_server(scenario)

    def test_malformed_and_absent_headers_mean_no_deadline(self):
        async def scenario(client, server):
            resp = await client.post(
                "/api/v2/spans", data=bulk_payload(2, per=2),
                headers={"Content-Type": "application/json",
                         "X-Request-Timeout-Ms": "bogus"},
            )
            assert resp.status == 202
            resp = await client.get("/api/v2/traces")
            assert resp.status == 200

        run_server(scenario)


# -- backoff guidance + admission at the real boundary -------------------


class TestBoundaryGuidance:
    def test_b3_sheds_bulk_with_retry_after_admits_errors(self):
        async def scenario(client, server):
            ctl = server._overload
            assert ctl is not None
            for _ in range(6):
                ctl.evaluate(SATURATED)
            assert ctl.level == B3
            resp = await client.post(
                "/api/v2/spans", data=bulk_payload(3, per=2),
                headers={"Content-Type": "application/json"},
            )
            assert resp.status == 429
            assert int(resp.headers["Retry-After"]) >= 1
            assert int(resp.headers["X-Retry-After-Ms"]) >= 50
            assert "B3" in await resp.text()
            resp = await client.post(
                "/api/v2/spans", data=error_payload(3),
                headers={"Content-Type": "application/json"},
            )
            assert resp.status == 202  # essential class survives B3

            prom = await (await client.get("/prometheus")).text()
            assert "zipkin_tpu_overload_level 3" in prom
            assert "zipkin_tpu_overload_shed_bulk_total 1" in prom
            statusz = await (await client.get("/api/v2/tpu/statusz")).json()
            assert statusz["overload"]["levelName"] == "B3"
            assert statusz["overload"]["readMode"] == "cache_only"

        run_server(scenario)

    def test_grpc_trailers_carry_retry_delay(self):
        from zipkin_tpu.server.grpc import _SpanServiceHandler

        ctl = ctl_with(ema_alpha=1.0)
        drive_to(ctl, B3)
        handler = _SpanServiceHandler(
            types.SimpleNamespace(overload=ctl)
        )
        trailers = dict(handler._retry_trailers())
        assert trailers["retry-delay"].endswith("s")
        assert float(trailers["retry-delay"][:-1]) >= 0.05
        assert int(trailers["retry-delay-ms"]) >= 50
        # no controller -> no trailers (bare rejection, pre-ISSUE-13)
        bare = _SpanServiceHandler(types.SimpleNamespace())
        assert bare._retry_trailers() is None


# -- sustained flood through the mp tier ---------------------------------


class TestSustainedFlood:
    def test_flood_sheds_with_guidance_zero_acked_loss_b0_recovery(
        self, tmp_path
    ):
        """The overload gate: >= 3x queue capacity through the
        real HTTP boundary while the device feed is slow AND the WAL
        hits ENOSPC mid-flood. Every shed must carry backoff guidance;
        every 202 must survive to durable parity; the disk-full window
        must degrade to the flagged at-risk mode (not crash) and clear;
        the ladder must return to B0 within one long SLO window."""
        workers, depth, per = 1, 2, 40
        n_flood = 18
        assert n_flood >= 3 * workers * depth  # the >=3x contract

        config = ServerConfig(
            storage_type="tpu", default_lookback=DAY_MS,
            tpu_fast_ingest=True, tpu_mp_workers=workers,
            tpu_mp_queue_depth=depth,
        )
        storage = TpuStorage(
            config=CFG, num_devices=2, batch_size=512,
            checkpoint_dir=str(tmp_path / "ckpt"),
            wal_dir=str(tmp_path / "wal"),
        )

        async def scenario(client, server):
            # slow device feed for the first 6 applied payloads (the
            # flood window), ENOSPC on the first WAL append: the flood
            # and the disk-full event overlap
            faults.arm_resource("feed.latency", nth=1, count=6,
                                latency_ms=120)
            faults.arm_resource("wal.append", nth=1, count=1)

            async def post(i):
                resp = await client.post(
                    "/api/v2/spans", data=bulk_payload(i, per=per),
                    headers={"Content-Type": "application/json"},
                )
                return resp.status, dict(resp.headers)

            results = await asyncio.gather(
                *[post(i) for i in range(n_flood)]
            )
            acked = [r for r in results if r[0] == 202]
            shed = [r for r in results if r[0] == 429]
            assert len(acked) + len(shed) == n_flood
            assert acked, "the tier must keep admitting during a flood"
            assert shed, "an 18-payload burst must overflow a depth-2 tier"
            for _, headers in shed:
                assert int(headers["Retry-After"]) >= 1
                assert int(headers["X-Retry-After-Ms"]) > 0

            # drain the accepted payloads to the device + WAL
            await asyncio.to_thread(server._mp_ingester.drain)

            acked_spans = per * len(acked)
            counters = storage.ingest_counters()
            # disk-full degraded, did not crash: flagged at-risk
            assert counters["walEnospc"] == 1
            assert counters["walMissedRecords"] == 1
            assert counters["durabilityAtRisk"] == 1
            # zero acked loss at the device tier
            assert storage.agg.host_counters["spans"] == acked_spans
            # recovery: a committed snapshot re-covers the lost WAL
            # record (the device state it captures includes that batch)
            assert storage.snapshot() is not None
            assert storage.ingest_counters()["durabilityAtRisk"] == 0

            # durable parity: a cold boot from the same dirs replays to
            # exactly the acked span set — zero acked loss, zero
            # unacked admission
            revived = make(tmp_path)
            assert revived.agg.host_counters["spans"] == acked_spans
            assert_query_parity(storage, revived)
            revived.close()

            # ladder recovery: saturate, then calm ticks must restore
            # B0 well inside one long SLO window (300 ticks at the 1 Hz
            # tick cadence; 3 levels x dwell 5 + EMA decay is ~20)
            ctl = server._overload
            for _ in range(6):
                ctl.evaluate(SATURATED)
            assert ctl.level == B3
            ticks_to_b0 = None
            for t in range(1, 41):
                if ctl.evaluate(CALM) == B0:
                    ticks_to_b0 = t
                    break
            assert ticks_to_b0 is not None and ticks_to_b0 <= 40
            assert ctl.status()["history"], "transitions must be recorded"

            metrics = await (await client.get("/metrics")).json()
            assert metrics["gauge.zipkin_tpu.overloadTransitions"] >= 2
            assert metrics["gauge.zipkin_tpu.overloadLevel"] == 0

            # TestClient tears down the app, not ZipkinServer.stop():
            # close the worker pool explicitly or its shm segments leak
            await asyncio.to_thread(server._mp_ingester.close)

        run_server(scenario, config=config, storage=storage)


# -- per-site ENOSPC recovery (the resource fault family) ----------------


class TestEnospcRecovery:
    def test_wal_append_enospc_flags_pages_and_recovers(self, tmp_path):
        bs = batches(4)
        oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
        for spans in bs:
            oracle.accept(spans).execute()

        victim = make(tmp_path)
        victim.accept(bs[0]).execute()
        faults.arm_resource("wal.append", nth=1, count=1)
        victim.accept(bs[1]).execute()  # ENOSPC: degrade, don't crash
        c = victim.ingest_counters()
        assert c["walEnospc"] == 1
        assert c["walMissedRecords"] == 1
        assert c["durabilityAtRisk"] == 1

        # the durability page: the gauge spec trips the watchdog
        rec = StageRecorder()
        clock = types.SimpleNamespace(t=1000.0)
        win = WindowedTelemetry(
            rec, victim.ingest_counters, tick_s=1.0, slots=16,
            coarse_slots=4, coarse_factor=16,
            clock=lambda: clock.t,
        )
        specs = [s for s in default_specs(short_s=4, long_s=8)
                 if s.name == "durability_at_risk"]
        dog = SloWatchdog(win, specs)
        clock.t += 1.0
        win.tick(clock.t)
        assert dog.verdicts()[0]["alert"], "at-risk mode must page"

        victim.accept(bs[2]).execute()  # WAL healthy again
        assert victim.snapshot() is not None  # commit clears at-risk
        assert victim.ingest_counters()["durabilityAtRisk"] == 0
        clock.t += 1.0
        win.tick(clock.t)
        assert not dog.verdicts()[0]["alert"]

        victim.accept(bs[3]).execute()
        del victim  # crash: HBM gone
        revived = make(tmp_path)  # checkpoint + WAL replay
        assert_query_parity(oracle, revived)
        revived.close()
        oracle.close()

    def test_snapshot_enospc_keeps_prior_generation_and_retries(
        self, tmp_path
    ):
        bs = batches(3)
        oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
        for spans in bs:
            oracle.accept(spans).execute()

        victim = make(tmp_path)
        victim.accept(bs[0]).execute()
        assert victim.snapshot() is not None  # generation 0 committed
        victim.accept(bs[1]).execute()
        faults.arm_resource("snapshot", nth=1, count=1)
        assert victim.snapshot() is None  # ENOSPC: no crash, no commit
        c = victim.ingest_counters()
        assert c["snapshotEnospc"] == 1
        assert c["durabilityAtRisk"] == 1
        # space freed: the retry commits and clears the flag
        assert victim.snapshot() is not None
        assert victim.ingest_counters()["durabilityAtRisk"] == 0
        victim.accept(bs[2]).execute()
        del victim
        revived = make(tmp_path)
        assert_query_parity(oracle, revived)
        revived.close()
        oracle.close()

    def test_snapshot_enospc_without_retry_still_recovers_via_wal(
        self, tmp_path
    ):
        """A failed snapshot must leave the WAL authoritative: crash in
        the at-risk window and the replay still reaches parity."""
        bs = batches(2)
        oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
        for spans in bs:
            oracle.accept(spans).execute()
        victim = make(tmp_path)
        for spans in bs:
            victim.accept(spans).execute()
        faults.arm_resource("snapshot", nth=1, count=1)
        assert victim.snapshot() is None
        del victim  # crash while durability-at-risk
        revived = make(tmp_path)
        assert_query_parity(oracle, revived)
        revived.close()
        oracle.close()

    def test_archive_enospc_drops_batch_not_process(self, tmp_path):
        bs = batches(3)
        oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
        for spans in bs:
            oracle.accept(spans).execute()

        victim = TpuStorage(
            config=CFG, num_devices=2, batch_size=512,
            archive_dir=str(tmp_path / "arch"),
        )
        victim.accept(bs[0]).execute()
        faults.arm_resource("archive", nth=1, count=1)
        victim.accept(bs[1]).execute()  # archive write ENOSPC: no crash
        c = victim.ingest_counters()
        assert c["archiveEnospc"] == 1
        assert c["archiveSpansDroppedEnospc"] >= len(bs[1])
        assert c["archiveAtRisk"] == 1
        # the raw archive is a lossy cache, not the durability path:
        # the page gauge must NOT treat its ENOSPC as at-risk
        assert c["durabilityAtRisk"] == 0
        victim.accept(bs[2]).execute()  # space freed: at-risk clears
        assert victim.ingest_counters()["archiveAtRisk"] == 0
        # aggregate answers are untouched by the archive drop
        assert_query_parity(oracle, victim)
        victim.close()
        oracle.close()

    def test_alloc_failure_degrades_to_backpressure(self):
        from zipkin_tpu.collector.core import Collector
        from zipkin_tpu.storage.memory import InMemoryStorage
        from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

        collector = Collector(InMemoryStorage())
        faults.arm_resource("alloc", nth=1, count=1)
        with pytest.raises(IngestBackpressure, match="allocation failure"):
            collector.accept_spans_bytes(bulk_payload(9, per=2))
        # one-shot: the next message ingests normally
        assert collector.accept_spans_bytes(bulk_payload(10, per=2)) == 2
