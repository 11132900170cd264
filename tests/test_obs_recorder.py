"""Flight-recorder core: torn-read-free snapshots under concurrent
writers, log2-bucket math round-trips, and the slow-path ring/hook.

The consistency oracle: every writer thread records a FIXED duration
into its own stage, so in any generation-consistent snapshot that
stage's ``sum_us == count * us`` exactly and exactly one bucket holds
all the counts. A torn read (count bumped but sum not yet, or buckets
copied across a writer's update) breaks the equality — the recorder
rounds to integer µs precisely so this invariant is exact, not
approximate.
"""

from __future__ import annotations

import threading

from zipkin_tpu.obs import stages as stages_mod
from zipkin_tpu.obs.recorder import (
    NUM_BUCKETS,
    StageRecorder,
    bucket_index,
    bucket_le_us,
)

STAGES = stages_mod.STAGES


class TestBucketMath:
    def test_round_trip_known_durations(self):
        # (duration_s, expected µs) — rounding at the µs boundary
        cases = [
            (0.0, 0), (4e-7, 0), (6e-7, 1), (1e-6, 1), (0.001, 1000),
            (0.123456, 123456), (1.0, 1_000_000), (60.0, 60_000_000),
        ]
        for dur_s, us in cases:
            b = bucket_index(dur_s)
            assert us <= bucket_le_us(b), (dur_s, us, b)
            if b > 0:
                assert us > bucket_le_us(b - 1), (dur_s, us, b)

    def test_bucket_bounds_are_log2(self):
        assert bucket_le_us(0) == 0
        assert bucket_le_us(1) == 1
        assert bucket_le_us(10) == 1023
        # top bucket clips: absurd durations stay in range
        assert bucket_index(1e9) == NUM_BUCKETS - 1

    def test_quantiles_on_known_distribution(self):
        rec = StageRecorder(enabled=True)
        # 99 fast (1 ms) + 1 slow (1 s): p50 lands in the 1 ms bucket,
        # p99 still in the fast bucket (cum 99 >= 99), max is exact
        for _ in range(99):
            rec.record("parse", 0.001)
        rec.record("parse", 1.0)
        st = rec.snapshot().stage("parse")
        assert st.count == 100
        assert st.max_us == 1_000_000
        # log2 resolution: quantile reads report the bucket's inclusive
        # upper bound (true value within 2x below it)
        assert 1000 <= st.p50_us <= 1023
        assert 1000 <= st.p99_us <= 1023
        assert st.quantile_us(1.0) == 1_000_000


class TestConcurrentSnapshots:
    def test_threaded_writers_never_tear(self):
        rec = StageRecorder(enabled=True)
        n_threads = 4
        per_thread = 4000
        # one stage and one FIXED duration per writer -> exact oracle
        plan = [(STAGES[i], (i + 1) * 7) for i in range(n_threads)]
        stop = threading.Event()
        errors = []

        def writer(stage, us):
            dur_s = us / 1e6
            for _ in range(per_thread):
                rec.record(stage, dur_s)

        def reader():
            prev = {stage: 0 for stage, _ in plan}
            while not stop.is_set():
                snap = rec.snapshot()
                for stage, us in plan:
                    st = snap.stage(stage)
                    if st.sum_us != st.count * us:
                        errors.append(
                            f"torn: {stage} sum {st.sum_us} != "
                            f"{st.count} * {us}"
                        )
                    if sum(1 for c in st.buckets if c) > 1:
                        errors.append(f"torn: {stage} spans buckets")
                    if st.count < prev[stage]:
                        errors.append(f"non-monotone count on {stage}")
                    prev[stage] = st.count

        threads = [
            threading.Thread(target=writer, args=p) for p in plan
        ]
        rd = threading.Thread(target=reader)
        rd.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        rd.join()
        assert errors == [], errors[:5]
        snap = rec.snapshot()
        for stage, us in plan:
            st = snap.stage(stage)
            assert st.count == per_thread
            assert st.sum_us == per_thread * us
            assert st.max_us == us
        assert snap.locals_seen == n_threads

    def test_generation_is_even_and_advances(self):
        rec = StageRecorder(enabled=True)
        g0 = rec.snapshot().generation
        rec.record("pack", 0.002)
        g1 = rec.snapshot().generation
        assert g1 % 2 == 0 and g1 > g0


class TestConfigAndSlowPath:
    def test_disabled_recorder_is_a_noop(self):
        rec = StageRecorder(enabled=False)
        rec.record("parse", 5.0)
        assert rec.snapshot().total_count == 0
        rec.set_enabled(True)
        rec.record("parse", 5.0)
        assert rec.snapshot().total_count == 1

    def test_budget_crossing_rings_and_hooks(self):
        rec = StageRecorder(enabled=True, slow_ring_size=4)
        rec.set_budget_scale(0.0)  # every nonzero duration is over
        seen = []
        rec.set_slow_hook(lambda ev: seen.append(ev["stage"]))
        for _ in range(6):
            rec.record("wal_fsync", 0.010)
        events = rec.slow_events()
        assert len(events) == 4  # bounded ring
        assert all(e["stage"] == "wal_fsync" for e in events)
        assert events[-1]["durUs"] == 10_000
        assert len(seen) == 6  # hook saw every crossing, ring clipped
        # a hook in place may enrich the event before the ring keeps it
        rec.set_slow_hook(lambda ev: ev.update(traceId="cafe"))
        rec.record("wal_fsync", 0.010)
        assert rec.slow_events()[-1]["traceId"] == "cafe"

    def test_budget_scale_restores(self):
        rec = StageRecorder(enabled=True)
        base = rec.budget_us("parse")
        rec.set_budget_scale(2.0)
        assert rec.budget_us("parse") == 2 * base
        rec.set_budget_scale(1.0)
        assert rec.budget_us("parse") == base
        # under-budget durations never touch the ring
        rec.record("parse", base / 2e6)
        assert rec.slow_events() == []

    def test_record_relayed_skips_slow_ring_and_hooks(self):
        """The fan-out dispatcher relays worker-measured stage walls via
        record_relayed: histograms/quantiles fill identically, but the
        slow ring and self-span hook never fire — the dispatcher's B3
        context is not the context that did the work."""
        rec = StageRecorder(enabled=True)
        rec.set_budget_scale(0.0)  # every nonzero duration is over
        seen = []
        rec.set_slow_hook(lambda ev: seen.append(ev["stage"]))
        rec.record_relayed("parse", 0.010)
        st = rec.snapshot().stage("parse")
        assert st.count == 1
        assert st.max_us == 10_000
        assert rec.slow_events() == []
        assert seen == []
        rec.set_budget_scale(1.0)
        # disabled recorder: relayed records are no-ops too
        rec.set_enabled(False)
        rec.record_relayed("parse", 0.010)
        assert rec.snapshot().stage("parse").count == 1
        rec.set_enabled(True)

    def test_overhead_self_measurement_isolated(self):
        rec = StageRecorder(enabled=True)
        ns = rec.measure_overhead(n=500)
        assert ns > 0
        # the scratch recorder absorbed the samples, not this one
        assert rec.snapshot().total_count == 0


class TestCatalogue:
    def test_budgets_cover_every_stage(self):
        assert set(stages_mod.DEFAULT_BUDGETS_US) == set(STAGES)
        assert all(v > 0 for v in stages_mod.DEFAULT_BUDGETS_US.values())

    def test_issue_stage_names_all_present(self):
        expected = {
            "http_boundary", "grpc_boundary", "parse", "pack", "route",
            "device_dispatch", "rollup", "ctx_advance", "wal_append",
            "wal_fsync", "snapshot", "sampler_tick", "archive_write",
            "query_fresh", "query_cached", "readpack_transfer", "mp_record",
            "mp_shm_copy", "mp_vocab_replay", "mp_lut_remap",
            "coalesce", "mp_device_feed", "accuracy_rollup",
            "wire_to_durable",
            "query_lock_wait", "query_wall", "query_mirror",
            "mirror_publish", "reader_serve",
            # PR 26: the publish's hold, split, and the write path's wait
            "publish_lock_hold", "publish_queue_drain", "ingest_lock_wait",
        }
        assert set(STAGES) == expected
