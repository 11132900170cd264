"""Sampler determinism across crash-resume (ISSUE 4 satellite).

The tier's replay story: verdicts are a pure function of (span,
published tables), tables are snapshot leaves + sctl WAL deltas, so a
process killed mid-ingest and rebooted from disk must produce
byte-identical verdicts for the same trace ids. The crash is injected
with the PR-3 fault registry (``ZT_CRASHPOINT`` sites) at the nastiest
instant — mid-WAL-append, after the controller has already published
tightened tables.
"""

from __future__ import annotations

import json

import numpy as np

from zipkin_tpu import faults
from zipkin_tpu.sampling.reference import host_verdict
from zipkin_tpu.storage.tpu import TpuStorage
from zipkin_tpu.tpu.state import AggConfig

CFG = AggConfig(
    max_services=64, max_keys=256, hll_precision=8, digest_centroids=16,
    digest_buffer=4096, ring_capacity=4096, link_buckets=4,
    bucket_minutes=60, hist_slices=2, sampling=True,
)


def make(tmp_path):
    return TpuStorage(
        config=CFG, num_devices=2, batch_size=512,
        checkpoint_dir=str(tmp_path / "ckpt"),
        wal_dir=str(tmp_path / "wal"),
        archive_dir=str(tmp_path / "archive"),
        sampling_budget=100.0,
        # the tests tick the controller by hand; its own 5 s thread
        # would otherwise publish at a moment of its choosing on a
        # loaded machine (and outlive ``del victim``)
        sampling_interval_s=3600.0,
    )


def payload(n, base):
    return json.dumps([
        {"traceId": f"{i + base:016x}", "id": f"{i + base:016x}",
         "name": f"op{i % 3}",
         "timestamp": 1_700_000_000_000_000 + i,
         "duration": 1000 + (i % 50),
         "localEndpoint": {"serviceName": f"svc{i % 4}"},
         **({"tags": {"error": "true"}} if i % 10 == 0 else {})}
        for i in range(n)
    ]).encode()


PROBE = dict(
    trace_h=np.arange(1, 50_000, 13, dtype=np.uint32),
    svc=np.tile(np.arange(8, dtype=np.int64), 481)[:3847],
    rsvc=np.zeros(3847, np.int64),
    key=np.ones(3847, np.int64),
    dur=np.full(3847, 1234, np.uint32),
    has_dur=np.ones(3847, bool),
    err=np.zeros(3847, bool),
    valid=np.ones(3847, bool),
)


def verdicts(sampler):
    return host_verdict(
        **PROBE, rate=sampler.rate, tail=sampler.tail, link=sampler.link,
        rare_min=sampler.rare_min,
    )


def test_crash_mid_ingest_reproduces_identical_verdicts(tmp_path):
    victim = make(tmp_path)
    victim.ingest_json_fast(payload(1000, base=1))
    # the controller publishes tightened tables (sctl record in the WAL)
    assert victim.sampling_controller.tick(1.0)
    victim.ingest_json_fast(payload(1000, base=10_001))
    assert victim.sampling_controller.tick(1.0)

    tables = (
        victim.sampler.rate.copy(),
        victim.sampler.tail.copy(),
        victim.sampler.link.copy(),
    )
    v_live = verdicts(victim.sampler)
    assert 0 < int(v_live.sum()) < len(v_live)  # tightened, a real mix
    counters = dict(victim.agg.host_counters)

    # kill the process mid-WAL-append on the NEXT batch (header+meta on
    # disk, payload torn): the batch was never acked, the record must
    # not half-apply on reboot
    faults.arm("wal.append.mid", nth=1, action="raise")
    try:
        with np.testing.assert_raises(faults.CrashpointTriggered):
            victim.ingest_json_fast(payload(1000, base=20_001))
    finally:
        faults.disarm()
    del victim  # device state notionally lost; disk is all that survives

    reborn = make(tmp_path)
    # published tables reconstructed exactly (snapshot leaves absent ->
    # replayed sctl deltas alone must land them)
    np.testing.assert_array_equal(reborn.sampler.rate, tables[0])
    np.testing.assert_array_equal(reborn.sampler.tail, tables[1])
    np.testing.assert_array_equal(reborn.sampler.link, tables[2])
    # and the device leaves agree with the host tables (replicated)
    np.testing.assert_array_equal(
        np.asarray(reborn.agg.state.s_rate)[0], tables[0]
    )
    np.testing.assert_array_equal(
        np.asarray(reborn.agg.state.s_tail)[0], tables[1]
    )
    np.testing.assert_array_equal(
        np.asarray(reborn.agg.state.s_link)[0], tables[2]
    )
    # byte-identical verdicts for the same trace ids
    np.testing.assert_array_equal(verdicts(reborn.sampler), v_live)
    # exact counter restore, including the sampler tallies (the torn
    # third batch was never acked and must not be counted)
    assert dict(reborn.agg.host_counters) == counters

    # the restarted process gates NEW traffic under the restored tables:
    # re-ingesting the second batch's ids reproduces its keep count
    kept_before = counters["sampledKept"]
    reborn2_kept = []
    for st in (reborn,):
        st.ingest_json_fast(payload(1000, base=10_001))
        reborn2_kept.append(st.agg.host_counters["sampledKept"] - kept_before)
    # oracle: a second pristine boot from the same disk state
    del reborn
    oracle = make(tmp_path)
    # note: reborn's extra batch was WAL-logged, so the oracle replays
    # it — its verdict-kept count must match reborn's live gating
    assert (
        oracle.agg.host_counters["sampledKept"] - kept_before
        == reborn2_kept[0]
    )
    oracle.close()


def test_snapshot_then_crash_restores_tables_from_leaves(tmp_path):
    victim = make(tmp_path)
    victim.ingest_json_fast(payload(1000, base=1))
    assert victim.sampling_controller.tick(1.0)
    tables = (
        victim.sampler.rate.copy(),
        victim.sampler.tail.copy(),
        victim.sampler.link.copy(),
    )
    v_live = verdicts(victim.sampler)
    victim.snapshot()  # tables now live in snapshot LEAVES, WAL truncated
    victim.ingest_json_fast(payload(500, base=30_001))
    counters = dict(victim.agg.host_counters)
    del victim

    reborn = make(tmp_path)
    np.testing.assert_array_equal(reborn.sampler.rate, tables[0])
    np.testing.assert_array_equal(reborn.sampler.tail, tables[1])
    np.testing.assert_array_equal(reborn.sampler.link, tables[2])
    np.testing.assert_array_equal(verdicts(reborn.sampler), v_live)
    assert dict(reborn.agg.host_counters) == counters
    reborn.close()
