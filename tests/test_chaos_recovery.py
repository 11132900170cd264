"""Fault-injection chaos recovery (ISSUE 3 tentpole).

Each cycle arms one named crashpoint (zipkin_tpu.faults.SITES — the
exact instants where a crash tears on-disk state hardest), crashes the
ingesting store AT it, boots a fresh store from the same dirs, and
asserts bit-identical counter/link/sketch parity against an
uninterrupted oracle fed the recovered batch prefix.

Crash simulation uses action="raise": ``CrashpointTriggered``
propagates out of the write path and the store object is abandoned —
the same HBM-is-gone idiom as tests/test_wal.py, with the addition
that the armed site flushes its partial write first so the on-disk
tear is exactly what a SIGKILL after a real flush would leave.

Tier-1 runs the deterministic single-site tests; the randomized
multi-site soak (>=20 kill/restart cycles) is marked slow.
"""

from __future__ import annotations

import glob
import logging
import random
import re

import pytest

from tests.fixtures import lots_of_spans
from tests.test_wal import CFG, assert_query_parity, batches, make
from zipkin_tpu import faults
from zipkin_tpu.storage.tpu import TpuStorage


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


# -- registry basics -----------------------------------------------------


def test_crashpoint_registry():
    assert faults.armed_site() is None
    faults.crashpoint("wal.append.mid")  # disarmed: no-op
    with pytest.raises(ValueError, match="unknown crashpoint site"):
        faults.arm("no.such.site")
    faults.arm("wal.append.mid", nth=2, action="raise")
    assert faults.is_armed("wal.append.mid")
    faults.crashpoint("snapshot.post_meta")  # different site: no-op
    faults.crashpoint("wal.append.mid")  # pass 1 of 2: survives
    with pytest.raises(faults.CrashpointTriggered):
        faults.crashpoint("wal.append.mid")
    assert faults.armed_site() is None  # one-shot: self-disarmed


def test_env_arming(monkeypatch):
    monkeypatch.setenv(faults.ENV_VAR, "archive.mid_segment:3")
    monkeypatch.setenv(faults.ENV_ACTION, "raise")
    faults._arm_from_env()
    assert faults.is_armed("archive.mid_segment")
    faults.disarm()
    monkeypatch.setenv(faults.ENV_VAR, "bogus.site")
    faults._arm_from_env()  # must not raise: a typo cannot brick boot
    assert faults.armed_site() is None


def test_multi_site_env_arming(monkeypatch):
    """Comma-separated ZT_CRASHPOINT / ZT_CORRUPT arm several sites at
    once (the corruption soak combines a corrupt site with a kill site
    in one subprocess run). Sites fire independently."""
    monkeypatch.setenv(faults.ENV_VAR, "wal.append.mid:2, archive.mid_segment")
    monkeypatch.setenv(faults.ENV_ACTION, "raise")
    monkeypatch.setenv(faults.ENV_CORRUPT, "snapshot.state:zero:2, wal.record")
    faults._arm_from_env()
    assert faults.is_armed("wal.append.mid")
    assert faults.is_armed("archive.mid_segment")
    assert faults.is_corrupt_armed("snapshot.state")
    assert faults.is_corrupt_armed("wal.record")
    # one site firing leaves the others armed
    with pytest.raises(faults.CrashpointTriggered):
        faults.crashpoint("archive.mid_segment")
    assert faults.is_armed("wal.append.mid")
    assert faults.is_corrupt_armed("wal.record")
    faults.disarm()
    assert faults.armed_site() is None
    assert not faults.is_corrupt_armed("wal.record")
    # a typo'd corrupt spec must not brick a boot either
    monkeypatch.setenv(faults.ENV_CORRUPT, "no.such.site:flip")
    faults._arm_from_env()
    assert not any(faults.is_corrupt_armed(s) for s in faults.CORRUPT_SITES)


def test_corrupt_registry_one_shot(tmp_path):
    with pytest.raises(ValueError, match="unknown corrupt site"):
        faults.arm_corrupt("no.such.site")
    with pytest.raises(ValueError, match="unknown corrupt mode"):
        faults.arm_corrupt("wal.record", mode="melt")
    p = tmp_path / "blob"
    p.write_bytes(bytes(range(200)))
    assert not faults.corrupt_point("wal.record", str(p), 0, 200)  # disarmed
    faults.arm_corrupt("wal.record", mode="flip", nth=2)
    assert not faults.corrupt_point("wal.record", str(p), 0, 200)  # 1 of 2
    assert faults.corrupt_point("wal.record", str(p), 0, 200)
    assert not faults.is_corrupt_armed("wal.record")  # one-shot
    data = p.read_bytes()
    # deterministic damage: flip XORs exactly the mid-range byte
    assert len(data) == 200 and data[100] == (100 ^ 0xFF)
    assert data[:100] == bytes(range(100))
    faults.arm_corrupt("wal.record", mode="truncate")
    assert faults.corrupt_point("wal.record", str(p), 0, 200)
    assert p.stat().st_size == 100
    faults.arm_corrupt("wal.record", mode="zero")
    assert faults.corrupt_point("wal.record", str(p), 0, 100)
    zeroed = p.read_bytes()[33:66]
    assert zeroed == b"\x00" * len(zeroed)


# -- deterministic sites (tier-1) ----------------------------------------


def test_crash_mid_wal_append_recovers_to_parity(tmp_path):
    """Torn WAL record (header+meta on disk, payload missing): the
    crashed batch was never acked, everything before it replays."""
    bs = batches(5)
    victim = make(tmp_path)
    for spans in bs[:3]:
        victim.accept(spans).execute()
    faults.arm("wal.append.mid", action="raise")
    with pytest.raises(faults.CrashpointTriggered):
        victim.accept(bs[3]).execute()
    del victim  # crash: HBM gone, torn record on disk

    revived = make(tmp_path)
    oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
    for spans in bs[:3]:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived)
    # the revived store is fully usable: the lost batch's client retry
    # and further traffic land normally and stay durable
    revived.accept(bs[3]).execute()
    revived.accept(bs[4]).execute()
    del revived
    revived2 = make(tmp_path)
    for spans in bs[3:]:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived2)


def test_crash_between_snapshot_state_and_meta_keeps_old_pair(tmp_path):
    """snapshot.post_state: the new state .npz is renamed in but
    meta.json still describes the previous snapshot. The commit
    protocol (meta.json names its state file) must restore the OLD
    complete pair and replay the longer WAL tail — pairing new state
    with old meta would double-replay into it."""
    bs = batches(5)
    victim = make(tmp_path)
    for spans in bs[:2]:
        victim.accept(spans).execute()
    victim.snapshot()  # a complete old pair exists
    for spans in bs[2:4]:
        victim.accept(spans).execute()
    faults.arm("snapshot.post_state", action="raise")
    with pytest.raises(faults.CrashpointTriggered):
        victim.snapshot()
    del victim

    revived = make(tmp_path)
    oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
    for spans in bs[:4]:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived)


def test_crash_after_snapshot_meta_before_truncate(tmp_path):
    """snapshot.post_meta: the snapshot is durable but covered WAL
    segments were not truncated. Replay must skip the covered records
    (seq <= wal_seq) instead of double-applying them."""
    bs = batches(4)
    victim = make(tmp_path)
    for spans in bs:
        victim.accept(spans).execute()
    faults.arm("snapshot.post_meta", action="raise")
    with pytest.raises(faults.CrashpointTriggered):
        victim.snapshot()
    del victim

    revived = make(tmp_path)
    oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
    for spans in bs:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived)


# -- deterministic corruption sites (tier-1, ISSUE 7) --------------------


@pytest.mark.parametrize("mode", faults.CORRUPT_MODES)
def test_corrupt_snapshot_state_falls_back_to_parity(tmp_path, mode):
    """snapshot.state rot: the newest committed generation is damaged
    AT REST. Boot must quarantine it, fall back to the older retained
    generation, and replay the longer WAL suffix — aggregates
    bit-identical to an uninterrupted oracle, zero acked-span loss."""
    bs = batches(5)
    victim = make(tmp_path)
    for spans in bs[:2]:
        victim.accept(spans).execute()
    victim.snapshot()  # the intact fallback generation
    for spans in bs[2:4]:
        victim.accept(spans).execute()
    faults.arm_corrupt("snapshot.state", mode=mode)
    victim.snapshot()  # commits, then rots
    assert not faults.is_corrupt_armed("snapshot.state")
    del victim  # crash

    revived = make(tmp_path)
    oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
    for spans in bs[:4]:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived)
    assert revived.restore_stats["restoreFallbacks"] == 1
    assert revived.restore_stats["generationsQuarantined"] == 1
    # the rotted generation is evidence: renamed aside, never unlinked
    assert glob.glob(str(tmp_path / "ckpt" / "*.npz.quarantine"))
    # fully usable post-fallback: new traffic lands and stays durable
    revived.accept(bs[4]).execute()
    del revived
    oracle.accept(bs[4]).execute()
    assert_query_parity(oracle, make(tmp_path))


@pytest.mark.parametrize("mode", faults.CORRUPT_MODES)
def test_corrupt_wal_record_covered_by_snapshot(tmp_path, mode):
    """wal.record rot on an acked record that a LATER snapshot covers:
    replay seeks past covered records without reading their bytes, so
    recovery is bit-identical — zero acked-span loss. The single-copy
    WAL's boundary is the uncovered suffix (rot there loses the record's
    bytes; the scrubber surfaces it as scrubCorruptDetected)."""
    bs = batches(4)
    victim = make(tmp_path)
    victim.accept(bs[0]).execute()
    faults.arm_corrupt("wal.record", mode=mode)
    victim.accept(bs[1]).execute()  # acked, then its payload rots
    assert not faults.is_corrupt_armed("wal.record")
    victim.accept(bs[2]).execute()
    victim.snapshot()  # wal_seq now covers the rotted record
    del victim  # crash

    revived = make(tmp_path)
    oracle = make(tmp_path / "oracle", wal=False, checkpoint=False)
    for spans in bs[:3]:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, revived)
    # post-revival traffic must get FRESH seqs past the snapshot's
    # coverage even though the damaged record can hide part of the
    # numbering from the boot scan — replay must not skip it next boot
    revived.accept(bs[3]).execute()
    del revived
    oracle.accept(bs[3]).execute()
    assert_query_parity(oracle, make(tmp_path))


@pytest.mark.parametrize("mode", faults.CORRUPT_MODES)
def test_corrupt_archive_frame_quarantined_with_accounting(tmp_path, mode):
    """archive.frame rot never touches aggregates (the archive is the
    raw-span store); the scrubber pulls the sealed segment from service
    with accounting instead of letting reads fail on bad frames."""
    from zipkin_tpu.runtime.scrub import Scrubber

    feed = [
        lots_of_spans(300, seed=700 + i, services=8, span_names=12)
        for i in range(3)
    ]
    store = _make_chaos(tmp_path)
    store.accept(feed[0]).execute()
    faults.arm_corrupt("archive.frame", mode=mode)
    store.accept(feed[1]).execute()  # this frame rots post-ack
    assert not faults.is_corrupt_armed("archive.frame")
    store.accept(feed[2]).execute()
    store._disk.flush()  # seal: the rotted frame is now at rest

    scrubber = Scrubber(store, interval_s=3600.0, bytes_per_sec=0)
    res = scrubber.scan_once()
    assert res["corrupt"] == 1 and res["quarantined"] == 1
    assert res["spans_quarantined"] > 0
    store.scrubber = scrubber  # counters flow through ingest_counters
    counters = store.ingest_counters()
    assert counters["segmentsQuarantined"] == 1
    assert counters["archiveSegmentsQuarantined"] == 1
    assert (
        counters["archiveSpansQuarantined"] == counters["spansQuarantined"] > 0
    )
    # renamed aside with sidecars, never unlinked
    arc = tmp_path / "state" / "archive"
    assert glob.glob(str(arc / "*.dat.quarantine"))
    assert not glob.glob(str(arc / "*.dat"))
    # a second pass is idempotent: the quarantined segment left the set
    assert scrubber.scan_once()["corrupt"] == 0
    # aggregates: bit-identical to an uninterrupted oracle
    oracle = _make_chaos(tmp_path, oracle=True)
    for spans in feed:
        oracle.accept(spans).execute()
    assert_query_parity(oracle, store)
    store.close()


def test_wal_bad_crc_warning_names_seq_and_offset(tmp_path, caplog):
    """The skip-segment-tail warning must locate the abandonment (seq +
    byte offset) so a postmortem can tell what the rot cost."""
    bs = batches(3)
    victim = make(tmp_path, checkpoint=False)
    victim.accept(bs[0]).execute()
    faults.arm_corrupt("wal.record", mode="flip")
    victim.accept(bs[1]).execute()
    victim.accept(bs[2]).execute()
    del victim  # crash; boot replays from seq 0 and hits the rot

    with caplog.at_level(logging.WARNING):
        make(tmp_path, checkpoint=False)
    m = re.search(r"bad crc on record seq (\d+) at offset (\d+)", caplog.text)
    assert m, caplog.text
    assert int(m.group(1)) == 2
    assert int(m.group(2)) > 0  # record 2 starts after record 1's bytes


# -- randomized multi-site soak (slow) -----------------------------------


def _make_chaos(root, oracle=False):
    sub = "oracle" if oracle else "state"
    return TpuStorage(
        config=CFG, num_devices=1, batch_size=512,
        checkpoint_dir=None if oracle else str(root / sub / "ckpt"),
        wal_dir=None if oracle else str(root / sub / "wal"),
        archive_dir=None if oracle else str(root / sub / "archive"),
    )


@pytest.mark.slow
def test_randomized_chaos_cycles(tmp_path):
    """>=20 randomized crash/restart cycles across ALL registered
    sites; after every crash the revived store must be bit-identical to
    an oracle fed exactly the recovered batch prefix."""
    rng = random.Random(0xC4A05)
    per = 300
    feed = [
        lots_of_spans(per, seed=900 + i, services=8, span_names=12)
        for i in range(120)
    ]
    oracle = _make_chaos(tmp_path, oracle=True)
    oracle_k = 0
    committed = 0  # batches proven durable so far
    cursor = 0  # next feed index (re-feeds any unacked/lost batch)
    cycles = 0
    target = 21
    hits = {s: 0 for s in faults.SITES}

    while cycles < target:
        site = faults.SITES[cycles % len(faults.SITES)]
        victim = _make_chaos(tmp_path)

        # boot parity: recovery must reproduce exactly a batch prefix
        recovered = victim.agg.host_counters["spans"]
        assert recovered % per == 0, (site, recovered)
        k = recovered // per
        assert k >= committed, f"{site}: lost acked batches ({k}<{committed})"
        while oracle_k < k:
            oracle.accept(feed[oracle_k]).execute()
            oracle_k += 1
        assert_query_parity(oracle, victim)
        committed = k
        cursor = k  # the client retries anything unacked

        crashed = False
        if site.startswith("snapshot."):
            for _ in range(rng.randint(1, 3)):
                victim.accept(feed[cursor]).execute()
                cursor += 1
            faults.arm(site, nth=1, action="raise")
            with pytest.raises(faults.CrashpointTriggered):
                victim.snapshot()
            crashed = True
        else:
            faults.arm(site, nth=rng.randint(1, 3), action="raise")
            try:
                while cursor < len(feed):
                    victim.accept(feed[cursor]).execute()
                    cursor += 1
                    if rng.random() < 0.3:
                        victim.snapshot()
            except faults.CrashpointTriggered:
                crashed = True
        assert crashed, site
        faults.disarm()
        del victim
        hits[site] += 1
        cycles += 1

    assert cycles >= 20
    assert all(n >= 4 for n in hits.values()), hits

    # final boot: everything ever acked is present and queryable
    final = _make_chaos(tmp_path)
    k = final.agg.host_counters["spans"] // per
    while oracle_k < k:
        oracle.accept(feed[oracle_k]).execute()
        oracle_k += 1
    assert_query_parity(oracle, final)
    # the disk archive recovered alongside (torn frames truncated)
    assert final._disk is not None
    assert final._disk.spans_written >= 0


@pytest.mark.slow
def test_randomized_corruption_soak(tmp_path):
    """Every corrupt site x {flip, truncate, zero}, twice, in random
    order: each cycle damages a durable artifact, crashes, and the next
    boot must quarantine the rot, fall back where needed, and come up
    bit-identical to an oracle fed every batch ever acked — ZERO
    acked-span loss (k == cursor, not merely a prefix). Some cycles run
    an at-rest scrub pass before the crash: a scrub must never
    quarantine anything the next boot's replay still needs."""
    from zipkin_tpu.runtime.scrub import Scrubber

    rng = random.Random(0xB17507)
    per = 300
    feed = [
        lots_of_spans(per, seed=1300 + i, services=8, span_names=12)
        for i in range(90)
    ]
    oracle = _make_chaos(tmp_path, oracle=True)
    oracle_k = 0
    cursor = 0  # batches acked so far; every one must survive
    combos = [
        (s, m) for s in faults.CORRUPT_SITES for m in faults.CORRUPT_MODES
    ] * 2
    rng.shuffle(combos)
    scrub_passes = 0

    for site, mode in combos:
        victim = _make_chaos(tmp_path)
        recovered = victim.agg.host_counters["spans"]
        assert recovered % per == 0, (site, mode, recovered)
        k = recovered // per
        assert k == cursor, (
            f"{site}:{mode} lost acked batches ({k} != {cursor})"
        )
        while oracle_k < k:
            oracle.accept(feed[oracle_k]).execute()
            oracle_k += 1
        assert_query_parity(oracle, victim)

        n_feed = rng.randint(2, 4)
        if site == "snapshot.state":
            for _ in range(n_feed):
                victim.accept(feed[cursor]).execute()
                cursor += 1
            faults.arm_corrupt(site, mode=mode)
            victim.snapshot()  # commits, then the generation rots
        else:
            faults.arm_corrupt(site, mode=mode, nth=rng.randint(1, n_feed))
            for _ in range(n_feed):
                victim.accept(feed[cursor]).execute()
                cursor += 1
            if site == "wal.record":
                # single-copy WAL: rot is lossless once a snapshot
                # covers the record (replay seeks past covered seqs);
                # the uncovered suffix is the documented boundary
                victim.snapshot()
            elif rng.random() < 0.5:
                victim.snapshot()
        assert not faults.is_corrupt_armed(site), (site, mode)
        if rng.random() < 0.4:
            victim._disk.flush()
            Scrubber(victim, interval_s=3600.0, bytes_per_sec=0).scan_once()
            scrub_passes += 1
        faults.disarm()
        del victim  # crash

    final = _make_chaos(tmp_path)
    assert final.agg.host_counters["spans"] == cursor * per
    while oracle_k < cursor:
        oracle.accept(feed[oracle_k]).execute()
        oracle_k += 1
    assert_query_parity(oracle, final)
    assert scrub_passes >= 3  # the at-rest leg actually ran
    # rot left evidence behind, never silent deletion: at least the
    # snapshot.state cycles must have quarantined generations
    q = glob.glob(str(tmp_path / "state" / "ckpt" / "*.npz.quarantine"))
    assert len(q) >= 6, q  # 2 cycles x 3 modes, re-tried metas aside
