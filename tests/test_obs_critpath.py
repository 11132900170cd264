"""Ingest critical-path tracer (ISSUE 11): interval-ledger torn-read
freedom under threaded slot churn, the conservation property over
randomized fan-out runs (segments sum to measured wall within bound),
and orphaned-slot reclaim after an uncleanly killed worker.

The fuzz oracle mirrors test_obs_recorder's: every writer stamps a
FIXED, pid-derived interval pattern, so any consistent read of a slot
must show intervals that all decode back to that slot's pid — a torn
read (old pid, new intervals, or a half-written triple) violates the
pattern and fails loudly.
"""

from __future__ import annotations

import threading
import time

import pytest

from zipkin_tpu.obs import critpath as cp
from zipkin_tpu.obs.critpath import (
    MAX_D_IV,
    SEG_ENQUEUE,
    CritPathLedger,
    CritPathStitcher,
    _OFF_N_D,
    _OFF_D_IV,
    _OFF_PID,
    _OFF_STATE,
    _ST_FREE,
    _ST_OPEN,
)

# -- ledger fuzz --------------------------------------------------------


def _writer(led: CritPathLedger, widx: int, iters: int, fail: list) -> None:
    """alloc -> stamp a pid-derived pattern -> ack -> release: full slot
    lifecycle including reuse (release feeds the LIFO free list, so
    other writers immediately recycle the slot under the readers)."""
    try:
        for i in range(iters):
            pid = widx * 1_000_000 + i + 1
            slot = led.alloc(pid, 0, wire_t0_ns=1)
            if slot < 0:
                continue  # transient exhaustion is legal (counted)
            n = 1 + (i % 5)
            for j in range(n):
                t0 = pid * 1000 + j * 10
                led.stamp(slot, SEG_ENQUEUE, t0, t0 + 7, pid=pid)
            led.ack(slot, pid=pid, t_ns=2)
            led.release(slot)
    except Exception as e:  # pragma: no cover - surfaced by the assert
        fail.append(e)


def _reader(led: CritPathLedger, stop: threading.Event, fail: list) -> None:
    """Every successfully snapshotted non-FREE slot must be internally
    consistent: interval count in range, every triple decoding to the
    slot header's pid with the writer's fixed duration."""
    try:
        while not stop.is_set():
            for slot in range(led.slots):
                blk = led.read_slot(slot)
                if blk is None:
                    continue  # writer kept it torn all retries: skip, legal
                if int(blk[_OFF_STATE]) == _ST_FREE:
                    continue
                pid = int(blk[_OFF_PID])
                n = int(blk[_OFF_N_D])
                assert 0 <= n <= MAX_D_IV, f"slot {slot}: n_d={n}"
                for j in range(n):
                    base = _OFF_D_IV + 3 * j
                    code = int(blk[base])
                    t0 = int(blk[base + 1])
                    t1 = int(blk[base + 2])
                    assert code == SEG_ENQUEUE, f"slot {slot}: code={code}"
                    assert t0 == pid * 1000 + j * 10, (
                        f"slot {slot}: torn interval {j}: t0={t0} pid={pid}"
                    )
                    assert t1 == t0 + 7
    except Exception as e:  # pragma: no cover - surfaced by the assert
        fail.append(e)


def test_ledger_fuzz_torn_read_free_under_slot_reuse():
    led = CritPathLedger(1, slots=8)  # few slots => constant reuse
    fail: list = []
    stop = threading.Event()
    readers = [
        threading.Thread(target=_reader, args=(led, stop, fail))
        for _ in range(2)
    ]
    writers = [
        threading.Thread(target=_writer, args=(led, w, 2000, fail))
        for w in range(4)
    ]
    try:
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=60)
        stop.set()
        for t in readers:
            t.join(timeout=10)
        assert not fail, fail[0]
    finally:
        stop.set()
        led.close()


def test_ledger_pid_guard_rejects_stragglers_after_reuse():
    """A stamp/ack carrying the OLD owner's pid must bounce once the
    slot has been reclaimed and reallocated — the SIGKILL straggler
    shape (a worker that missed its reap writing into a recycled slot)."""
    led = CritPathLedger(1, slots=1)
    try:
        s1 = led.alloc(7, 0, wire_t0_ns=1)
        assert s1 == 0
        led.abandon(s1)  # reclaim (reaper path)
        s2 = led.alloc(8, 0, wire_t0_ns=1)
        assert s2 == 0  # same physical slot, new owner
        led.stamp(s2, SEG_ENQUEUE, 8000, 8007, pid=7)  # straggler: dropped
        led.ack(s2, pid=7)  # straggler ack: dropped
        blk = led.read_slot(0)
        assert int(blk[_OFF_STATE]) == _ST_OPEN  # still the new owner's
        assert int(blk[_OFF_N_D]) == 0
        led.stamp(s2, SEG_ENQUEUE, 8000, 8007, pid=8)  # owner: lands
        blk = led.read_slot(0)
        assert int(blk[_OFF_N_D]) == 1
    finally:
        led.close()


def test_stale_open_slot_reclaimed_no_stuck_timeline():
    """An OPEN slot whose owner vanished (no ack will ever come) must be
    swept back to FREE by the stitcher's reclaim pass — timelines cannot
    wedge the ledger."""
    led = CritPathLedger(1, slots=4)
    st = CritPathStitcher(led, queue_capacity=4, reclaim_age_s=0.05)
    try:
        slot = led.alloc(99, 0, wire_t0_ns=time.perf_counter_ns())
        assert slot >= 0
        assert st.stitch() == 0  # too young: untouched
        assert led.state(slot) == _ST_OPEN
        time.sleep(0.1)
        st.stitch()
        assert st.reclaimed == 1
        assert led.state(slot) == _ST_FREE
        assert led.alloc(100, 0, wire_t0_ns=1) >= 0  # slot usable again
    finally:
        led.close()


# -- conservation over randomized fan-out runs --------------------------


def _mp_run(n_payloads, spans_each, workers, seed, kill_widx=None):
    """Drive the real fan-out tier with critpath armed; returns the
    stitched waterfall + raw counters."""
    from tests.fixtures import lots_of_spans
    from tests.test_mp_ingest import make_store
    from zipkin_tpu.model.json_v2 import encode_span_list
    from zipkin_tpu.tpu.mp_ingest import MultiProcessIngester

    store = make_store()
    # distinct seeds => randomized service/name mixes per payload
    ps = []
    for i in range(n_payloads):
        spans = lots_of_spans(
            spans_each, seed=seed + i, services=8 + (seed + i) % 7,
            span_names=16 + (seed + 2 * i) % 9,
        )
        ps.append(encode_span_list(spans))
    ing = MultiProcessIngester(
        store, workers=workers, queue_depth=8, critpath_slots=64
    )
    try:
        for i, p in enumerate(ps):
            cp.WIRE_T0_NS.set(time.perf_counter_ns())
            ing.submit(p)
            if kill_widx is not None and i == 0:
                ing._procs[kill_widx].kill()
                deadline = time.monotonic() + 30
                while (
                    ing._maps[kill_widx] is not None
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.05)
                assert ing._maps[kill_widx] is None, "never reaped"
        ing.drain()
        ing.critpath.stitch()
        wf = ing.critpath.waterfall()
        counters = ing.critpath.counters()
        ledger_states = [
            ing._cp_ledger.state(s) for s in range(ing._cp_ledger.slots)
        ]
        return wf, counters, ledger_states
    finally:
        ing.close()


@pytest.mark.parametrize("workers,seed", [(1, 11), (2, 23)])
def test_conservation_segments_sum_to_wall(workers, seed):
    from zipkin_tpu import native

    if not native.available():
        pytest.skip("native codec unavailable")
    wf, counters, _ = _mp_run(4, 512, workers, seed)
    assert wf["timelines"] >= 1
    assert counters["critpathTimelines"] == wf["timelines"]
    # the conservation property: per-chunk critical-path segments sum
    # to the measured wire->ack wall within the 10% bound at p50
    assert abs(wf["conservation"]["p50"] - 1.0) <= 0.10, wf["conservation"]
    # wire-to-durable is a real, nonzero number distinct from any stage
    assert wf["wireToDurable"]["count"] == wf["timelines"]
    assert wf["wireToDurable"]["p99Us"] >= wf["wireToDurable"]["p50Us"] > 0
    # the decomposition names both sides of the queueing split
    svc = wf["queueWaitVsService"]["serviceUs"]
    wait = wf["queueWaitVsService"]["waitUs"]
    assert svc > 0
    assert 0.0 <= wf["queueWaitVsService"]["waitFraction"] <= 1.0
    assert wait >= 0
    # every folded chunk's worker stages made it across the process
    # boundary: parse must appear in the segment table
    segs = {row["segment"]: row for row in wf["segments"]}
    assert segs["parse"]["count"] >= wf["timelines"]
    assert segs["device_feed"]["kind"] == "service"


def test_sigkilled_worker_slots_reclaimed_no_stuck_timelines():
    """Randomized fan-out run with a SIGKILL'd worker: its orphaned
    ledger slots are abandoned/reclaimed (not left OPEN forever), the
    drain completes, and the surviving timelines still conserve."""
    from zipkin_tpu import native

    if not native.available():
        pytest.skip("native codec unavailable")
    wf, counters, states = _mp_run(6, 256, 2, 31, kill_widx=0)
    # nothing left open or done: every slot either folded (DONE ->
    # released) or was abandoned when the reaper refed its payload
    assert all(s == _ST_FREE for s in states), states
    # the kill shows up in the books: refed payloads' timelines are
    # abandoned, not silently folded with half a worker's intervals
    assert counters["critpathAbandoned"] >= 1
    if wf["timelines"]:
        assert abs(wf["conservation"]["p50"] - 1.0) <= 0.10


# -- Little's-law gauges (ISSUE 16 satellite: idle-stitch zeroing) -------


def test_littles_law_gauges_nonzero_after_driven_load():
    """Regression for the r08 run's all-zero gauge columns: waterfall()
    runs its own stitch, and when that stitch folds nothing (the load
    just drained — the report path's usual timing) the old code zeroed
    all four gauges before reading them. Post-fix, the gauges keep the
    last real window until the staleness horizon, so a report taken
    right after a drained run must show the load that just ran."""
    from zipkin_tpu import native

    if not native.available():
        pytest.skip("native codec unavailable")
    wf, counters, _ = _mp_run(4, 512, 2, 31)
    # _mp_run stitched once (folding the payloads) and waterfall()
    # stitched AGAIN on an idle tracer — the regression's exact shape
    ll = wf["littlesLaw"]
    assert ll["lambdaCps"] > 0, ll
    assert ll["littleL"] > 0, ll
    assert ll["workerOccupancy"] > 0, ll
    assert counters["critpathLambdaCps"] > 0


def test_gauges_survive_idle_stitches_until_stale_horizon():
    """Unit shape of the fix: an idle stitch inside the horizon must
    not touch the gauges; one past the horizon must zero them (a stale
    saturation reading may not hold an SLO alert forever)."""
    led = CritPathLedger(1, 8)
    try:
        st = CritPathStitcher(led, queue_capacity=4, gauge_stale_s=3600.0)
        st.lambda_cps = 123.0
        st.little_l = 4.5
        st.worker_occupancy = 0.5
        st.queue_saturation = 0.25
        st._gauges_at_ns = time.perf_counter_ns()
        st.stitch()  # idle: nothing to fold, horizon not reached
        assert st.lambda_cps == 123.0
        assert st.little_l == 4.5
        assert st.worker_occupancy == 0.5
        assert st.queue_saturation == 0.25
        # back-date the last real window past the horizon
        st._gauges_at_ns = time.perf_counter_ns() - int(7200 * 1e9)
        st.stitch()
        assert st.lambda_cps == 0.0
        assert st.little_l == 0.0
        assert st.worker_occupancy == 0.0
        assert st.queue_saturation == 0.0
    finally:
        led.close()
