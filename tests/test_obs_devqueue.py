"""The completion clock of the device observatory (obs/device.py,
``DeviceQueue``): when each dispatched program had run, and from that its
device time, its wait in the device's queue and how far the host is ahead.

(a) the arithmetic, on fake tokens whose readiness the test controls;
(b) through a real ShardedAggregator on the CPU mesh;
(c) ``obs.span``;
(d) one profiled CPU ingest: the host spans stand in the trace.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import threading

import jax
import numpy as np
import pytest

from tests.fixtures import lots_of_spans
from zipkin_tpu import obs
from zipkin_tpu.obs import device as obs_device
from zipkin_tpu.obs.device import OBSERVATORY, DeviceObservatory, DeviceQueue
from zipkin_tpu.obs.recorder import StageRecorder
from zipkin_tpu.tpu.state import AggConfig
from zipkin_tpu.tpu.store import TpuStorage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WAIT_S = 20.0

# -- (a) arithmetic -------------------------------------------------------


class FakeToken:
    """Ready when the test says so."""

    def __init__(self, fails: bool = False) -> None:
        self.entered = threading.Event()
        self.ready = threading.Event()
        self.fails = fails

    def block_until_ready(self):
        self.entered.set()
        assert self.ready.wait(WAIT_S), "the test never released the token"
        if self.fails:
            raise RuntimeError("Array has been deleted")


class FakeNow:
    def __init__(self) -> None:
        self.value = 0

    def __call__(self) -> int:
        return self.value


class Stats:
    """What the queue touches of a ProgramStats."""

    def __init__(self, name: str, step=None) -> None:
        self.name, self.step = name, step
        self.done = self.device_ns = self.queue_wait_ns = 0
        self.max_device_ns = 0


def finish(queue: DeviceQueue, now: FakeNow, token: FakeToken, t: int,
           done_before: int) -> None:
    """The device finishes ``token``'s program at ``t``: the clock's thread
    must be waiting on it, and has booked it when this returns."""
    assert token.entered.wait(WAIT_S), "the clock never reached the token"
    now.value = t
    token.ready.set()
    with queue._cond:
        assert queue._cond.wait_for(
            lambda: len(queue._recent) + queue.dropped > done_before, WAIT_S)


def play(schedule, **kw):
    """``schedule`` = [(program stats, lanes, t_enq, t_done)] in dispatch
    order, times in ns on a fake clock; pushes and completions happen in
    time order. -> (queue, records of the ring)."""
    now = FakeNow()
    queue = DeviceQueue(now=now, **kw)
    tokens = [FakeToken() for _ in schedule]
    events = sorted(
        [(t_enq, 0, k) for k, (_, _, t_enq, _) in enumerate(schedule)]
        + [(t_done, 1, k) for k, (_, _, _, t_done) in enumerate(schedule)])
    done = 0
    for t, is_done, k in events:
        if is_done:
            finish(queue, now, tokens[k], t, done)
            done += 1
        else:
            stats, lanes, t_enq, _ = schedule[k]
            now.value = t
            assert queue.push(stats, tokens[k], t_enq, lanes=lanes, seq=k + 1)
    assert queue.wait_idle(WAIT_S)
    return queue, queue.status()["recent"]


def test_back_to_back_entries_wait_for_the_one_before():
    step = Stats("spmd_step", "plain")
    # three steps handed over at 0, 10, 20; the device needs 100 each
    queue, recent = play([(step, 8, 0, 100), (step, 8, 10, 200),
                          (step, 8, 20, 300)])
    assert [(r["startNs"] - r["enqNs"], r["doneNs"] - r["startNs"])
            for r in recent] == [(0, 100), (90, 100), (180, 100)]
    assert [r["seq"] for r in recent] == [1, 2, 3]
    assert step.done == 3 and step.device_ns == 300
    assert step.queue_wait_ns == 270 and step.max_device_ns == 100
    c = queue.counters()
    assert c["stepPlainDone"] == 3 and c["stepFusedDone"] == 0
    # lanes ahead at each step's enqueue: 0, then 8, then 16
    assert c["stepLanesAheadSum"] == 24
    assert c["deviceQueueLanesMax"] == 24
    assert c["deviceQueueSteps"] == 0 and c["deviceQueueLanes"] == 0


def test_an_idle_gap_is_nobodys_time():
    step = Stats("spmd_step", "plain")
    queue, recent = play([(step, 8, 0, 100), (step, 8, 500, 560)])
    assert [(r["startNs"] - r["enqNs"], r["doneNs"] - r["startNs"])
            for r in recent] == [(0, 100), (0, 60)]
    assert step.queue_wait_ns == 0 and step.device_ns == 160


def test_per_program_sums_and_the_step_split():
    plain, fused = Stats("spmd_step", "plain"), Stats("spmd_step_rollup",
                                                      "fused")
    read = Stats("spmd_card")
    queue, _ = play([(plain, 8, 0, 10), (fused, 8, 1, 110), (read, 0, 2, 115),
                     (plain, 8, 3, 125)])
    assert (plain.done, plain.device_ns) == (2, 20)
    assert (fused.done, fused.device_ns) == (1, 100)
    assert (read.done, read.device_ns, read.queue_wait_ns) == (1, 5, 108)
    c = queue.counters()
    assert (c["stepPlainDone"], c["stepFusedDone"]) == (2, 1)
    # whole microseconds: 20 ns and 100 ns are none
    assert c["stepPlainDeviceUs"] == 0 and c["stepFusedDeviceUs"] == 0
    assert queue.step_device_ns == {"plain": 20, "fused": 100}
    # the read is no step: its wait is its program's, not the steps'
    assert queue.step_queue_wait_ns == 0 + 9 + 112


def test_a_fence_resolves_with_the_entry_before_it():
    now = FakeNow()
    queue = DeviceQueue(now=now)
    step = Stats("spmd_step", "plain")
    idle = queue.fence()  # nothing queued: resolved as it enters
    assert idle.wait(0) == 0.0 and queue.thread is None
    first, second = FakeToken(), FakeToken()
    queue.push(step, first, 0, lanes=8)
    queue.push(step, second, 5, lanes=8)
    now.value = 7
    fence = queue.fence()
    assert fence.wait(0.05) is None  # two steps are ahead of it
    finish(queue, now, first, 1_000_000_000, 0)
    assert fence.wait(0.05) is None
    finish(queue, now, second, 3_000_000_007, 1)
    assert fence.wait(WAIT_S) == 3.0  # entered at 7 ns, resolved at 3 s + 7
    assert queue.wait_idle(WAIT_S)
    assert len(queue.status()["recent"]) == 2  # a fence is no record


def test_beyond_capacity_tokens_are_dropped_and_counted():
    now = FakeNow()
    queue = DeviceQueue(capacity=4096, now=now)
    step = Stats("spmd_step", "plain")
    tokens = [FakeToken() for _ in range(4096)]
    for k, tok in enumerate(tokens):
        assert queue.push(step, tok, k, lanes=1)
    over = FakeToken()
    assert not queue.push(step, over, 5000, lanes=1)
    assert queue.fence() is None
    c = queue.counters()
    assert c["deviceQueueDropped"] == 2 and c["deviceQueueSteps"] == 4096
    assert queue.status()["depth"] == 4096
    now.value = 10_000
    for tok in tokens:
        tok.ready.set()
    assert queue.wait_idle(WAIT_S)
    # the entry pushed after the hole only anchors the chain: the dropped
    # program's time must not be booked to it
    after = FakeToken()
    after.ready.set()
    queue.push(step, after, 20_000, lanes=1)
    assert queue.wait_idle(WAIT_S)
    assert step.done == 4096 and queue.counters()["deviceQueueDropped"] == 3
    assert not over.entered.is_set()


def test_a_token_that_raises_is_a_hole():
    now = FakeNow()
    queue = DeviceQueue(now=now)
    step = Stats("spmd_step", "plain")
    bad, anchor, good = FakeToken(fails=True), FakeToken(), FakeToken()
    queue.push(step, bad, 0)
    finish(queue, now, bad, 50, 0)
    queue.push(step, anchor, 60)
    finish(queue, now, anchor, 100, 1)
    queue.push(step, good, 110)
    finish(queue, now, good, 150, 2)
    assert step.done == 1 and step.device_ns == 40
    assert queue.counters()["deviceQueueDropped"] == 2


def test_the_ring_keeps_the_last_256_records():
    step = Stats("spmd_step", "plain")
    queue = DeviceQueue()
    ready = FakeToken()
    ready.ready.set()
    for k in range(300):
        queue.push(step, ready, k, seq=k + 1)
    assert queue.wait_idle(WAIT_S)
    recent = queue.status()["recent"]
    assert len(recent) == 256
    assert [r["seq"] for r in recent] == list(range(45, 301))
    assert set(recent[0]) == {"seq", "program", "lanes", "enqNs", "startNs",
                              "doneNs"}
    assert step.done == 300


def test_on_done_gets_the_device_seconds():
    now = FakeNow()
    queue = DeviceQueue(now=now)
    seen = []
    tok = FakeToken()
    queue.push(Stats("spmd_rollup"), tok, 0, on_done=seen.append)
    finish(queue, now, tok, 2_000_000_000, 0)
    assert seen == [2.0]


def test_observatory_off_is_a_pass_through_with_no_thread():
    off = DeviceObservatory(enabled=False)
    calls = []

    def program(x):
        calls.append(x)
        return x

    fn = off.wrap("toy_state", program, token="state", step="plain")
    assert fn(3) == 3 and calls == [3]  # no marker reads the int "state"
    assert off.queue.thread is None
    assert off.queue.counters() == {
        "stepPlainDone": 0, "stepPlainDeviceUs": 0, "stepFusedDone": 0,
        "stepFusedDeviceUs": 0, "stepQueueWaitUs": 0, "stepLanesAheadSum": 0,
        "deviceQueueSteps": 0, "deviceQueueLanes": 0,
        "deviceQueueLanesMax": 0, "deviceQueueDropped": 0,
    }


def test_an_output_token_is_the_smallest_leaf():
    on = DeviceObservatory(enabled=True, analysis=False)

    @jax.jit
    def read(x):
        return {"big": x * 2, "packed": x[:2].sum()}

    fn = on.wrap("toy_read", read)
    fn(jax.numpy.arange(64.0))
    assert on.queue.wait_idle(WAIT_S)
    st = on.programs()["toy_read"]
    assert st["done"] == 1 and st["deviceMs"] >= 0.0
    assert {"done", "deviceMs", "queueWaitMs", "maxDeviceMs", "callWallMs",
            "maxCallMs"} <= set(st)
    assert on.status()["queue"]["recent"][0]["program"] == "toy_read"


# -- (b) a real aggregator on the CPU mesh -----------------------------------

SMALL = dict(max_services=128, max_keys=512, hll_precision=10,
             digest_centroids=32, ring_capacity=1 << 12,
             digest_buffer=1 << 11)
NEW_COUNTERS = (
    "stepPlainDone", "stepPlainDeviceUs", "stepFusedDone",
    "stepFusedDeviceUs", "stepQueueWaitUs", "stepLanesAheadSum",
    "deviceQueueSteps", "deviceQueueLanes", "deviceQueueLanesMax",
    "deviceQueueDropped",
)


@pytest.fixture
def observatory_on():
    was = OBSERVATORY.enabled
    OBSERVATORY.set_enabled(True)
    yield OBSERVATORY
    OBSERVATORY.set_enabled(was)


def small_store() -> TpuStorage:
    return TpuStorage(config=AggConfig(**SMALL), pad_to_multiple=256)


def test_counters_are_there_from_boot(observatory_on):
    store = small_store()
    counters = store.ingest_counters()
    for name in NEW_COUNTERS:
        assert name in counters, name
    status = observatory_on.status()
    assert {"depth", "depthMax", "steps", "lanes", "lanesMax", "dropped",
            "capacity", "recent"} <= set(status["queue"])


def test_every_step_is_seen_done_and_split_by_variant(observatory_on):
    store = small_store()
    agg = store.agg
    agg.block_until_ready()
    before = store.ingest_counters()
    stages0 = obs.RECORDER.snapshot()
    spans = lots_of_spans(300, seed=7)
    fused_due = 0
    for _ in range(14):
        lanes = 256  # what 300 spans pad to on each of the 8 shards
        fused_due += (
            agg._pend_lanes + lanes > agg.config.digest_buffer
            or agg._lanes_since_rollup + lanes > agg.config.rollup_segment)
        store.accept(spans).execute()
    agg.block_until_ready()
    after = store.ingest_counters()
    d = {k: after[k] - before[k] for k in NEW_COUNTERS + ("batches",)}
    assert d["batches"] == 14
    assert d["stepPlainDone"] + d["stepFusedDone"] == d["batches"]
    assert d["stepFusedDone"] == fused_due >= 1
    assert d["stepPlainDeviceUs"] > 0 and d["stepFusedDeviceUs"] > 0
    assert after["deviceQueueSteps"] == 0 and after["deviceQueueLanes"] == 0
    assert after["deviceQueueLanesMax"] >= 256
    assert after["deviceQueueDropped"] == before["deviceQueueDropped"]
    # the last roll-up-fused step's device time is the maintenance gauge,
    # and the stage `rollup` holds the same observation
    rollups = [r for r in observatory_on.status()["queue"]["recent"]
               if r["program"].endswith("_rollup")]
    assert rollups
    last = rollups[-1]
    assert after["ctxMaintenanceMs"] == pytest.approx(
        (last["doneNs"] - last["startNs"]) / 1e6)
    stages1 = obs.RECORDER.snapshot()
    assert (stages1.stage("rollup").count - stages0.stage("rollup").count
            == len([r for r in rollups if r["seq"] > before["batches"]]))
    assert (stages1.stage("ingest_lock_wait").count
            - stages0.stage("ingest_lock_wait").count) == 14
    # the records carry the batch number the host counters give the step
    steps = [r for r in observatory_on.status()["queue"]["recent"]
             if r["program"].startswith("spmd_step")]
    assert steps[-1]["seq"] == after["batches"]
    assert steps[-1]["lanes"] == 256


def run_steps(enabled: bool):
    OBSERVATORY.set_enabled(enabled)
    store = small_store()
    for seed in range(9):
        store.accept(lots_of_spans(300, seed=seed)).execute()
    store.agg.rollup_now()
    store.agg.flush_now()
    store.agg.block_until_ready()
    return [np.asarray(leaf) for leaf in store.agg.state]


def test_state_is_bit_identical_with_the_clock_on_and_off():
    """The marker reads one word of the new state and the next step donates
    that state: nothing of it may be kept, touched or changed."""
    was = OBSERVATORY.enabled
    try:
        off = run_steps(False)
        threads_off = {t.name for t in threading.enumerate()}
        on = run_steps(True)
    finally:
        OBSERVATORY.set_enabled(was)
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert OBSERVATORY.queue.counters()["deviceQueueDropped"] == 0
    # no test before this one may have started it for the check to bite;
    # either way the thread is the one the observatory owns, or none
    assert "device-clock" not in threads_off or OBSERVATORY.queue.thread


def test_no_donated_buffer_is_kept_alive(observatory_on):
    store = small_store()
    store.accept(lots_of_spans(300, seed=1)).execute()
    old_leaves = list(store.agg.state)
    store.accept(lots_of_spans(300, seed=2)).execute()
    store.agg.block_until_ready()
    # the step donated the old state: every leaf of it is gone, the
    # marker's read of one of them notwithstanding
    assert all(leaf.is_deleted() for leaf in old_leaves)
    assert observatory_on.queue.counters()["deviceQueueDropped"] == 0


def test_the_state_token_is_a_transfer_not_a_program(observatory_on):
    """A jitted marker takes a slot in the runtime's window of programs in
    flight and halves the host's run-ahead (PERF.md section 6, PR 26): the
    token is a copy of one word into pinned host memory."""
    store = small_store()
    stats = store.agg._step.program_stats
    token = obs_device._state_token(stats, store.agg.state)
    assert token.size == 1 and token.sharding.memory_kind == "pinned_host"
    assert not token.is_deleted()
    leaf = jax.tree_util.tree_leaves(store.agg.state)[stats._token_leaf]
    assert leaf.size == store.agg.n_shards  # one word a shard
    store.accept(lots_of_spans(300, seed=4)).execute()  # donates the state
    store.agg.block_until_ready()
    assert leaf.is_deleted() and not token.is_deleted()
    assert int(np.asarray(token).reshape(-1)[0]) == 0  # pend_pos at boot


def test_publish_hold_and_drain_are_recorded(observatory_on):
    store = small_store()
    store.accept(lots_of_spans(300, seed=3)).execute()
    s0 = obs.RECORDER.snapshot()
    assert store.mirror.publish(force=True)
    s1 = obs.RECORDER.snapshot()
    hold = s1.stage("publish_lock_hold")
    drain = s1.stage("publish_queue_drain")
    whole = s1.stage("mirror_publish")
    assert hold.count - s0.stage("publish_lock_hold").count == 1
    assert drain.count - s0.stage("publish_queue_drain").count == 1
    assert whole.count - s0.stage("mirror_publish").count == 1
    d_hold = hold.sum_us - s0.stage("publish_lock_hold").sum_us
    d_drain = drain.sum_us - s0.stage("publish_queue_drain").sum_us
    d_whole = whole.sum_us - s0.stage("mirror_publish").sum_us
    assert d_drain <= d_hold <= d_whole + 1


def test_publish_with_the_observatory_off_enters_no_fence():
    was = OBSERVATORY.enabled
    OBSERVATORY.set_enabled(False)
    try:
        store = small_store()
        store.accept(lots_of_spans(300, seed=3)).execute()
        s0 = obs.RECORDER.snapshot()
        assert store.mirror.publish(force=True)
        s1 = obs.RECORDER.snapshot()
    finally:
        OBSERVATORY.set_enabled(was)
    assert (s1.stage("publish_lock_hold").count
            - s0.stage("publish_lock_hold").count) == 1
    assert (s1.stage("publish_queue_drain").count
            == s0.stage("publish_queue_drain").count)


# -- (c) obs.span ---------------------------------------------------------------


def test_span_records_like_record():
    rec = StageRecorder(enabled=True)
    with rec.span("parse", n=3) as sp:
        pass
    rec.record("parse", sp.t1 - sp.t0)
    st = rec.snapshot().stage("parse")
    assert st.count == 2 and sp.t1 >= sp.t0 > 0
    assert st.sum_us == 2 * int((sp.t1 - sp.t0) * 1_000_000 + 0.5)


def test_span_that_raises_or_is_dropped_records_nothing():
    rec = StageRecorder(enabled=True)
    with pytest.raises(ValueError):
        with rec.span("parse"):
            raise ValueError("no")
    with rec.span("parse") as sp:
        sp.drop()
    with rec.span("pack") as sp:
        with sp.child("read", key="k"):
            pass
    snap = rec.snapshot()
    assert snap.stage("parse").count == 0 and snap.stage("pack").count == 1


def test_span_imports_no_jax_where_jax_is_not():
    code = (
        "import sys\n"
        "from zipkin_tpu import obs\n"
        "with obs.span('parse', n=1) as sp:\n"
        "    with sp.child('read', key='k'):\n"
        "        pass\n"
        "assert obs.RECORDER.snapshot().stage('parse').count == 1\n"
        "sys.exit(3 if 'jax' in sys.modules else 0)\n"
    )
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr or "obs.span pulled in jax"


def lint(tmp_path, source: str):
    from zipkin_tpu.lint import run_paths

    path = tmp_path / "case.py"
    path.write_text(source)
    return [f for f in run_paths([str(path)], root=tmp_path).findings
            if f.rule == "ZT08"]


def test_zt08_knows_the_span_shape(tmp_path):
    good = (
        "import jax\nfrom zipkin_tpu import obs\n"
        "def host():\n    with obs.span('parse', n=1):\n        pass\n"
    )
    unknown = good.replace("'parse'", "'no_such_stage'")
    dynamic = good.replace("obs.span('parse', n=1)", "obs.span(name)")
    traced = (
        "import jax\nfrom zipkin_tpu import obs\n"
        "@jax.jit\ndef step(x):\n"
        "    with obs.span('parse'):\n        return x + 1\n"
    )
    assert lint(tmp_path, good) == []
    assert any("unknown stage" in f.message for f in lint(tmp_path, unknown))
    assert any("string literal" in f.message for f in lint(tmp_path, dynamic))
    assert any("device-traced" in f.message for f in lint(tmp_path, traced))


# -- (d) the host spans stand in a profiler trace ---------------------------------


def test_profiled_ingest_has_device_dispatch_events_with_variant(
        observatory_on, tmp_path):
    store = small_store()
    spans = lots_of_spans(300, seed=5)
    store.accept(spans).execute()  # compiles outside the trace
    store.agg.block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            store.accept(spans).execute()
        store.mirror.publish(force=True)
        store.agg.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("zt."):
                    events.setdefault(ev.name.split("#")[0], []).append(
                        (plane.name, dict(ev.stats), ev.name))
    assert len(events.get("zt.device_dispatch", [])) == 3
    for plane_name, stats, raw in events["zt.device_dispatch"]:
        assert plane_name.startswith("/host:")
        text = raw + repr(stats)
        assert "variant" in text and "spmd_step" in text
        assert "lanes" in text and "seq" in text
    for name in ("zt.ingest_lock_wait", "zt.publish_lock_hold",
                 "zt.mirror_publish", "zt.publish_lock_hold.read",
                 "zt.readpack_transfer"):
        assert name in events, (name, sorted(events))
