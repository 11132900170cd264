"""PR 35: a window whose length can be chosen from one round of long runs, a
set-up that says where its seconds go and has one mode, and a stage that took
no time reads 0.0. No JAX: the set-up runs against a stub that keeps the
program's two maintenance counts and a publisher of the read mirror that the
case drives (beside ``test_client_faults.py``'s stub, whose POST handling it
inherits)."""

import threading
import time

import pytest

import client as client_mod
import measures
import run as run_mod
from launcher import Http
from test_client_faults import CONFIG, POST_SPANS, Stub, workload

STEPS = ["spmd_step", "spmd_step_flush", "spmd_step_rollup",
         "spmd_step_flush_rollup"]
WARM = {"step_programs": STEPS, "flush_by": "/flush"}
PER = 4  # CONFIG: a roll-up every 4 POSTs, a flush every 4


# ---- measures.sub_window --------------------------------------------------

def record(seconds: float = 6.0) -> dict:
    """A dozen sends of one sender by hand, 100 spans each, the window open
    at t = 100: one every half second, answered 0.2 s later; number 7 waits
    1.5 s (so 8 is due at 105.0), number 10 fails (no answer)."""
    sends, due = [], 100.0
    for n in range(12):
        took = 1.5 if n == 7 else 0.2
        s = {"n": n, "phase": "window", "due": due, "retries": 0,
             "status": 202, "acked": due + took, "ended": due + took}
        if n == 10:
            s.update(status="TimeoutError: timed out", acked=None)
        sends.append(s)
        due += max(0.5, took)
    snap = {"device_totals": {}, "program_calls": {}}
    return {"t0": 100.0, "t_close": sends[-1]["ended"], "t_drained":
            sends[-1]["ended"] + 0.4, "seconds": seconds, "post_spans": 100,
            "drain_limit_s": 90.0, "sends": sends, "before": snap,
            "after": snap}


def test_sub_window_at_the_runs_own_length_is_the_run():
    r = record()
    whole, _ = measures.end_to_end(r, 1.0)
    for n_s in (6.0, 7.5, 1000.0):
        assert measures.sub_window(r, n_s) == {
            k: v for k, v in whole.items() if k != "setup_s"}


def test_sub_window_by_hand():
    r = record()
    # first 3 s: numbers 0-5 (due 100.0 ... 102.5), the last answered at
    # 102.7, drained 0.4 s later as the whole run was: 600 spans in 3.1 s
    cut = measures.sub_window(r, 3.0)
    assert cut["ingest_spans_per_s"] == pytest.approx(600 / 3.1)
    assert cut["ack_p95_ms"] == pytest.approx(200.0)
    # first 5 s: numbers 0-7; 7 (due 103.5) is answered at 105.0, which closes
    # the window; 800 spans in 5.4 s; seven acks of 200 ms and one of 1,500:
    # the 95th percentile lies 0.65 of the way from the 7th to the 8th
    cut = measures.sub_window(r, 5.0)
    assert cut["ingest_spans_per_s"] == pytest.approx(800 / 5.4)
    assert cut["ack_p95_ms"] == pytest.approx(200.0 + 0.65 * 1300.0)


def test_sub_window_counts_a_failed_batch_to_the_drain_limit():
    r = record()
    # the whole run: 11 applied of 12, and the one that failed waits from
    # its due (106.0) to the close (106.7) and the drain limit past it
    whole = measures.sub_window(r, 6.0)
    assert whole["ingest_spans_per_s"] == pytest.approx(1100 / 7.1)
    cut = measures.sub_window(r, 6.2)  # beyond the run: still the run
    assert cut == whole
    # a cut that still holds it: numbers 0-10, closed when 10 ended
    r2 = dict(r, seconds=8.0)
    cut = measures.sub_window(r2, 6.1)
    acks = sorted([200.0] * 9 + [1500.0, (106.2 + 90.0 - 106.0) * 1000.0])
    assert cut["ack_p95_ms"] == pytest.approx(measures.pctl(acks, 95))
    assert cut["ingest_spans_per_s"] == pytest.approx(1000 / 6.6)


# ---- measures.setup_parts -------------------------------------------------

def test_the_setups_parts_sum_to_setup_s():
    r = record()
    r["sends"] = [dict(s, phase="fill") for s in r["sends"][:5]] + r["sends"]
    r["marks"] = {"health": 71.5, "warm": 88.25, "fill": 88.5, "pin": 97.0}
    r["publish_waits"] = [{"s": 0.75, "looked": True},
                          {"s": 15.0, "looked": False}]
    t_start = 60.0
    parts = measures.setup_parts(r, t_start)
    names = ("boot_s", "warm_s", "fill_s", "pin_s", "sync_s")
    assert [parts[n] for n in names] == [11.5, 16.75, 0.25, 8.5, 3.0]
    assert sum(parts[n] for n in names) == r["t0"] - t_start  # = setup_s
    assert parts["after_publish_s"] == 15.75
    assert parts["after_publish_missed"] == 1
    assert parts["setup_batches"] == 5


# ---- readers/stage_delta.py -----------------------------------------------

def snap(count: int, sum_us: float) -> dict:
    return {"stages": {"publish_queue_drain": {"count": count,
                                               "sumUs": sum_us}},
            "counters": {"spans": 0}}


@pytest.mark.parametrize("after, want", [
    (snap(5, 900.0), 0.0),   # two more publishes that drained for 0 ms
    (snap(3, 900.0), None),  # the recorder saw nothing of the stage
    (snap(5, 4900.0), 2.0),  # 4,000 us over two publishes, in ms
])
def test_a_stage_that_took_no_time_reads_zero(after, want):
    ctx = {"result": {"before": snap(3, 900.0), "after": after},
           "window_s": 30.0}
    assert run_mod.layer_value("publish_drain_ms", ctx) == want


# ---- the set-up against a publisher that the case drives -------------------

class Program(Stub):
    """The stub with the program's step variants (a flush is folded into the
    POST that would overfill the digest buffer, a roll-up into the one that
    would overfill the half-ring), ``/flush`` and the mirror's publisher: a
    thread that looks every ``tick_s`` and backs off, publishes (emptying the
    buffer) or skips, as ``tpu/mirror.py`` does. Without ``tick_s`` it has no
    clock: it looks whenever the client asks for the counters twice with
    nothing in between, which is a client that waits for it, so that no case
    hangs on this machine's timing. ``backoff_s``: how long it backs off
    after each publish; ``spoil_at``: POST numbers right after which it
    publishes, whatever its ticker says."""

    def __init__(self, tick_s=None, backoff_s=0.0, spoil_at=()) -> None:
        self.calls = dict.fromkeys(STEPS, 0)
        self.pend = self.since_roll = self.posted = self.published_at = 0
        self.mirror = {"mirrorPublishes": 0, "mirrorPublishSkips": 0,
                       "mirrorPublishBackoffs": 0}
        self.backoff_s, self.spoil_at = backoff_s, set(spoil_at)
        self.done_at, self.polled = 0.0, False
        self.state = threading.Lock()
        self.stop = threading.Event()
        super().__init__()
        self.ticker = threading.Thread(target=self.tick, args=(tick_s,))
        if tick_s:
            self.ticker.start()

    applied = property(lambda self: self.posted * POST_SPANS,
                       lambda self, spans: self.step(spans // POST_SPANS))

    def tick(self, tick_s: float) -> None:
        while not self.stop.wait(tick_s):
            with self.state:
                self.look()

    def look(self, force: bool = False) -> None:
        if not force and time.monotonic() - self.done_at < self.backoff_s:
            self.mirror["mirrorPublishBackoffs"] += 1
        elif self.posted == self.published_at:
            self.mirror["mirrorPublishSkips"] += 1
        else:
            self.mirror["mirrorPublishes"] += 1
            self.pend, self.published_at = 0, self.posted
            self.done_at = time.monotonic()

    def step(self, posted: int) -> None:
        """The stub's ``applied += POST_SPANS``: one POST more is applied."""
        with self.state:
            self.polled = False
            while self.posted < posted:
                flush, roll = self.pend + 1 > PER, self.since_roll + 1 > PER
                self.calls[STEPS[flush + 2 * roll]] += 1
                self.pend = 1 if flush else self.pend + 1
                self.since_roll = 1 if roll else self.since_roll + 1
                self.posted += 1
                if self.posted in self.spoil_at:
                    self.look(force=True)

    def get(self, path: str):
        with self.state:
            if path == "/flush":
                self.pend = 0
            polled, self.polled = self.polled, path == "/api/v2/tpu/counters"
            if self.polled:
                if polled and not self.ticker.ident:
                    self.look()
                return 200, dict(self.mirror)
            if path == "/api/v2/tpu/statusz":
                return 200, {"device": {"programs": {
                    n: {"calls": c} for n, c in self.calls.items()}}}
        return super().get(path)

    def close(self) -> None:
        self.stop.set()
        if self.ticker.ident:
            self.ticker.join()
        super().close()


def set_up(stub: Program) -> dict:
    spec = {"port": stub.port, "seed": 2147483777, "seconds": 0.1, "out": "",
            "config": dict(CONFIG, warm=WARM), "workload": workload(1)}
    c = client_mod.Client(spec)
    try:
        rounds, missing, spoiled = c.warm_steps()
        c.pin_phase()
    finally:
        stub.close()
    return {"rounds": rounds, "missing": missing, "spoiled": spoiled,
            "sent": c.next_n, "since_roll": stub.since_roll,
            "pend": stub.pend, "waits": c.publish_waits}


@pytest.fixture
def small_timeouts(monkeypatch):
    monkeypatch.setattr(client_mod, "SETUP_TIMEOUT_S", 5.0)


def test_a_skip_ends_the_wait_for_the_publisher(small_timeouts):
    """The publish that the last batch made due has run before the wait
    begins, so no publish can come: the publisher's next look is a skip, and
    the wait ends with it, a tick later and not at its limit of 15 s."""
    stub = Program(tick_s=0.3)
    try:
        http = Http(stub.port, 5.0)
        c = client_mod.Client({
            "port": stub.port, "seed": 1, "seconds": 0.1, "out": "",
            "config": dict(CONFIG, warm=WARM), "workload": workload(1)})
        c.post_applied(2)
        while not stub.mirror["mirrorPublishes"]:
            time.sleep(0.01)  # the publish, before the wait begins
        t0 = time.monotonic()
        c.after_publish(http)
        assert time.monotonic() - t0 < 2.0
        assert c.publish_waits[-1]["looked"] is True
        assert stub.mirror["mirrorPublishes"] == 1  # it was a skip
        http.close()
    finally:
        stub.close()


def test_a_publisher_that_backs_off_is_waited_out_not_to_the_limit(
        small_timeouts):
    stub = Program(tick_s=0.1, backoff_s=0.8)
    run = set_up(stub)
    assert run["missing"] == []
    assert stub.mirror["mirrorPublishBackoffs"] > 0
    # a wait before the first publish of all is not entered
    assert any(w["looked"] for w in run["waits"])
    assert max(w["s"] for w in run["waits"]) < 3.0


@pytest.mark.parametrize("spoil_at, spoiled, sent", [
    ((), 0, 22), ((6,), 1, 22), ((11,), 1, 22), ((6, 15), 2, None)])
def test_the_setup_ends_alike_whichever_way_the_warm_up_went(
        small_timeouts, spoil_at, spoiled, sent):
    """A publish inside the burst that is to fill the buffer spoils the
    round: the warm-up tells it from a round without progress and goes round
    again, and the set-up still ends on the floor's count of batches (one
    spoiled round is in it; past it, on the next count in the same phase),
    half a roll-up period after the last roll-up, every variant reached."""
    run = set_up(Program(spoil_at=spoil_at))
    assert run["missing"] == [] and run["spoiled"] == spoiled
    floor = 2 * (PER + PER) + PER + 2
    assert floor == 22
    assert run["sent"] == sent or (sent is None and run["sent"] > floor)
    assert run["since_roll"] == PER // 2 and run["pend"] == 0


def test_hints_that_name_nothing_cost_one_round(small_timeouts):
    stub = Program()
    stub.calls = {"another_step": 0}
    stub.step = lambda posted: setattr(stub, "posted", posted)
    run = set_up(stub)
    assert run["rounds"] == 2 and run["spoiled"] == 0
    assert run["missing"] == STEPS
