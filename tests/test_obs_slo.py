"""SLO burn-rate watchdog (ISSUE 9): multi-window trip/clear semantics
for all three spec kinds, driven by scripted ticks on a fake clock."""

import pytest

from zipkin_tpu.obs.recorder import StageRecorder
from zipkin_tpu.obs.slo import SloSpec, SloWatchdog, default_specs
from zipkin_tpu.obs.windows import WindowedTelemetry


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class Harness:
    """Recorder + counter dict + windows sized so short=4 ticks,
    long=8 ticks — burns age out of both within one test."""

    def __init__(self, specs):
        self.rec = StageRecorder()
        self.vals = {}
        self.clock = FakeClock()
        self.win = WindowedTelemetry(
            self.rec, lambda: dict(self.vals),
            tick_s=1.0, slots=16, coarse_slots=4, coarse_factor=16,
            clock=self.clock,
        )
        self.dog = SloWatchdog(self.win, specs)

    def tick(self, n=1):
        for _ in range(n):
            self.clock.advance(1.0)
            self.win.tick(self.clock())

    def verdict(self, name):
        return next(v for v in self.dog.verdicts() if v["name"] == name)


LAT = SloSpec("q_p99", "latency", short_s=4, long_s=8, burn_threshold=2.0,
              objective=0.9, stage="query_fresh", threshold_us=1000)
RATIO = SloSpec("throttle", "ratio", short_s=4, long_s=8,
                burn_threshold=2.0, objective=0.9,
                bad="mpRejected", good="mpAccepted")
GAUGE = SloSpec("snap_age", "gauge", short_s=4, long_s=8,
                gauge="snapshotAgeS", limit=100.0)


# -- spec validation -----------------------------------------------------


def test_spec_grammar_rejects_malformed():
    with pytest.raises(ValueError):
        SloSpec("x", "nonsense")
    with pytest.raises(ValueError):
        SloSpec("x", "latency")  # no stage
    with pytest.raises(ValueError):
        SloSpec("x", "ratio", bad="b")  # no good/total
    with pytest.raises(ValueError):
        SloSpec("x", "gauge", gauge="g")  # no limit


def test_default_specs_cover_north_star():
    names = {s.name for s in default_specs()}
    assert {"ingest_wire_to_ack", "query_fresh_p99",
            "durability_wal_fsync", "backpressure_429",
            "ingest_wire_to_durable", "ingest_queue_saturation"} <= names


def test_wire_to_durable_slo_trips_and_clears():
    """The critpath stitcher feeds wire_to_durable observations through
    record_relayed (worker-measured relay: no self-span feedback); the
    default-shaped latency spec must trip on sustained slow timelines
    and clear when the fleet recovers."""
    spec = SloSpec("ingest_wire_to_durable", "latency", short_s=4,
                   long_s=8, burn_threshold=2.0, objective=0.99,
                   stage="wire_to_durable", threshold_us=5_000_000)
    h = Harness([spec])
    # healthy: chunks reach durable in ~3 ms
    for _ in range(4):
        for _ in range(20):
            h.rec.record_relayed("wire_to_durable", 0.003)
        h.tick()
    assert not h.verdict("ingest_wire_to_durable")["alert"]
    # fan-out tier backs up: half the chunks take 8 s wire->fsync
    # (bad frac 0.5, budget 0.01 -> burn 50 on both windows)
    for _ in range(8):
        for _ in range(10):
            h.rec.record_relayed("wire_to_durable", 0.003)
            h.rec.record_relayed("wire_to_durable", 8.0)
        h.tick()
    v = h.verdict("ingest_wire_to_durable")
    assert v["alert"]
    assert v["windows"]["4s"]["burn"] >= 2.0
    assert h.dog.trips == 1
    # recovery: healthy timelines age the burn out of both windows
    for _ in range(9):
        for _ in range(20):
            h.rec.record_relayed("wire_to_durable", 0.003)
        h.tick()
    assert not h.verdict("ingest_wire_to_durable")["alert"]
    assert h.dog.clears == 1


def test_queue_saturation_gauge_spec_reads_stitcher_counter():
    """The queue-saturation spec is a gauge over the stitcher-published
    critpathQueueSaturation counter: above limit trips, zeroed-on-idle
    clears (the stitcher zeroes the gauge when a stitch folds nothing)."""
    spec = SloSpec("ingest_queue_saturation", "gauge", short_s=4,
                   long_s=8, gauge="critpathQueueSaturation", limit=0.9)
    h = Harness([spec])
    h.vals["critpathQueueSaturation"] = 0.97
    h.tick()
    assert h.verdict("ingest_queue_saturation")["alert"]
    h.vals["critpathQueueSaturation"] = 0.0  # idle stitch zeroes it
    h.tick()
    assert not h.verdict("ingest_queue_saturation")["alert"]


# -- latency kind --------------------------------------------------------


def test_latency_slo_trips_on_burn_and_clears_on_recovery():
    h = Harness([LAT])
    # healthy traffic: everything far under the threshold
    for _ in range(4):
        for _ in range(20):
            h.rec.record("query_fresh", 10e-6)
        h.tick()
    assert not h.verdict("q_p99")["alert"]
    # burn: half the observations over threshold (bad frac 0.5,
    # budget 0.1 -> burn 5 >= 2 on both windows once long fills)
    for _ in range(4):
        for _ in range(10):
            h.rec.record("query_fresh", 10e-6)
            h.rec.record("query_fresh", 0.050)
        h.tick()
    v = h.verdict("q_p99")
    assert v["alert"]
    assert v["windows"]["4s"]["burn"] >= 2.0
    assert h.dog.trips == 1
    # and /prometheus shows it burning (the server's renderer)
    from zipkin_tpu.server.app import _prom_slo

    assert 'zipkin_tpu_slo_alert{slo="q_p99"} 1' in _prom_slo(h.dog.verdicts())
    # recovery: healthy ticks push the burn out of both windows
    for _ in range(9):
        for _ in range(20):
            h.rec.record("query_fresh", 10e-6)
        h.tick()
    assert not h.verdict("q_p99")["alert"]
    assert h.dog.clears == 1


def test_latency_idle_windows_do_not_burn():
    h = Harness([LAT])
    h.tick(10)  # no observations at all
    v = h.verdict("q_p99")
    assert not v["alert"]
    assert v["windows"]["4s"]["burn"] == 0.0


def test_latency_alert_holds_until_both_windows_calm():
    h = Harness([LAT])
    for _ in range(4):
        h.rec.record("query_fresh", 0.050)
        h.tick()
    assert h.verdict("q_p99")["alert"]
    # two healthy ticks: short window may calm but long still burns
    for _ in range(2):
        for _ in range(50):
            h.rec.record("query_fresh", 10e-6)
        h.tick()
    long_burn = h.verdict("q_p99")["windows"]["8s"]["burn"]
    if long_burn >= 2.0:  # hysteresis: held while long window burns
        assert h.verdict("q_p99")["alert"]


# -- ratio kind ----------------------------------------------------------


def test_ratio_slo_trips_and_clears():
    h = Harness([RATIO])
    h.vals = {"mpAccepted": 0.0, "mpRejected": 0.0}
    for _ in range(4):
        h.vals["mpAccepted"] += 100
        h.tick()
    assert not h.verdict("throttle")["alert"]
    # 50% rejects: frac 0.5 / budget 0.1 = burn 5
    for _ in range(8):
        h.vals["mpAccepted"] += 50
        h.vals["mpRejected"] += 50
        h.tick()
    v = h.verdict("throttle")
    assert v["alert"]
    assert v["windows"]["8s"]["badFraction"] == pytest.approx(0.5)
    for _ in range(9):
        h.vals["mpAccepted"] += 100
        h.tick()
    assert not h.verdict("throttle")["alert"]
    assert h.dog.trips == 1 and h.dog.clears == 1


def test_ratio_with_total_denominator():
    spec = SloSpec("drops", "ratio", short_s=4, long_s=8,
                   burn_threshold=2.0, objective=0.999,
                   bad="collectorMessagesDropped",
                   total="collectorMessages")
    h = Harness([spec])
    h.vals = {"collectorMessages": 0.0, "collectorMessagesDropped": 0.0}
    for _ in range(8):
        h.vals["collectorMessages"] += 1000
        h.vals["collectorMessagesDropped"] += 10  # 1% >> 0.1% budget
        h.tick()
    v = h.verdict("drops")
    assert v["alert"]
    assert v["windows"]["4s"]["badFraction"] == pytest.approx(0.01)


# -- gauge kind ----------------------------------------------------------


def test_gauge_slo_uses_instantaneous_value_against_limit():
    h = Harness([GAUGE])
    h.vals = {"snapshotAgeS": 50.0}
    h.tick()
    v = h.verdict("snap_age")
    assert not v["alert"]
    assert v["windows"]["4s"]["burn"] == pytest.approx(0.5)
    h.vals["snapshotAgeS"] = 250.0  # over the limit -> burn 2.5 >= 1.0
    h.tick()
    assert h.verdict("snap_age")["alert"]
    h.vals["snapshotAgeS"] = 10.0
    h.tick()
    assert not h.verdict("snap_age")["alert"]


def test_gauge_absent_counter_reads_zero():
    h = Harness([GAUGE])
    h.tick()
    assert h.verdict("snap_age")["windows"]["4s"]["burn"] == 0.0


# -- wiring --------------------------------------------------------------


def test_watchdog_evaluates_on_tick_subscription():
    h = Harness([LAT])
    for _ in range(4):
        h.rec.record("query_fresh", 0.050)
        h.tick()
    # no explicit evaluate(): the on_tick subscription already ran it
    assert h.dog.alerts()["q_p99"]
    assert h.dog.alerting


def test_status_shape():
    h = Harness([LAT, RATIO])
    h.tick(2)
    body = h.dog.status()
    assert {v["name"] for v in body["specs"]} == {"q_p99", "throttle"}
    assert body["alerting"] is False
    assert body["trips"] == 0 and body["clears"] == 0
