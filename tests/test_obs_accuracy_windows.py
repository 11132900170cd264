"""Accuracy plane × windowed telemetry (ISSUE 10): gauge flow through
the windows' counter source, drift-SLO trip/clear via the watchdog, the
rollup scheduler, coverage gating, and a full rollup against a fake
device plane with known exact answers.

Mirrors the FakeClock idiom of test_obs_windows.py: every tick is
driven by hand, so trip latency is measured in ticks, not wall time.
"""

import numpy as np
import pytest

from zipkin_tpu.obs.accuracy import AccuracyEstimator, _digest_quantile
from zipkin_tpu.obs.recorder import StageRecorder
from zipkin_tpu.obs.shadow import HostShadow
from zipkin_tpu.obs.slo import SloSpec, SloWatchdog, default_specs
from zipkin_tpu.obs.windows import WindowedTelemetry
from zipkin_tpu.tpu.columnar import SpanColumns


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def make(source, **kw):
    clock = FakeClock()
    kw.setdefault("tick_s", 1.0)
    w = WindowedTelemetry(StageRecorder(), source, clock=clock, **kw)
    return w, clock


def tick(w, clock):
    clock.advance(w.tick_s)
    assert w.tick(clock())


def gauge_spec(limit=0.20, **kw):
    kw.setdefault("short_s", 2.0)
    kw.setdefault("long_s", 4.0)
    return SloSpec("digest_p99_relerr", "gauge",
                   gauge="accuracyDigestP99RelErr", limit=limit, **kw)


# -- gauges through the windows' counter source ---------------------------


def test_accuracy_gauges_flow_and_are_retained():
    vals = {"accuracyDigestP99RelErr": 0.0, "accuracyRollups": 0.0}
    w, clock = make(lambda: dict(vals))
    for i in range(5):
        vals["accuracyRollups"] += 1
        vals["accuracyDigestP99RelErr"] = 0.01 * (i + 1)
        tick(w, clock)
    # gauge reads are instantaneous: newest tick's capture wins
    assert w.current_counters()["accuracyDigestP99RelErr"] == pytest.approx(0.05)
    # the rollup counter windows like any counter: rate over the ring
    assert w.window(5 * w.tick_s).rate("accuracyRollups") == pytest.approx(1.0)
    assert w.window(2 * w.tick_s).counter_deltas["accuracyRollups"] == 2


def test_gauge_survives_ring_retention():
    vals = {"accuracyDigestP99RelErr": 0.4}
    w, clock = make(lambda: dict(vals), slots=4, coarse_slots=2,
                    coarse_factor=2)
    for _ in range(20):  # far past fine+coarse retention
        tick(w, clock)
    # old slots fell off the ring, but the gauge is a point read of the
    # NEWEST capture — retention never erases the current drift value
    assert w.current_counters()["accuracyDigestP99RelErr"] == pytest.approx(0.4)


# -- drift SLO: trip within one tick of publication, clear on recovery ----


def test_drift_slo_trips_and_clears_within_one_tick():
    vals = {"accuracyDigestP99RelErr": 0.0}
    w, clock = make(lambda: dict(vals))
    dog = SloWatchdog(w, specs=[gauge_spec(limit=0.20)], subscribe=True)
    for _ in range(3):
        tick(w, clock)
    assert dog.alerts() == {"digest_p99_relerr": False}
    # drift published by a rollup: next tick captures it, same-tick
    # evaluation trips (gauge burn = value/limit on both windows)
    vals["accuracyDigestP99RelErr"] = 0.5
    tick(w, clock)
    assert dog.alerts()["digest_p99_relerr"] is True
    assert dog.trips == 1
    # recovery clears on the first tick that captures the sane value
    vals["accuracyDigestP99RelErr"] = 0.01
    tick(w, clock)
    assert dog.alerts()["digest_p99_relerr"] is False
    assert dog.clears == 1


def test_gauge_at_exact_limit_trips():
    vals = {"accuracyDigestP99RelErr": 0.20}
    w, clock = make(lambda: dict(vals))
    dog = SloWatchdog(w, specs=[gauge_spec(limit=0.20)], subscribe=True)
    tick(w, clock)  # burn == 1.0 >= threshold 1.0
    assert dog.alerts()["digest_p99_relerr"] is True


def test_default_specs_include_accuracy_drift():
    names = {s.name for s in default_specs()}
    assert {"digest_p99_relerr", "hll_relerr", "hll_envelope"} <= names
    by_name = {s.name: s for s in default_specs()}
    # the specs watch the DRIFT gauges (error in excess of the ground
    # truth's own sampling noise), not the raw relative errors
    assert by_name["digest_p99_relerr"].gauge == "accuracyDigestP99Drift"
    assert by_name["hll_relerr"].gauge == "accuracyHllDrift"
    # the promoted PR 2 envelope counter rides the exact-denominator form
    assert by_name["hll_envelope"].bad == "hllEnvelopeExceeded"
    assert by_name["hll_envelope"].total == "hostTransfers"


# -- rollup scheduling and coverage gating --------------------------------


class FakeAgg:
    def __init__(self, spans=0):
        self.host_counters = {"spans": spans}
        self.sampler = None


class FakeStore:
    def __init__(self, spans=0):
        self.agg = FakeAgg(spans)


def test_maybe_rollup_is_rate_limited():
    clock = FakeClock()
    shadow = HostShadow()
    acc = AccuracyEstimator(FakeStore(), shadow, rollup_s=5.0, clock=clock)
    assert acc.maybe_rollup() is True
    assert acc.maybe_rollup() is False  # within rollup_s
    clock.advance(5.0)
    assert acc.maybe_rollup() is True
    assert acc.rollups == 2


def test_low_coverage_suppresses_to_no_signal():
    shadow = HostShadow()
    # the device saw 10k spans the shadow never did (e.g. WAL restore)
    acc = AccuracyEstimator(FakeStore(spans=10_000), shadow, rollup_s=0.0)
    g = acc.rollup()
    assert g["accuracyShadowCoverage"] == 0.0
    # suppressed: zero error, full recall — no signal, never false alert
    assert g["accuracyDigestP99RelErr"] == 0.0
    assert g["accuracyHllRelErr"] == 0.0
    assert g["accuracyLinkRecall"] == 1.0
    assert acc.status()["suppressed"] is True


# -- full rollup against a fake device plane with exact answers -----------


class FakeInterner:
    def __init__(self, names):
        self._names = dict(names)  # id -> name
        self._ids = {v: k for k, v in self._names.items()}

    def lookup(self, sid):
        return self._names.get(sid)

    def get(self, name):
        return self._ids.get(name)


class FakeVocab:
    def __init__(self, key_list, names):
        import threading

        self._lock = threading.Lock()
        self._key_list = key_list
        self.services = FakeInterner(names)


class DeviceAgg:
    """A device plane whose reads are built from the exact stream."""

    def __init__(self, durs, distinct, edges, max_services, spans):
        self.host_counters = {"spans": spans}
        self.sampler = None
        c = len(durs)
        # kid 1 holds every exact duration as a weight-1 centroid; the
        # digest read is then as truthful as the format allows
        self._digest = np.zeros((3, c, 2))
        self._digest[1, :, 0] = np.sort(durs)
        self._digest[1, :, 1] = 1.0
        self._cards = np.zeros(max_services + 1)
        self._cards[-1] = distinct
        self._edges = np.asarray(
            [p * max_services + ch for p, ch in edges], np.int64
        )

    def merged_digest(self):
        return self._digest

    def cardinalities(self):
        return self._cards

    def dependency_edges(self, lo, hi):
        calls = np.full(len(self._edges), 5, np.int64)
        return self._edges, calls, np.zeros_like(calls)


class DeviceStore:
    def __init__(self, agg, vocab, max_services):
        self.agg = agg
        self.vocab = vocab

        class _Cfg:
            pass

        self.config = _Cfg()
        self.config.max_services = max_services
        self.config.global_hll_row = max_services
        self.config.hll_precision = 14


def _client_server_lanes(n, durs):
    """n traces, each a CLIENT span (svc 1, dur) + its SERVER child
    (svc 2, shared) — the textbook dependency-linker pair."""
    m = 2 * n
    tl0 = np.repeat(np.arange(1, n + 1, dtype=np.uint32), 2)
    tl1 = np.zeros(m, np.uint32)
    trace_h = tl0.copy()  # any stable per-trace value works for the taps
    s0 = np.arange(1, m + 1, dtype=np.uint32)
    p0 = np.where(np.arange(m) % 2 == 1, s0 - 1, 0).astype(np.uint32)
    client = np.arange(m) % 2 == 0
    return SpanColumns(
        trace_h=trace_h, tl0=tl0, tl1=tl1,
        s0=s0, s1=np.zeros(m, np.uint32),
        p0=p0, p1=np.zeros(m, np.uint32),
        shared=~client,
        kind=np.where(client, 1, 2).astype(np.int32),  # CLIENT / SERVER
        svc=np.where(client, 1, 2).astype(np.int32),
        rsvc=np.where(client, 2, 0).astype(np.int32),
        key=np.where(client, 1, 2).astype(np.int32),
        err=np.zeros(m, bool),
        dur=np.repeat(durs, 2).astype(np.uint32),
        has_dur=client,  # only the client spans carry durations
        ts_min=np.zeros(m, np.uint32),
        valid=np.ones(m, bool),
    )


def test_full_rollup_matches_fake_device_plane():
    n = 128
    rng = np.random.default_rng(42)
    durs = rng.integers(1_000, 100_000, n)
    cols = _client_server_lanes(n, durs)
    shadow = HostShadow(reservoir_k=512, link_rate=1.0, seed=7)
    shadow.offer_cols(cols)
    vocab = FakeVocab(
        key_list=[(0, 0), (1, 0), (2, 0)],  # kid1 -> svc1, kid2 -> svc2
        names={1: "frontend", 2: "backend"},
    )
    agg = DeviceAgg(durs, distinct=n, edges=[(1, 2)], max_services=64,
                    spans=2 * n)
    store = DeviceStore(agg, vocab, max_services=64)
    acc = AccuracyEstimator(store, shadow, rollup_s=0.0)
    g = acc.rollup()

    assert g["accuracyShadowCoverage"] == pytest.approx(1.0)
    # digest read IS the exact stream -> tiny residual interpolation
    # error, and always within the stated distribution-free bound
    assert g["accuracyDigestP50RelErr"] < 0.05
    assert g["accuracyDigestP99RelErr"] < 0.05
    assert g["accuracyDigestP99RelErr"] <= g["accuracyDigestP99Bound"]
    # a truthful digest shows no drift beyond sampling noise
    assert g["accuracyDigestP99Drift"] < 0.02
    # device HLL returns the exact distinct count -> zero error
    assert g["accuracyHllRelErr"] == pytest.approx(0.0)
    assert g["accuracyHllBound"] > 0.0
    # every oracle edge (frontend -> backend) is in the device matrix
    assert g["accuracyLinkRecall"] == pytest.approx(1.0)
    st = acc.status()
    assert st["links"]["shadowEdges"] == 1
    assert st["links"]["matched"] == 1
    assert [r["service"] for r in st["services"]] == ["frontend"]
    assert st["services"][0]["reservoirSeen"] == n
    # exported for ingest_counters / the windows' counter source
    exp = acc.export_counters()
    assert exp["shadowSpans"] == 2 * n
    assert exp["accuracyRollups"] == 1


def test_rollup_detects_missing_device_edge():
    n = 96
    durs = np.full(n, 5_000)
    cols = _client_server_lanes(n, durs)
    shadow = HostShadow(link_rate=1.0, seed=8)
    shadow.offer_cols(cols)
    vocab = FakeVocab([(0, 0), (1, 0), (2, 0)],
                      {1: "frontend", 2: "backend"})
    # device lost the dependency edge entirely
    agg = DeviceAgg(durs, distinct=n, edges=[], max_services=64,
                    spans=2 * n)
    acc = AccuracyEstimator(DeviceStore(agg, vocab, 64), shadow,
                            rollup_s=0.0)
    g = acc.rollup()
    assert g["accuracyLinkRecall"] == pytest.approx(0.0)


def test_rollup_detects_hll_drift():
    n = 128
    durs = np.full(n, 5_000)
    cols = _client_server_lanes(n, durs)
    shadow = HostShadow(link_rate=0.0, seed=9)
    shadow.offer_cols(cols)
    vocab = FakeVocab([(0, 0), (1, 0), (2, 0)],
                      {1: "frontend", 2: "backend"})
    # device HLL reports half the true cardinality
    agg = DeviceAgg(durs, distinct=n // 2, edges=[], max_services=64,
                    spans=2 * n)
    acc = AccuracyEstimator(DeviceStore(agg, vocab, 64), shadow,
                            rollup_s=0.0)
    g = acc.rollup()
    assert g["accuracyHllRelErr"] == pytest.approx(0.5)
    assert g["accuracyHllRelErr"] > g["accuracyHllBound"]
    # unexplained error surfaces on the alerting gauge
    assert g["accuracyHllDrift"] == pytest.approx(
        0.5 - g["accuracyHllBound"]
    )


def test_rollup_detects_digest_drift():
    # an undersized digest on a bimodal stream: four equal-weight
    # centroids put the slow tenth into one cluster with fast spans,
    # and the shadow's exact tail says so on the alerting gauge
    n = 400
    durs = np.where(np.arange(n) % 10 == 9, 100_000, 1_000)
    cols = _client_server_lanes(n, durs)
    shadow = HostShadow(reservoir_k=512, link_rate=0.0, seed=10)
    shadow.offer_cols(cols)
    vocab = FakeVocab([(0, 0), (1, 0), (2, 0)],
                      {1: "frontend", 2: "backend"})
    agg = DeviceAgg(durs, distinct=n, edges=[], max_services=64,
                    spans=2 * n)
    agg._digest = np.zeros((3, 4, 2))
    agg._digest[1, :, 0] = np.sort(durs).reshape(4, -1).mean(axis=1)
    agg._digest[1, :, 1] = n / 4
    acc = AccuracyEstimator(DeviceStore(agg, vocab, 64), shadow,
                            rollup_s=0.0)
    g = acc.rollup()
    # the stated bound widens with the clusters; the drift gauge leaves
    # their width out and crosses the default spec's limit
    assert g["accuracyDigestP99RelErr"] <= g["accuracyDigestP99Bound"]
    assert g["accuracyDigestP99Drift"] > 0.20


def test_rollup_names_itself_in_the_lock_ledger():
    """The rollup's device reads each take the aggregator lock: they run
    under the holder label ``accuracy_rollup``, not ``unattributed``."""
    from zipkin_tpu.obs import querytrace

    n = 96
    durs = np.full(n, 5_000)
    shadow = HostShadow(link_rate=1.0, seed=8)
    shadow.offer_cols(_client_server_lanes(n, durs))
    vocab = FakeVocab([(0, 0), (1, 0), (2, 0)],
                      {1: "frontend", 2: "backend"})
    seen = []

    class LabelledAgg(DeviceAgg):
        def merged_digest(self):
            seen.append(querytrace.current_label())
            return super().merged_digest()

        def dependency_edges(self, lo, hi):
            seen.append(querytrace.current_label())
            return super().dependency_edges(lo, hi)

    agg = LabelledAgg(durs, distinct=n, edges=[(1, 2)], max_services=64,
                      spans=2 * n)
    AccuracyEstimator(DeviceStore(agg, vocab, 64), shadow,
                      rollup_s=0.0).rollup()
    assert seen and set(seen) == {"accuracy_rollup"}
    assert querytrace.current_label() == "unattributed"


def test_digest_quantile_midpoint_interpolation():
    rows = np.zeros((1, 4, 2))
    rows[0, :, 0] = [10.0, 20.0, 30.0, 40.0]
    rows[0, :, 1] = 1.0
    v, total = _digest_quantile(rows, 0.5)
    assert total == 4.0
    assert v == pytest.approx(25.0)  # midpoint between centroids 2 and 3
    # degenerate: empty rows report zero weight, never NaN
    assert _digest_quantile(np.zeros((1, 4, 2)), 0.5) == (0.0, 0.0)


# -- end-to-end: ticker drives rollup, watchdog sees the lagged gauge -----


def test_tick_pipeline_rollup_then_watchdog_lags_one_tick():
    """Registration order on the real server: accuracy rollup first,
    then watchdog. The tick captures counters BEFORE callbacks run, so
    a drifted gauge published during tick T is captured (and alerted
    on) at tick T+1 — drift trips within ONE tick of publication."""
    drifted = {"v": 0.0}

    class Acc:
        def export_counters(self):
            return {"accuracyDigestP99RelErr": drifted["v"]}

    acc = Acc()
    w, clock = make(acc.export_counters)
    fired = []
    w.on_tick(lambda _w: fired.append("rollup") or
              drifted.__setitem__("v", drift_next["v"]))
    dog = SloWatchdog(w, specs=[gauge_spec()], subscribe=True)
    drift_next = {"v": 0.0}
    tick(w, clock)
    assert not dog.alerts()["digest_p99_relerr"]
    drift_next["v"] = 0.9  # the NEXT rollup will publish drift
    tick(w, clock)  # rollup publishes after this tick's capture
    assert not dog.alerts()["digest_p99_relerr"]  # lag tick
    tick(w, clock)  # captures the published gauge -> trips
    assert dog.alerts()["digest_p99_relerr"] is True
    assert fired.count("rollup") == 3
