"""Collector core: the decode -> sample -> store pipeline every transport uses.

Reference semantics: ``zipkin2/collector/Collector.java``,
``CollectorComponent.java``, ``CollectorSampler.java``,
``CollectorMetrics.java``, ``InMemoryCollectorMetrics.java`` (SURVEY.md
§2.2, §3.2). The counter catalogue (messages, messagesDropped, bytes, spans,
spansDropped) is kept name-for-name so dashboards translate.

Sampling is **boundary sampling**: the decision is a pure function of the
trace id's low 64 bits, so every collector node makes the same call for
every span of a trace without coordination — the property that lets the
ingest tier scale out statelessly (and lets the TPU ingest shard by trace
id without resampling).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from zipkin_tpu import faults, obs
from zipkin_tpu.model import codec
from zipkin_tpu.obs import critpath
from zipkin_tpu.model.span import Span
from zipkin_tpu.storage.spi import StorageComponent
from zipkin_tpu.utils.component import Component

logger = logging.getLogger(__name__)

_MAX_I64 = (1 << 63) - 1


class CollectorSampler:
    """Samples traces at a fixed rate keyed on trace-id low-64 bits.

    ``is_sampled`` compares ``abs(signed_low64(traceId))`` against
    ``rate * 2^63`` — the same arithmetic as the reference, so a mixed
    fleet of reference and rebuild collectors samples identically.
    Debug spans always pass.
    """

    def __init__(self, rate: float = 1.0) -> None:
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"rate should be between 0 and 1: {rate}")
        self.rate = rate
        self._boundary = int(_MAX_I64 * rate)

    def is_sampled(self, trace_id_low64: int, debug: bool = False) -> bool:
        if debug:
            return True
        signed = trace_id_low64 - (1 << 64) if trace_id_low64 >= (1 << 63) else trace_id_low64
        # Java parity: CollectorSampler explicitly maps Long.MIN_VALUE to
        # Long.MAX_VALUE before comparing (abs() alone would overflow), so
        # that one id is dropped at rates < 1.0 like any max-magnitude id.
        t = _MAX_I64 if signed == -(1 << 63) else abs(signed)
        return t <= self._boundary

    def test(self, span: Span) -> bool:
        return self.is_sampled(span.trace_id_low64, bool(span.debug))


class CollectorMetrics:
    """Counter hooks; subclass or use :class:`InMemoryCollectorMetrics`."""

    def increment_messages(self) -> None: ...

    def increment_messages_dropped(self) -> None: ...

    def increment_bytes(self, quantity: int) -> None: ...

    def increment_spans(self, quantity: int) -> None: ...

    def increment_spans_dropped(self, quantity: int) -> None: ...

    def for_transport(self, transport: str) -> "CollectorMetrics":
        return self


class InMemoryCollectorMetrics(CollectorMetrics):
    """Thread-safe counters, partitionable per transport.

    Reference: ``InMemoryCollectorMetrics.java``.
    """

    def __init__(self, transport: Optional[str] = None, _counters: Optional[Dict[str, int]] = None) -> None:
        self.transport = transport
        self._counters: Dict[str, int] = _counters if _counters is not None else {}
        self._lock = threading.Lock()

    def _inc(self, name: str, by: int = 1) -> None:
        key = f"{self.transport}.{name}" if self.transport else name
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + by

    def increment_messages(self) -> None:
        self._inc("messages")

    def increment_messages_dropped(self) -> None:
        self._inc("messages_dropped")

    def increment_bytes(self, quantity: int) -> None:
        self._inc("bytes", quantity)

    def increment_spans(self, quantity: int) -> None:
        self._inc("spans", quantity)

    def increment_spans_dropped(self, quantity: int) -> None:
        self._inc("spans_dropped", quantity)

    def for_transport(self, transport: str) -> "InMemoryCollectorMetrics":
        child = InMemoryCollectorMetrics(transport, self._counters)
        child._lock = self._lock
        return child

    def get(self, name: str, transport: Optional[str] = None) -> int:
        key = f"{transport}.{name}" if transport else name
        with self._lock:
            return self._counters.get(key, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)


class Collector:
    """The shared ingest pipeline: bytes or spans in, storage writes out.

    Reference: ``Collector.java#acceptSpans``. Errors while storing are
    counted as dropped spans and logged, never raised to the transport —
    at-least-once transports redeliver, lossy ones move on.
    """

    def __init__(
        self,
        storage: StorageComponent,
        *,
        sampler: Optional[CollectorSampler] = None,
        metrics: Optional[CollectorMetrics] = None,
        fast_ingest: bool = False,
        mp_ingester=None,
        shadow=None,
    ) -> None:
        self.storage = storage
        self.sampler = sampler or CollectorSampler(1.0)
        self.metrics = metrics or CollectorMetrics()
        # opt-in line-rate path: JSON v2 bytes go straight to the TPU
        # store's native columnar parser, skipping Span objects and the
        # raw-span archive (aggregates only — the v5e ingest headline)
        self.fast_ingest = fast_ingest and hasattr(storage, "ingest_json_fast")
        # optional multi-process parse tier (tpu/mp_ingest.py): payloads
        # are handed to worker processes and acked immediately — the
        # reference's 202-on-enqueue semantics (SURVEY.md §3.2)
        self.mp_ingester = mp_ingester
        # accuracy-observatory tap (obs/shadow.py): the object path
        # offers its post-sampling batches so the shadow sees the same
        # stream the device plane aggregates. O(1) bounded append.
        self.shadow = shadow
        # overload control plane (runtime/overload.py, ISSUE 13): the
        # server wires its brownout controller here so B2/B3 admission
        # verdicts gate payloads BEFORE any parse or queue hand-off. A
        # shed surfaces as IngestBackpressure — the transports already
        # map that to 429 / RESOURCE_EXHAUSTED with backoff guidance —
        # never as a silent ack.
        self.overload = None
        self._consumer = storage.span_consumer()

    def accept_spans_bytes(
        self, data: bytes, encoding: Optional[codec.Encoding] = None
    ) -> int:
        """Decode one transport message and ingest it.

        Returns the number of spans accepted (post-sampling). Raises
        ``ValueError`` on malformed payloads (the transport decides whether
        that is an HTTP 400 or a poison-pill skip) — after counting the
        dropped message.
        """
        # zt-tenant-admission: the collector chokepoint — tenant budget
        # first (scope tenant), then the global brownout ladder (scope
        # global), before any parse or device dispatch
        self.metrics.increment_messages()
        self.metrics.increment_bytes(len(data))
        from zipkin_tpu.runtime.tenant import CURRENT_TENANT

        tenant = CURRENT_TENANT.get()
        ctl = self.overload
        if ctl is not None:
            # admission (ISSUEs 13/18): the tenant's own token bucket is
            # consulted first — a flooding tenant sheds alone while
            # everyone else rides B0 — then the global ladder (B2 sheds
            # bulk payloads probabilistically, B3 admits the error class
            # only). The verdict precedes every parse/queue path so a
            # shed costs one substring probe, and the refusal is
            # explicit — the sender gets a retryable rejection carrying
            # scope + per-scope backoff guidance, never a dropped ack.
            from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

            v = ctl.admit(data, tenant=tenant)
            if not v.admitted:
                self.metrics.increment_messages_dropped()
                if v.scope == "tenant":
                    msg = (
                        f"tenant {v.tenant!r} over ingest budget: "
                        f"{v.cls} payload shed; retry after the "
                        "advertised backoff"
                    )
                else:
                    msg = (
                        f"overload {ctl.level_name}: {v.cls} payload "
                        "shed; retry after the advertised backoff"
                    )
                raise IngestBackpressure(
                    msg, scope=v.scope, tenant=v.tenant,
                    retry_after_s=v.retry_after_s or None,
                )
        try:
            # resource-exhaustion injection (faults.py): an allocation
            # failure at the ingest boundary degrades to backpressure —
            # the sender retries against a tier that is telling the
            # truth about its memory — instead of crashing the server.
            faults.resource_point("alloc")
        except MemoryError as e:
            from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

            self.metrics.increment_messages_dropped()
            raise IngestBackpressure(f"allocation failure: {e}") from e
        _MP = (codec.Encoding.JSON_V2, codec.Encoding.PROTO3)
        if (
            self.mp_ingester is not None
            # MP is the fast path's scale-out: it keeps the fast path's
            # sampled-archive semantics, so it must never preempt the
            # full-fidelity object path when fast ingest is off
            and self.fast_ingest
            and (encoding is None or encoding in _MP)
        ):
            if encoding is not None or codec.detect(data) in _MP:
                # span/drop counters are incremented by the dispatcher as
                # batches land (the ingester holds this collector's
                # metrics); 0 = accepted asynchronously. A malformed
                # payload is counted + logged by the dispatcher instead
                # of HTTP-400'd — the at-least-once transports share
                # this poison-pill semantic (SURVEY.md §3.3). proto3
                # rides the same fan-out: the workers' native parser
                # sniffs the wire format (ISSUE 8).
                from zipkin_tpu.tpu.mp_ingest import IngestBackpressure

                tok = None
                if critpath.WIRE_T0_NS.get() == 0:
                    # direct submitters (tests, benches driving the
                    # collector without a server boundary) still get
                    # wire-to-durable timelines, measured from collector
                    # entry; token-reset so a long-lived caller thread
                    # stamps fresh per payload
                    tok = critpath.WIRE_T0_NS.set(time.perf_counter_ns())
                try:
                    # non-blocking at the boundary: a full tier must
                    # surface as 429/RESOURCE_EXHAUSTED, not as the
                    # event loop's to_thread pool silently queueing
                    self.mp_ingester.submit(
                        data, block=False, tenant=tenant
                    )
                except IngestBackpressure:
                    self.metrics.increment_messages_dropped()
                    raise
                finally:
                    if tok is not None:
                        critpath.WIRE_T0_NS.reset(tok)
                return 0
        # the native tier parses JSON v2 AND proto3 ListOfSpans (r4:
        # gRPC/proto3 ingest was the one first-class hot codec still on
        # the ~30k/s object path — VERDICT r3 order 6)
        _FAST = (codec.Encoding.JSON_V2, codec.Encoding.PROTO3)
        if self.fast_ingest and (encoding is None or encoding in _FAST):
            from zipkin_tpu.storage.throttle import RejectedExecutionError

            try:
                if encoding is not None or codec.detect(data) in _FAST:
                    result = self.storage.ingest_json_fast(data, self.sampler)
                    if result is not None:
                        accepted, sample_dropped = result
                        self.metrics.increment_spans(accepted + sample_dropped)
                        if sample_dropped:
                            self.metrics.increment_spans_dropped(sample_dropped)
                        return accepted
            except RejectedExecutionError:
                # load shed on the fast path must show up on the same drop
                # counters the object path maintains, or dashboards go blind
                self.metrics.increment_messages_dropped()
                raise
            except ValueError:
                pass  # fall through: the python codec owns error reporting
        try:
            t0 = time.perf_counter()
            spans = codec.decode_spans(data, encoding)
            obs.record("parse", time.perf_counter() - t0)
        except Exception as e:
            self.metrics.increment_messages_dropped()
            raise ValueError(f"cannot decode spans: {e}") from e
        return self.accept(spans)

    def accept(self, spans: Sequence[Span]) -> int:
        """Sample + store already-decoded spans; returns count accepted."""
        if not spans:
            return 0
        self.metrics.increment_spans(len(spans))
        sampled: List[Span] = [s for s in spans if self.sampler.test(s)]
        dropped = len(spans) - len(sampled)
        if dropped:
            self.metrics.increment_spans_dropped(dropped)
        if not sampled:
            return 0
        if self.shadow is not None:
            self.shadow.offer_spans(sampled)
        try:
            self._consumer.accept(sampled).execute()
        except Exception as e:
            from zipkin_tpu.storage.throttle import RejectedExecutionError

            self.metrics.increment_spans_dropped(len(sampled))
            if isinstance(e, RejectedExecutionError):
                # backpressure must reach the transport so senders back off
                # (the reference maps RejectedExecutionException to 503)
                raise
            logger.exception("cannot store %d spans", len(sampled))
            return 0
        return len(sampled)


@dataclasses.dataclass
class CollectorComponent(Component):
    """Lifecycle contract for transports (start/check/close).

    Reference: ``CollectorComponent.java``. Concrete transports:
    HTTP (in the server), gRPC, and the queue consumers in
    :mod:`zipkin_tpu.collector.transports`.
    """

    collector: Collector

    def start(self) -> "CollectorComponent":
        return self
