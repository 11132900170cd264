"""The HTTP server: Zipkin v2 API, collectors, health, and metrics.

Reference semantics: ``zipkin-server`` (SURVEY.md §2.4) — the Armeria app
rebuilt on aiohttp. Route-for-route:

- ``POST /api/v2/spans`` and ``POST /api/v1/spans`` (+gzip, content-type or
  first-byte format sniffing)   [``ZipkinHttpCollector.java``]
- ``GET /api/v2/{traces,trace/{id},traceMany,services,spans,remoteServices,
  dependencies,autocompleteKeys,autocompleteValues}``
  [``ZipkinQueryApiV2.java``]
- ``GET /health`` aggregating ``Component.check()``
  [``ZipkinHealthController.java``]
- ``GET /metrics`` (actuator counter names kept verbatim) and
  ``GET /prometheus``
- ``GET /config.json`` (UI config), ``GET /info``

Ingest responds 202 as soon as the storage call is dispatched, mirroring
the reference's enqueue-then-ack behavior.
"""

from __future__ import annotations

import asyncio
import gzip
import json
import logging
import time
from typing import Dict, List, Optional, Tuple

from aiohttp import web

import zipkin_tpu
from zipkin_tpu import obs
from zipkin_tpu.collector.core import (
    Collector,
    CollectorSampler,
    InMemoryCollectorMetrics,
)
from zipkin_tpu.internal.hex import normalize_trace_id
from zipkin_tpu.model import codec, json_v2
from zipkin_tpu.obs import critpath
from zipkin_tpu.model.codec import Encoding
from zipkin_tpu.runtime.tenant import (
    CURRENT_TENANT,
    TENANT_HEADER,
    normalize_tenant,
)
from zipkin_tpu.server.config import ServerConfig
from zipkin_tpu.storage.memory import InMemoryStorage
from zipkin_tpu.storage.spi import QueryRequest, StorageComponent
from zipkin_tpu.storage.throttle import RejectedExecutionError
from zipkin_tpu.tpu.mp_ingest import IngestBackpressure
from zipkin_tpu.utils.component import Component

logger = logging.getLogger(__name__)

JSON = "application/json"


class PayloadTooLarge(ValueError):
    """Inflated request body exceeded the decompression cap."""


def build_storage(config: ServerConfig) -> StorageComponent:
    """STORAGE_TYPE -> StorageComponent, the autoconfig seam."""
    common = dict(
        strict_trace_id=config.strict_trace_id,
        search_enabled=config.search_enabled,
        autocomplete_keys=config.autocomplete_keys,
    )
    if config.storage_type == "mem":
        return InMemoryStorage(max_span_count=config.mem_max_spans, **common)
    if config.storage_type == "tpu":
        from zipkin_tpu.parallel.mesh import enable_compile_cache
        from zipkin_tpu.storage.tpu import TpuStorage
        from zipkin_tpu.tpu.state import AggConfig

        # before the first program is built: a restart must not pay the
        # minutes of compile again
        logger.info("compile cache: %s", enable_compile_cache() or "off")
        agg_kwargs = dict(config.tpu_agg)
        if config.tpu_sampling:
            # sampling is a STATIC AggConfig field (it changes the
            # compiled ingest step), so it rides the agg config rather
            # than a storage kwarg
            agg_kwargs["sampling"] = True
            agg_kwargs["sample_rare_min"] = config.tpu_sampling_rare_min

        def _make(archive_dir):
            return TpuStorage(
                max_span_count=config.mem_max_spans,
                batch_size=config.tpu_batch_size,
                num_devices=config.tpu_devices,
                checkpoint_dir=config.tpu_checkpoint_dir,
                wal_dir=config.tpu_wal_dir,
                wal_fsync=config.tpu_wal_fsync,
                archive_dir=archive_dir,
                archive_max_bytes=config.tpu_archive_max_bytes,
                archive_segment_bytes=config.tpu_archive_segment_bytes,
                config=AggConfig(**agg_kwargs) if agg_kwargs else None,
                fast_archive_sample=config.tpu_fast_archive_sample,
                sampling_budget=(
                    config.tpu_sampling_budget if config.tpu_sampling else 0.0
                ),
                sampling_interval_s=config.tpu_sampling_interval_s,
                sampling_min_rate=config.tpu_sampling_min_rate,
                sampling_tail_quantile=config.tpu_sampling_tail_quantile,
                snapshot_keep=config.tpu_snapshot_keep,
                scrub_interval_s=config.tpu_scrub_interval_s,
                scrub_bytes_per_sec=config.tpu_scrub_bytes_per_sec,
                mirror_segment_bytes=config.tpu_mirror_segment_bytes,
                mirror_segment_readers=config.tpu_readers,
                **common,
            )

        if config.tpu_archive_dir:
            logger.info(
                "span archive: %s (budget %d bytes)",
                config.tpu_archive_dir, config.tpu_archive_max_bytes,
            )
            try:
                return _make(config.tpu_archive_dir)
            except OSError as e:
                # the default-on archive must not brick a server whose
                # cwd is read-only: degrade to archive-free (the r3
                # posture) loudly instead of refusing to boot
                logger.warning(
                    "span archive dir %s unusable (%s); serving without "
                    "the disk archive", config.tpu_archive_dir, e,
                )
        return _make(None)
    raise ValueError(f"unknown STORAGE_TYPE: {config.storage_type}")


class ZipkinServer:
    """Wires storage + collector + routes; owns component lifecycle."""

    def __init__(
        self,
        config: Optional[ServerConfig] = None,
        *,
        storage: Optional[StorageComponent] = None,
    ) -> None:
        self.config = config or ServerConfig()
        self.storage = storage if storage is not None else build_storage(self.config)
        if self.config.throttle_enabled:
            from zipkin_tpu.storage.throttle import ThrottledStorage

            self.storage = ThrottledStorage(
                self.storage, max_concurrency=self.config.throttle_max_concurrency
            )
        self.metrics = InMemoryCollectorMetrics()
        sampler = CollectorSampler(self.config.sample_rate)
        http_metrics = self.metrics.for_transport("http")
        self._mp_ingester = None
        core = getattr(self.storage, "delegate", self.storage)
        if self.config.tpu_fast_ingest and hasattr(core, "ingest_json_fast"):
            from zipkin_tpu import native

            # the fast path IS the native parser: without it every
            # payload would quietly take the object path instead
            if not native.available():
                raise RuntimeError(
                    "TPU_FAST_INGEST=true needs the native span parser "
                    "(zipkin_tpu/native/span_json.c), and it could not "
                    "be built or loaded: is a C compiler installed?"
                )
        if self.config.tpu_mp_workers > 0:
            from zipkin_tpu.tpu.store import TpuStorage as _CoreTpu

            # the MP tier needs the CORE store (it reaches the vocab and
            # aggregator directly); a throttle wrapper still exposes it
            # via .delegate
            if isinstance(core, _CoreTpu) and self.config.tpu_fast_ingest:
                from zipkin_tpu.tpu.mp_ingest import MultiProcessIngester

                self._mp_ingester = MultiProcessIngester(
                    core,
                    workers=self.config.tpu_mp_workers,
                    sampler=sampler,
                    queue_depth=self.config.tpu_mp_queue_depth,
                    ring_slots=self.config.tpu_mp_ring_slots,
                    coalesce_max=self.config.tpu_mp_coalesce_max,
                    metrics=http_metrics,
                    # ingest critical-path tracer (ISSUE 11): size the
                    # shared-memory interval ledger; 0 disables tracing
                    critpath_slots=(
                        self.config.obs_critpath_slots
                        if self.config.obs_critpath_enabled
                        else 0
                    ),
                    critpath_reclaim_s=self.config.obs_critpath_reclaim_s,
                )
                # surface the tier's gauges on ingest_counters() —
                # /metrics, /prometheus and /statusz all read it — and
                # let the storage adapter drain/close an attached tier
                # if the server's stop() never ran
                core.mp_ingester = self._mp_ingester
            else:
                logger.warning(
                    "TPU_MP_WORKERS=%d ignored: requires STORAGE_TYPE=tpu "
                    "and TPU_FAST_INGEST=true (the MP tier is the fast "
                    "path's scale-out)",
                    self.config.tpu_mp_workers,
                )
        self.collector = Collector(
            self.storage,
            sampler=sampler,
            metrics=http_metrics,
            fast_ingest=self.config.tpu_fast_ingest,
            mp_ingester=self._mp_ingester,
        )
        self._obs_emitter = None
        if self.config.obs_selfspans_enabled:
            from zipkin_tpu.obs.selfspans import SelfSpanEmitter

            # over-budget pipeline stages publish slow-dispatch spans
            # (service zipkin-tpu-pipeline) through the ordinary object
            # path — the tracer dogfooding itself
            self._obs_emitter = SelfSpanEmitter(
                Collector(
                    self.storage,
                    metrics=self.metrics.for_transport("obs"),
                ),
                budget_scale=self.config.obs_budget_scale,
            )
            self._obs_emitter.install(obs.RECORDER)
        # slowest-chunk critpath timelines ride the self-span plane when
        # both are armed: the stitcher hands pre-built spans to the
        # emitter's suppressed drain thread
        if (
            self._mp_ingester is not None
            and getattr(self._mp_ingester, "critpath", None) is not None
            and self._obs_emitter is not None
        ):
            self._mp_ingester.critpath.emitter = self._obs_emitter
        # query-plane observatory (obs/querytrace.py, ISSUE 12): the
        # store owns the stitcher + the instrumented aggregator lock;
        # propagate the configured enablement (trace arming and the lock
        # ledger switch together) and give the slowest-query timeline
        # the same self-span plane the critpath stitcher rides.
        _qt_core = getattr(self.storage, "delegate", self.storage)
        self._querytrace = getattr(_qt_core, "querytrace", None)
        if hasattr(_qt_core, "set_query_observatory"):
            _qt_core.set_query_observatory(self.config.obs_query_enabled)
        if self._querytrace is not None and self._obs_emitter is not None:
            self._querytrace.emitter = self._obs_emitter
        # epoch-published read mirror (tpu/mirror.py, ISSUE 14): apply
        # the configured posture to the store's mirror before any ticker
        # or route can consult it. TPU_READ_MIRROR=false reverts every
        # query entrypoint to the locked read path; the max-stale knob
        # is the published staleness contract the query_mirror_staleness
        # SLO pages against.
        self._mirror = getattr(_qt_core, "mirror", None)
        if self._mirror is not None:
            self._mirror.enabled = bool(self.config.tpu_read_mirror)
            self._mirror.max_stale_ms = float(
                self.config.tpu_mirror_max_stale_ms
            )
        # windowed telemetry plane + SLO watchdog (ISSUE 9): per-tick
        # delta rings over the recorder/counters, burn-rate evaluation
        # on every tick. The ticker thread follows start()/stop();
        # read paths catch up lazily so un-started embedders work too.
        self._obs_windows = None
        self._obs_slo = None
        self._obs_shadow = None
        self._accuracy = None
        self._obs_incidents = None
        if self.config.obs_windows_enabled:
            from zipkin_tpu.obs.windows import WindowedTelemetry

            self._obs_windows = WindowedTelemetry(
                obs.RECORDER,
                self._window_counter_source,
                tick_s=self.config.obs_windows_tick_s,
            )
            # accuracy observatory (ISSUE 10): bounded host shadow of the
            # ingest stream + rollup-cadence relative-error estimators.
            # TPU storage only (it audits the device sketch plane) and
            # riding the windowed ticker; registered BEFORE the watchdog
            # so each tick rolls up before burn evaluation (the gauges
            # the watchdog reads are the tick's captured counters, so
            # alerts lag at most one tick).
            core = getattr(self.storage, "delegate", self.storage)
            if (
                self.config.obs_shadow_enabled
                and hasattr(core, "agg")
                and hasattr(core, "vocab")
            ):
                from zipkin_tpu.obs.accuracy import AccuracyEstimator
                from zipkin_tpu.obs.shadow import HostShadow

                self._obs_shadow = HostShadow(
                    reservoir_k=self.config.obs_shadow_reservoir_k,
                    distinct_k=self.config.obs_shadow_distinct_k,
                    link_rate=self.config.obs_shadow_link_rate,
                    pending_max=self.config.obs_shadow_pending_max,
                    max_services=core.config.max_services,
                    # deref the aggregator LAZILY: clear()/restore swap
                    # it wholesale, and the shadow must follow
                    sampler_ref=lambda: core.agg.sampler,
                    # get, never intern: a read-side plane must not
                    # perturb the id streams it audits
                    svc_resolver=core.vocab.services.get,
                    # windowed ground truth (ISSUE 15): bucket the
                    # shadow's sub-streams at the time tier's epoch
                    # granularity so the accuracy rollup can audit
                    # sealed segments bucket-for-bucket
                    bucket_minutes=(
                        core.config.time_bucket_minutes
                        if getattr(core, "timetier", None) is not None
                        else 0
                    ),
                )
                self._accuracy = AccuracyEstimator(
                    core,
                    self._obs_shadow,
                    rollup_s=self.config.obs_shadow_rollup_s,
                )
                core.shadow = self._obs_shadow
                core.accuracy = self._accuracy
                self.collector.shadow = self._obs_shadow
                if self._mp_ingester is not None:
                    self._mp_ingester.shadow = self._obs_shadow
                self._obs_windows.on_tick(
                    lambda _w: self._accuracy.maybe_rollup()
                )
            # critpath stitcher on the windows ticker, BEFORE the
            # watchdog for the same reason as the accuracy rollup: each
            # tick folds completed ledger slots (feeding the
            # wire_to_durable histogram + saturation gauges) before burn
            # evaluation reads them, so alerts lag at most one tick.
            if (
                self._mp_ingester is not None
                and getattr(self._mp_ingester, "critpath", None) is not None
            ):
                self._obs_windows.on_tick(self._mp_ingester.critpath.on_tick)
            # query stitcher on the same ticker, also BEFORE the
            # watchdog: each tick folds completed query traces (feeding
            # the query_wall histogram; query_lock_wait lands directly
            # from the lock) before burn evaluation reads them.
            if self._querytrace is not None and self.config.obs_query_enabled:
                self._obs_windows.on_tick(self._querytrace.on_tick)
            # mirror publisher on the same ticker, after the stitchers
            # and BEFORE the watchdog: each tick cuts a fresh epoch (one
            # aggregator-lock hold runs all packed reads) so queries
            # serve at most one tick stale under continuous ingest, and
            # burn evaluation reads this tick's mirror gauges. paced:
            # when a publish costs more than a tick (slow device reads),
            # the duty-cycle cap leaves at least equal lock time free
            # between epochs for fresh reads and ingest.
            # time-tier sealer on the same ticker, BEFORE the mirror
            # publisher (ISSUE 15): each tick freezes finished device
            # time buckets into host segments, so the epoch the
            # publisher cuts next already serves demand-registered
            # windowed ``ttq:`` keys from sealed segments (no aggregator
            # lock in those computes).
            if getattr(core, "timetier", None) is not None:
                self._obs_windows.on_tick(lambda _w: core.tt_seal())
            if self._mirror is not None and self._mirror.enabled:
                _mirror_core = getattr(
                    self.storage, "delegate", self.storage
                )
                self._obs_windows.on_tick(
                    lambda _w: _mirror_core.publish_mirror(paced=True)
                )
            if self.config.obs_slo_enabled:
                from zipkin_tpu.obs.slo import SloWatchdog, default_specs

                self._obs_slo = SloWatchdog(
                    self._obs_windows,
                    default_specs(
                        short_s=self.config.obs_slo_short_s,
                        long_s=self.config.obs_slo_long_s,
                        burn_threshold=self.config.obs_slo_burn_threshold,
                    ),
                )
                # incident capture (obs/incidents.py): every SLO trip
                # snapshots the volatile planes — slow ring, windowed
                # percentiles, waterfalls — into a bounded-retention
                # bundle before the evidence rotates out.
                if self.config.obs_incident_dir:
                    from zipkin_tpu.obs.incidents import IncidentRecorder

                    self._obs_incidents = IncidentRecorder(
                        self.config.obs_incident_dir,
                        retention=self.config.obs_incident_retention,
                    )
                    self._wire_incident_sources(core)
                    self._obs_slo.on_trip.append(
                        self._obs_incidents.on_slo_trip
                    )
        # overload control plane (runtime/overload.py, ISSUE 13): folds
        # the published signals into the brownout ladder every telemetry
        # tick. Constructed even without the windowed plane (tests and
        # embedders drive evaluate() directly); when windows run, the
        # controller subscribes AFTER the stitchers — it reads the
        # gauges the same tick just folded.
        self._overload = None
        if self.config.overload_enabled:
            from zipkin_tpu.runtime.overload import OverloadController

            core = getattr(self.storage, "delegate", self.storage)
            self._overload = OverloadController(
                enter=(
                    self.config.overload_enter_b1,
                    self.config.overload_enter_b2,
                    self.config.overload_enter_b3,
                ),
                exit_margin=self.config.overload_exit_margin,
                dwell_ticks=self.config.overload_dwell_ticks,
                max_stale_ms=self.config.overload_max_stale_ms,
                retry_base_s=self.config.overload_retry_base_s,
                # B2 bulk sheds nudge the sampling tier's pressure hook:
                # sustained overload degrades into lower sampling rates
                # instead of an ever-taller wall of 429s
                rate_controller=getattr(core, "sampling_controller", None),
            )
            # ingest admission gate: the collector consults the ladder
            # before any parse or queue hand-off
            self.collector.overload = self._overload
            # read-mode seam: the store's cached-read path serves
            # cache-first (B1/B2) / cache-only (B3) within the stated
            # staleness bound
            core.overload = self._overload
            # B1 observability shed: self-spans and slowest-chunk
            # timelines are the first cargo overboard
            if self._obs_emitter is not None:
                self._obs_emitter.gate = self._overload.shed_observability
            if self._obs_windows is not None:
                self._obs_windows.on_tick(self._overload.on_tick)
            # every ladder transition is an incident: capture the flight
            # around the brownout before the volatile planes rotate
            if self._obs_incidents is not None:
                self._obs_incidents.add_source(
                    "overload", self._overload.status
                )
                rec = self._obs_incidents
                self._overload.on_transition.append(
                    lambda ev: rec.capture({
                        "kind": "overload_transition",
                        "name": f"overload-{ev['from']}-to-{ev['to']}",
                        **ev,
                    })
                )
            # tenant-isolated admission (runtime/tenant.py, ISSUE 18):
            # per-tenant ingest budgets and tenant-scoped brownout
            # levels folded by the controller each tick. Constructed
            # even with a zero budget (accounting-only) so per-tenant
            # counters and /statusz rows always publish; enforcement
            # arms when TPU_TENANT_INGEST_BYTES_PER_S > 0.
            if self.config.tenant_enabled:
                from zipkin_tpu.runtime.tenant import TenantAdmission

                retained_table = None
                rc = getattr(core, "sampling_controller", None)
                if self.config.tenant_retained_spans_per_s > 0:
                    from zipkin_tpu.sampling.controller import (
                        TenantBudgetTable,
                    )

                    # retained-spans/sec budget, charged at dispatcher
                    # ack time (span counts are only known post-parse)
                    # and consulted by admit() before accepting more
                    # bytes from a tenant already in debt
                    retained_table = TenantBudgetTable(
                        spans_per_s=self.config.tenant_retained_spans_per_s,
                        burst_s=self.config.tenant_ingest_burst_s,
                        max_tenants=self.config.tenant_max,
                    )
                    if rc is not None:
                        rc.tenant_table = retained_table
                ta = TenantAdmission(
                    bytes_per_s=self.config.tenant_ingest_bytes_per_s,
                    burst_s=self.config.tenant_ingest_burst_s,
                    max_tenants=self.config.tenant_max,
                    flood_ratio=self.config.tenant_flood_ratio,
                    dwell_ticks=self.config.tenant_dwell_ticks,
                    retained_table=retained_table,
                )
                self._overload.tenant_admission = ta
                if self._mp_ingester is not None:
                    # the dispatcher attributes each acked payload's
                    # span count back to its tenant (thread-safe sink)
                    self._mp_ingester.tenant_sink = ta.note_retained
                # tenant-scoped SLOs (PR 9 grammar): one shed-ratio
                # spec per TPU_TENANT_SLO entry, evaluated over that
                # tenant's own counters only
                if self._obs_slo is not None and self.config.tenant_slo_tenants:
                    from zipkin_tpu.obs.slo import tenant_specs

                    for t in self.config.tenant_slo_tenants:
                        for spec in tenant_specs(
                            t,
                            short_s=self.config.obs_slo_short_s,
                            long_s=self.config.obs_slo_long_s,
                            burn_threshold=self.config.obs_slo_burn_threshold,
                        ):
                            self._obs_slo.add_spec(spec)
        self.components: Dict[str, Component] = {self.config.storage_type: self.storage}
        self._runner: Optional[web.AppRunner] = None
        self._grpc = None
        self._scribe = None
        self._snapshot_task = None

    # -- app ---------------------------------------------------------------

    def make_app(self) -> web.Application:
        app = web.Application(client_max_size=64 * 1024 * 1024)
        if self.config.deadline_propagation_enabled:
            # outermost: stamp the caller's X-Request-Timeout-Ms budget
            # before any other middleware spends time on the request
            app.middlewares.append(self._deadline_middleware)
        if self.config.self_tracing_enabled:
            from zipkin_tpu.server.self_tracing import self_tracing_middleware

            app.middlewares.append(
                self_tracing_middleware(
                    Collector(
                        self.storage,
                        metrics=self.metrics.for_transport("self"),
                    ),
                    sample_rate=self.config.self_tracing_sample_rate,
                )
            )
        r = app.router
        if self.config.http_collector_enabled:
            r.add_post("/api/v2/spans", self.post_spans_v2)
            r.add_post("/api/v1/spans", self.post_spans_v1)
        r.add_get("/api/v2/traces", self.get_traces)
        r.add_get("/api/v2/trace/{trace_id}", self.get_trace)
        r.add_get("/api/v2/traceMany", self.get_trace_many)
        r.add_get("/api/v2/services", self.get_services)
        r.add_get("/api/v2/spans", self.get_span_names)
        r.add_get("/api/v2/remoteServices", self.get_remote_services)
        r.add_get("/api/v2/dependencies", self.get_dependencies)
        r.add_get("/api/v2/autocompleteKeys", self.get_autocomplete_keys)
        r.add_get("/api/v2/autocompleteValues", self.get_autocomplete_values)
        if hasattr(self.storage, "latency_quantiles"):
            # TPU aggregation tier extensions (sketch-served reads)
            r.add_get("/api/v2/tpu/percentiles", self.get_tpu_percentiles)
            r.add_get("/api/v2/tpu/cardinalities", self.get_tpu_cardinalities)
            r.add_get("/api/v2/tpu/counters", self.get_tpu_counters)
            r.add_get("/api/v2/tpu/overview", self.get_tpu_overview)
            r.add_post("/api/v2/tpu/snapshot", self.post_tpu_snapshot)
        # flight-recorder debug plane: the recorder is process-global,
        # so this serves regardless of the storage tier
        r.add_get("/api/v2/tpu/statusz", self.get_tpu_statusz)
        r.add_get("/health", self.get_health)
        r.add_get("/info", self.get_info)
        r.add_get("/metrics", self.get_metrics)
        r.add_get("/prometheus", self.get_prometheus)
        r.add_get("/config.json", self.get_ui_config)
        r.add_get("/zipkin/", self.get_ui)
        r.add_get("/zipkin", self.get_ui)
        r.add_get("/zipkin/static/{name}", self.get_ui_asset)
        return app

    # Span fields are attacker-controlled and the app renders them; even
    # with the esc() discipline (pinned by tests/test_ui_assets.py) the
    # UI ships defense-in-depth: only same-origin scripts execute, so an
    # escaping regression cannot become script execution. 'unsafe-inline'
    # styles stay allowed — the app positions bars with style attributes.
    _UI_CSP = (
        "default-src 'self'; script-src 'self'; style-src 'self' "
        "'unsafe-inline'; img-src 'self' data:; object-src 'none'; "
        "base-uri 'none'; frame-ancestors 'none'"
    )

    async def get_ui(self, request: web.Request) -> web.Response:
        from zipkin_tpu.server.ui import index_page

        return web.Response(
            text=index_page(), content_type="text/html",
            headers={"Content-Security-Policy": self._UI_CSP},
        )

    async def get_ui_asset(self, request: web.Request) -> web.Response:
        from zipkin_tpu.server.ui import asset

        found = asset(request.match_info["name"])
        if found is None:
            return web.Response(status=404, text="no such asset")
        body, ctype = found
        return web.Response(
            body=body, content_type=ctype,
            headers={"Content-Security-Policy": self._UI_CSP},
        )

    async def start(self) -> "ZipkinServer":
        app = self.make_app()
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.config.host, self.config.port)
        await site.start()
        if self.config.grpc_collector_enabled:
            from zipkin_tpu.server.grpc import GrpcCollectorServer

            grpc_collector = Collector(
                self.storage,
                sampler=self.collector.sampler,
                metrics=self.metrics.for_transport("grpc"),
                # without this the gRPC tier decodes proto3 on the
                # Python object path (~15k spans/s measured) while
                # HTTP rides the native parser — the r4 "line-rate
                # gRPC" claim depends on the fast path here too
                fast_ingest=self.config.tpu_fast_ingest,
                # SpanService/Report routes into the SAME parse
                # fan-out as HTTP (ISSUE 8): proto3 is the tier's
                # preferred wire, not the odd one out
                mp_ingester=self._mp_ingester,
                shadow=self._obs_shadow,
            )
            # same brownout admission as HTTP: the ladder must not have
            # a transport-shaped hole in it
            grpc_collector.overload = self._overload
            self._grpc = GrpcCollectorServer(
                grpc_collector,
                host=self.config.host,
                port=self.config.grpc_port,
                deadlines=self.config.deadline_propagation_enabled,
            )
            await self._grpc.start()
        if self.config.scribe_enabled:
            from zipkin_tpu.collector.scribe import ScribeCollector

            self._scribe = ScribeCollector(
                Collector(
                    self.storage,
                    sampler=self.collector.sampler,
                    metrics=self.metrics.for_transport("scribe"),
                    shadow=self._obs_shadow,
                ),
                host=self.config.host,
                port=self.config.scribe_port,
            )
            await self._scribe.start()
            self.components["scribe"] = self._scribe
        if (
            self.config.tpu_snapshot_interval_s > 0
            and getattr(self.storage, "checkpoint_dir", None)
            and hasattr(self.storage, "snapshot")
        ):
            # periodic snapshots close the durability loop: they bound
            # WAL growth (segments covered by a snapshot are deleted)
            # and bound the replay window after a crash. The reference
            # has no in-process analog — its durability is the storage
            # backend's (SURVEY.md §5 checkpoint row).
            self._snapshot_task = asyncio.create_task(
                self._snapshot_loop(self.config.tpu_snapshot_interval_s)
            )
        if self._obs_windows is not None:
            self._obs_windows.start_ticker()
        logger.info("zipkin-tpu listening on :%d", self.config.port)
        return self

    async def _snapshot_loop(self, interval_s: float) -> None:
        while True:
            await asyncio.sleep(interval_s)
            try:
                path = await asyncio.to_thread(self.storage.snapshot)
                logger.info("periodic snapshot -> %s", path)
            except asyncio.CancelledError:  # pragma: no cover
                raise
            except Exception:  # pragma: no cover - keep the loop alive
                logger.exception("periodic snapshot failed; will retry")

    async def stop(self) -> None:
        if self._obs_windows is not None:
            # first: the ticker's counter source reads the storage,
            # which teardown below closes
            await asyncio.to_thread(self._obs_windows.stop_ticker)
        take_final_snapshot = self._snapshot_task is not None
        if self._snapshot_task is not None:
            self._snapshot_task.cancel()
            try:
                await self._snapshot_task
            except (asyncio.CancelledError, Exception):
                pass
            self._snapshot_task = None
        if self._scribe is not None:
            await self._scribe.stop()
            self._scribe = None
        if self._grpc is not None:
            await self._grpc.stop()
            self._grpc = None
        if self._runner is not None:
            await self._runner.cleanup()
        if self._mp_ingester is not None:
            try:
                # finish queued payloads before teardown (202s issued)
                await asyncio.to_thread(self._mp_ingester.drain)
            except Exception:
                logger.exception("mp-ingest drain failed during stop")
            finally:
                # close() must always run: it joins the worker processes
                # and unlinks the shared-memory block
                await asyncio.to_thread(self._mp_ingester.close)
                self._mp_ingester = None
        if self._obs_emitter is not None:
            # before any final snapshot: the emitter's last flush feeds
            # spans into storage, and stop() disarms the global recorder
            # budgets/hook this server installed
            try:
                await asyncio.to_thread(self._obs_emitter.stop)
            finally:
                self._obs_emitter = None
        if take_final_snapshot:
            # final snapshot LAST: collectors are stopped and the MP
            # queue drained, so every 202-acked span is in storage —
            # snapshotting earlier would strand post-snapshot spans in
            # the WAL (or, without a WAL, lose them)
            try:
                await asyncio.to_thread(self.storage.snapshot)
            except Exception:  # pragma: no cover
                logger.exception("shutdown snapshot failed")
        self.storage.close()

    # -- deadlines + backoff guidance (ISSUE 13) ---------------------------

    @web.middleware
    async def _deadline_middleware(self, request, handler):
        """Stamp the caller's ``X-Request-Timeout-Ms`` budget at the
        earliest server-side instant; handlers check it right before
        their expensive dispatch. gRPC carries the same contract via
        its native deadline (``context.time_remaining``)."""
        raw = request.headers.get("X-Request-Timeout-Ms")
        if raw:
            try:
                budget_ms = float(raw)
            except ValueError:
                budget_ms = None  # malformed header: no deadline
            if budget_ms is not None:
                request["deadline_mono"] = (
                    time.monotonic() + max(0.0, budget_ms) / 1000.0
                )
        return await handler(request)

    def _deadline_expired(self, request) -> Optional[web.Response]:
        """504 when the caller's budget is already spent — counted on
        the controller so ``deadlineExpired`` surfaces on /metrics."""
        deadline = request.get("deadline_mono")
        if deadline is None or time.monotonic() <= deadline:
            return None
        if self._overload is not None:
            self._overload.note_deadline_expired()
        return web.Response(
            status=504,
            text="deadline expired before dispatch",
            headers={"X-Deadline-Expired": "1"},
        )

    def _backoff_headers(self, exc=None) -> Dict[str, str]:
        """Retry guidance for a shed: ``Retry-After`` is RFC
        delta-seconds (integer, so ceil); ``X-Retry-After-Ms`` preserves
        sub-second precision. When the shed carries a scope (ISSUE 18)
        the delay is the one the rejecting control computed — a
        tenant-budget shed advertises THAT tenant's bucket deficit, not
        the global ladder's jittered backoff — and
        ``X-Shed-Scope``/``X-Shed-Tenant`` say which control rejected
        the payload."""
        if self._overload is None:
            return {}
        delay_s = getattr(exc, "retry_after_s", None)
        scope = getattr(exc, "scope", None)
        tenant = getattr(exc, "tenant", None)
        if delay_s is None:
            delay_s = self._overload.retry_after_s(
                tenant if scope == "tenant" else None
            )
        headers = {
            "Retry-After": str(max(1, int(-(-delay_s // 1)))),
            "X-Retry-After-Ms": str(int(delay_s * 1000.0)),
        }
        if scope:
            headers["X-Shed-Scope"] = str(scope)
        if tenant:
            headers["X-Shed-Tenant"] = str(tenant)
        return headers

    # -- ingest ------------------------------------------------------------

    MAX_INFLATED = 256 * 1024 * 1024  # decompression-bomb guard

    async def _read_body(self, request: web.Request) -> bytes:
        # aiohttp transparently inflates Content-Encoding: gzip; the magic
        # check also covers clients that compress without the header. Inflate
        # incrementally with a size cap: client_max_size only bounds the
        # COMPRESSED bytes, so a gzip bomb must not materialize unbounded.
        body = await request.read()
        if body[:2] == b"\x1f\x8b":
            import zlib

            chunks: List[bytes] = []
            total = 0
            remaining = body
            while remaining:  # multi-member gzip is valid per RFC 1952
                d = zlib.decompressobj(wbits=31)
                out = d.decompress(remaining, self.MAX_INFLATED - total)
                total += len(out)
                if d.unconsumed_tail:
                    raise PayloadTooLarge(
                        f"gzip payload inflates past {self.MAX_INFLATED} bytes"
                    )
                chunks.append(out)
                remaining = d.unused_data
            body = b"".join(chunks)
        return body

    async def post_spans_v2(self, request: web.Request) -> web.Response:
        return await self._ingest(request, v1=False)

    async def post_spans_v1(self, request: web.Request) -> web.Response:
        return await self._ingest(request, v1=True)

    # zt-ingest-boundary: HTTP POST /api/v{1,2}/spans is a wire
    # entrypoint — tenant identity is extracted from X-Tenant-Id here,
    # before the collector chokepoint runs admission
    async def _ingest(self, request: web.Request, *, v1: bool) -> web.Response:
        # body read → collector hand-off complete; the stage counts the
        # POSTs that end in the 202 ack, as it always has
        with obs.span("http_boundary") as boundary:
            response = await self._accept(request, v1, boundary.t0)
            if response.status != 202:
                boundary.drop()
        return response

    async def _accept(self, request: web.Request, v1: bool,
                      t0: float) -> web.Response:
        # critpath wire anchor: the same instant http_boundary measures
        # from, in the ns domain the interval ledger uses. Contextvars
        # survive asyncio.to_thread, so the MP submit path reads it.
        critpath.WIRE_T0_NS.set(int(t0 * 1e9))
        # tenant admission identity (ISSUE 18): absent or hostile header
        # values normalize to the default tenant, so legacy clients keep
        # flowing; the collector chokepoint reads the contextvar (which
        # survives asyncio.to_thread) for budget attribution
        CURRENT_TENANT.set(
            normalize_tenant(request.headers.get(TENANT_HEADER))
        )
        try:
            body = await self._read_body(request)
        except PayloadTooLarge as e:
            return web.Response(status=413, text=str(e))
        except Exception:
            return web.Response(status=400, text="cannot gunzip body")
        ctype = request.headers.get("Content-Type", "").split(";")[0].strip()
        encoding: Optional[Encoding] = None
        if ctype == "application/x-protobuf":
            encoding = Encoding.PROTO3
        elif ctype == "application/x-thrift":
            encoding = Encoding.THRIFT
        elif ctype == JSON and v1:
            encoding = Encoding.JSON_V1
        # else: sniff (covers missing/odd content types)
        # deadline propagation (ISSUE 13): the caller's budget may have
        # expired while the body was read — work already past its
        # deadline must be dropped BEFORE the collector dispatches it,
        # or an overloaded tier burns capacity on answers nobody awaits
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        try:
            await asyncio.to_thread(self.collector.accept_spans_bytes, body, encoding)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        except RejectedExecutionError as e:
            # storage throttle shed the write: tell the sender to back off
            # (reference behavior for RejectedExecutionException)
            return web.Response(status=503, text=str(e))
        except IngestBackpressure as e:
            # a tenant budget shed the payload, every parse-worker
            # queue in the fan-out tier is full, or the global brownout
            # ladder shed it: 429 (Too Many Requests) — transient,
            # retryable, distinct from the throttle's 503 so dashboards
            # can tell the tiers apart. Retry-After carries backoff
            # scoped to whichever control rejected the payload
            # (X-Shed-Scope: tenant|global, ISSUE 18); the millisecond
            # twin keeps sub-second precision visible to clients that
            # want to decorrelate.
            return web.Response(
                status=429, text=str(e), headers=self._backoff_headers(e)
            )
        return web.Response(status=202)

    # -- query -------------------------------------------------------------

    def _parse_query(self, request: web.Request) -> QueryRequest:
        q = request.query

        def opt_int(name: str) -> Optional[int]:
            raw = q.get(name)
            return int(raw) if raw else None

        import time

        end_ts = opt_int("endTs") or int(time.time() * 1000)
        lookback = opt_int("lookback") or self.config.default_lookback
        return QueryRequest(
            end_ts=end_ts,
            lookback=lookback,
            limit=opt_int("limit") or self.config.query_limit,
            service_name=q.get("serviceName"),
            remote_service_name=q.get("remoteServiceName"),
            span_name=q.get("spanName"),
            annotation_query=parse_annotation_query(q.get("annotationQuery")),
            min_duration=opt_int("minDuration"),
            max_duration=opt_int("maxDuration"),
        )

    async def get_traces(self, request: web.Request) -> web.Response:
        try:
            query = self._parse_query(request)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        traces = await asyncio.to_thread(
            lambda: self.storage.span_store().get_traces_query(query).execute()
        )
        return web.json_response(
            [[json_v2.span_to_dict(s) for s in t] for t in traces]
        )

    async def get_trace(self, request: web.Request) -> web.Response:
        raw_id = request.match_info["trace_id"]
        try:
            normalize_trace_id(raw_id)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        spans = await asyncio.to_thread(
            lambda: self.storage.span_store().get_trace(raw_id).execute()
        )
        if not spans:
            return web.Response(status=404, text=f"trace {raw_id} not found")
        return web.json_response([json_v2.span_to_dict(s) for s in spans])

    async def get_trace_many(self, request: web.Request) -> web.Response:
        raw = request.query.get("traceIds", "")
        ids = [x for x in raw.split(",") if x]
        if not ids:
            return web.Response(status=400, text="traceIds parameter is required")
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        traces = await asyncio.to_thread(
            lambda: self.storage.traces().get_traces(ids).execute()
        )
        return web.json_response(
            [[json_v2.span_to_dict(s) for s in t] for t in traces]
        )

    async def get_services(self, request: web.Request) -> web.Response:
        names = await asyncio.to_thread(
            lambda: self.storage.service_and_span_names().get_service_names().execute()
        )
        return web.json_response(names)

    async def get_span_names(self, request: web.Request) -> web.Response:
        service = request.query.get("serviceName", "")
        names = await asyncio.to_thread(
            lambda: self.storage.service_and_span_names()
            .get_span_names(service)
            .execute()
        )
        return web.json_response(names)

    async def get_remote_services(self, request: web.Request) -> web.Response:
        service = request.query.get("serviceName", "")
        names = await asyncio.to_thread(
            lambda: self.storage.service_and_span_names()
            .get_remote_service_names(service)
            .execute()
        )
        return web.json_response(names)

    @staticmethod
    def _staleness_param(request: web.Request) -> Optional[float]:
        """Per-request mirror staleness bound (ms). ``staleness_ms<=0``
        forces the fresh locked read; absent means the server default.
        Raises ValueError on garbage (callers 400 it)."""
        raw = request.query.get("staleness_ms")
        return float(raw) if raw is not None else None

    async def get_dependencies(self, request: web.Request) -> web.Response:
        raw_end = request.query.get("endTs")
        if not raw_end:
            return web.Response(status=400, text="endTs parameter is required")
        try:
            end_ts = int(raw_end)
            lookback = int(request.query.get("lookback") or self.config.default_lookback)
            staleness = self._staleness_param(request)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        # per-request staleness bound routes through only when the
        # backing store HAS a mirror (the in-memory tier's SPI signature
        # stays byte-compatible with the reference)
        kwargs = (
            {"staleness_ms": staleness}
            if staleness is not None and self._mirror is not None
            else {}
        )
        links = await asyncio.to_thread(
            lambda: self.storage.span_store()
            .get_dependencies(end_ts, lookback, **kwargs)
            .execute()
        )
        return web.json_response([json_v2.link_to_dict(x) for x in links])

    async def get_autocomplete_keys(self, request: web.Request) -> web.Response:
        keys = await asyncio.to_thread(
            lambda: self.storage.autocomplete_tags().get_keys().execute()
        )
        return web.json_response(keys)

    async def get_autocomplete_values(self, request: web.Request) -> web.Response:
        key = request.query.get("key")
        if not key:
            return web.Response(status=400, text="key parameter is required")
        values = await asyncio.to_thread(
            lambda: self.storage.autocomplete_tags().get_values(key).execute()
        )
        return web.json_response(values)

    # -- TPU aggregation tier extensions -----------------------------------
    # Not part of the reference HTTP surface: these serve the sketch reads
    # the BASELINE north star adds (latency percentiles, trace cardinality)
    # straight from device state. The Lens-compatible endpoints above stay
    # byte-compatible; these are additive under /api/v2/tpu/.

    async def get_tpu_percentiles(self, request: web.Request) -> web.Response:
        raw_q = request.query.get("q", "0.5,0.9,0.99")
        try:
            qs = [float(x) for x in raw_q.split(",") if x]
            if not qs or any(not (0.0 <= q <= 1.0) for q in qs):
                raise ValueError(f"q out of range: {raw_q!r}")
            # optional endTs/lookback (ms, the query-API convention) route
            # to the time-sliced histograms — windowed percentiles
            end_ts = request.query.get("endTs")
            lookback = request.query.get("lookback")
            end_ts = int(end_ts) if end_ts is not None else None
            lookback = int(lookback) if lookback is not None else None
            staleness = self._staleness_param(request)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        rows = await asyncio.to_thread(
            self.storage.latency_quantiles,
            qs,
            request.query.get("serviceName"),
            request.query.get("spanName"),
            request.query.get("sketch", "digest") == "digest",
            end_ts,
            lookback,
            staleness,
        )
        return web.json_response(rows)

    async def get_tpu_cardinalities(self, request: web.Request) -> web.Response:
        try:
            staleness = self._staleness_param(request)
            # optional endTs/lookback (ms, the query-API convention)
            # route to the time tier — windowed cardinalities over the
            # covering bucket segments (HLL register-max merge)
            end_ts = request.query.get("endTs")
            lookback = request.query.get("lookback")
            end_ts = int(end_ts) if end_ts is not None else None
            lookback = int(lookback) if lookback is not None else None
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        return web.json_response(
            await asyncio.to_thread(
                self.storage.trace_cardinalities, staleness, end_ts, lookback
            )
        )

    async def get_tpu_counters(self, request: web.Request) -> web.Response:
        return web.json_response(
            await asyncio.to_thread(self.storage.ingest_counters)
        )

    async def get_tpu_overview(self, request: web.Request) -> web.Response:
        """Percentiles + cardinalities + counters in ONE storage read —
        one aggregator dispatch and one device→host transfer — instead
        of the three requests the UI sketch page used to issue."""
        if not hasattr(self.storage, "sketch_overview"):
            return web.Response(
                status=501, text="storage does not serve sketch_overview"
            )
        raw_q = request.query.get("q", "0.5,0.9,0.99")
        try:
            qs = [float(x) for x in raw_q.split(",") if x]
            if not qs or any(not (0.0 <= q <= 1.0) for q in qs):
                raise ValueError(f"q out of range: {raw_q!r}")
            staleness = self._staleness_param(request)
        except ValueError as e:
            return web.Response(status=400, text=str(e))
        expired = self._deadline_expired(request)
        if expired is not None:
            return expired
        body = await asyncio.to_thread(
            self.storage.sketch_overview,
            qs,
            request.query.get("serviceName"),
            request.query.get("spanName"),
            staleness,
        )
        return web.json_response(body)

    async def post_tpu_snapshot(self, request: web.Request) -> web.Response:
        if not hasattr(self.storage, "snapshot"):
            return web.Response(status=501, text="storage does not snapshot")
        path = await asyncio.to_thread(self.storage.snapshot)
        if path is None:
            return web.Response(status=409, text="no checkpoint_dir configured")
        return web.json_response({"snapshot": path})

    # -- ops ---------------------------------------------------------------

    async def get_health(self, request: web.Request) -> web.Response:
        results = {}
        overall_up = True
        for name, component in self.components.items():
            result = await asyncio.to_thread(component.check)
            results[name] = {
                "status": "UP" if result.ok else "DOWN",
                **({"error": str(result.error)} if result.error else {}),
            }
            overall_up &= result.ok
        body = {"status": "UP" if overall_up else "DOWN", "zipkin": results}
        return web.json_response(body, status=200 if overall_up else 503)

    async def get_info(self, request: web.Request) -> web.Response:
        return web.json_response(
            {"zipkin": {"version": zipkin_tpu.__version__, "flavor": "tpu"}}
        )

    def _window_counter_source(self) -> dict:
        """Counters the windowed plane samples each tick: transport-
        summed collector tallies (the wire-to-ack SLO's numerators) plus
        the storage tier's flat ingest counters."""
        sums = {"messages": 0, "messages_dropped": 0,
                "spans": 0, "spans_dropped": 0}
        for key, value in self.metrics.snapshot().items():
            _, _, name = key.partition(".")
            if name in sums:
                sums[name] += value
        out = {
            "collectorMessages": sums["messages"],
            "collectorMessagesDropped": sums["messages_dropped"],
            "collectorSpans": sums["spans"],
            "collectorSpansDropped": sums["spans_dropped"],
        }
        if hasattr(self.storage, "ingest_counters"):
            try:
                out.update(self.storage.ingest_counters())
            except Exception:
                pass
        # per-tenant admission counters (ISSUE 18): the windowed plane
        # must see tenantOffered_<slug>/tenantShed_<slug> so the
        # tenant-scoped shed-ratio SloSpecs can burn against them
        if self._overload is not None:
            try:
                out.update(self._overload.counters())
            except Exception:
                pass
        return out

    def _windows_catch_up(self) -> None:
        """Read-path tick driver: keeps windows/SLO fresh on servers
        that never ran start() (TestServer embedding). Blocking —
        call via asyncio.to_thread."""
        w = self._obs_windows
        if w is not None and not w.ticker_running:
            w.tick_if_due()

    def _wire_incident_sources(self, core) -> None:
        """Register the statusz-equivalent dict builders an incident
        bundle snapshots. The recorder wraps each source in its own
        try/except, so a torn plane degrades to an error note inside
        the bundle instead of losing it."""
        rec = self._obs_incidents
        rec.add_source("slo", self._obs_slo.status)
        rec.add_source("windows", self._obs_windows.status)
        rec.add_source("stages", lambda: {
            st.stage: {"count": st.count, "p50Us": st.p50_us,
                       "p99Us": st.p99_us, "maxUs": st.max_us}
            for st in obs.RECORDER.snapshot().nonzero()
        })
        rec.add_source("slowRing", obs.RECORDER.slow_events)
        if hasattr(core, "ingest_counters"):
            rec.add_source("counters", core.ingest_counters)
        if self._querytrace is not None:
            rec.add_source("queries", self._querytrace.waterfall)
        ing = self._mp_ingester
        cp = getattr(ing, "critpath", None) if ing is not None else None
        if cp is not None:
            rec.add_source("critpath", cp.waterfall)

    async def get_metrics(self, request: web.Request) -> web.Response:
        """Actuator-style counters, reference catalogue kept verbatim:
        ``counter.zipkin_collector.spans.http`` etc."""
        out = {}
        for key, value in self.metrics.snapshot().items():
            transport, _, name = key.partition(".")
            out[f"counter.zipkin_collector.{name}.{transport}"] = value
        # boot-time restore gauges (ISSUE 3): cost of the last recovery
        restore = getattr(self.storage, "restore_stats", None)
        if restore:
            for name, value in restore.items():
                out[f"gauge.zipkin_tpu.{name}"] = value
        # incremental link-ctx gauges (ISSUE 5): since-rollup delta size,
        # advance count, and device time of the last ctx-advancing program
        counters = None
        if hasattr(self.storage, "ingest_counters"):
            counters = await asyncio.to_thread(self.storage.ingest_counters)
            for name in ("ctxDeltaLanes", "ctxAdvances", "ctxMaintenanceMs"):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            # fan-out tier gauges (ISSUE 8): pool size/health, bounded-queue
            # posture, and the acked-span accounting that proves zero loss
            for name in (
                "mpWorkers", "mpWorkersAlive", "mpQueueDepth", "mpInflight",
                "mpAccepted", "mpSampleDropped", "mpFallbacks", "mpRejected",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            # critical-path stitcher (ISSUE 11): timeline accounting and
            # the Little's-law saturation gauges behind the queue SLO
            for name in (
                "critpathTimelines", "critpathSkipped", "critpathAbandoned",
                "critpathReclaimed", "critpathDegraded", "critpathTruncated",
                "critpathLambdaCps", "critpathLittleL",
                "critpathWorkerOccupancy", "critpathQueueSaturation",
                "critpathConservationP50Milli",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            # query-plane observatory (ISSUE 12): stitched query walls,
            # the aggregator-lock contention ledger, and cached-read
            # staleness (age-at-serve)
            for name in (
                "queryTraces", "queryWallP50Us", "queryWallP99Us",
                "queryWallMaxUs", "queryConservationP50Milli",
                "queryLockAcquisitions", "queryLockContended",
                "queryLockReentries", "queryLockWaiters",
                "queryLockWaitersHighWater", "queryLockWaitP50Us",
                "queryLockWaitP99Us", "queryLockWaitMaxUs",
                "queryLockHoldP50Us", "queryLockHoldP99Us",
                "queryLockHoldMaxUs", "readCacheServeAgeMs",
                "readCacheServeAgeMaxMs", "readCacheEntries",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            # epoch-published read mirror (ISSUE 14): publish cadence,
            # serve tallies, and staleness-at-serve — the gauges the
            # query_mirror_staleness SLO reads
            for name in (
                "mirrorGeneration", "mirrorPublishes", "mirrorPublishSkips",
                "mirrorPublishBackoffs",
                "mirrorPublishMs", "mirrorServes", "mirrorStaleServes",
                "mirrorMisses", "mirrorServeAgeMs", "mirrorServeAgeMaxMs",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            # scale-out read serving (ISSUE 19): shm segment publication
            # ledger + the reader-fleet heartbeat rollup (demand-ring
            # traffic, max staleness over alive readers, respawns)
            for name in (
                "segmentGeneration", "segmentPublishes",
                "segmentPublishErrors", "segmentOverflows",
                "segmentSkippedKeys", "segmentPayloadBytes",
                "segmentSerializeMs", "mirrorSegmentSinkErrors",
                "readerRespawns", "readerDemandRequests",
                "readerDemandOverflow", "readerDemandUnparsed",
                "readerServeAgeMs", "readerGenerationLagMax",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
        # sampling-tier gauges (ISSUE 4): retention verdict tallies, the
        # controller's budget posture, and the live per-service keep rate
        if getattr(self.storage, "sampler", None) is not None:
            if counters is None:
                counters = await asyncio.to_thread(
                    self.storage.ingest_counters
                )
            for name in (
                "sampledKept", "sampledDropped", "budgetUtilization",
                "samplerPublishes", "samplerPressure",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
            rates = await asyncio.to_thread(self.storage.sampler_rates)
            for svc, rate in sorted(rates.items()):
                out[f"gauge.zipkin_tpu.samplerRate.{svc}"] = rate
        # durability-plane gauges (ISSUE 7): at-rest scrub progress and
        # quarantine tallies (restoreFallbacks / generationsQuarantined
        # already flow via the restore_stats block above)
        if counters:
            for name in (
                "scrubBytes", "scrubPasses", "scrubCorruptDetected",
                "segmentsQuarantined", "spansQuarantined",
                "archiveSegmentsQuarantined", "archiveSpansQuarantined",
            ):
                if name in counters:
                    out[f"gauge.zipkin_tpu.{name}"] = counters[name]
        # accuracy observatory (ISSUE 10): relative-error gauges from the
        # latest rollup plus the shadow's own occupancy counters
        if self._accuracy is not None:
            acc = await asyncio.to_thread(self._accuracy.export_counters)
            for name, value in sorted(acc.items()):
                out[f"gauge.zipkin_tpu.{name}"] = value
        # pipeline flight recorder (zipkin_tpu.obs): per-stage quantiles
        for st in obs.RECORDER.snapshot().nonzero():
            out[f"gauge.zipkin_tpu.stage.{st.stage}.p50Us"] = st.p50_us
            out[f"gauge.zipkin_tpu.stage.{st.stage}.p99Us"] = st.p99_us
            out[f"gauge.zipkin_tpu.stage.{st.stage}.maxUs"] = st.max_us
        # SLO watchdog verdicts (ISSUE 9): alert flag + per-window burn
        if self._obs_slo is not None:
            await asyncio.to_thread(self._windows_catch_up)
            for v in await asyncio.to_thread(self._obs_slo.verdicts):
                base = f"gauge.zipkin_tpu.slo.{v['name']}"
                out[f"{base}.alert"] = int(v["alert"])
                for wname, wv in v["windows"].items():
                    out[f"{base}.burn.{wname}"] = wv["burn"]
        # overload control plane (ISSUE 13): ladder level, load index,
        # per-class admit/shed tallies, deadline drops
        if self._overload is not None:
            for name, value in self._overload.counters().items():
                out[f"gauge.zipkin_tpu.{name}"] = value
        return web.json_response(out)

    async def get_prometheus(self, request: web.Request) -> web.Response:
        lines: List[str] = []
        # collector counters, one family per counter name, transport label
        by_name: Dict[str, List[Tuple[str, float]]] = {}
        for key, value in sorted(self.metrics.snapshot().items()):
            transport, _, name = key.partition(".")
            by_name.setdefault(name, []).append((transport, value))
        for name, rows in sorted(by_name.items()):
            fam = _prom_name(f"zipkin_collector_{name}_total")
            lines.append(
                f"# HELP {fam} Collector {name.replace('_', ' ')} by transport."
            )
            lines.append(f"# TYPE {fam} counter")
            for transport, value in rows:
                lines.append(
                    f'{fam}{{transport="{_prom_label(transport)}"}} {value}'
                )
        if hasattr(self.storage, "ingest_counters"):
            # device-tier gauges (sketch occupancy / ingest truth counters;
            # with the sampling tier armed this includes sampled_kept /
            # sampled_dropped / budget_utilization)
            counters = await asyncio.to_thread(self.storage.ingest_counters)
            for name, value in sorted(counters.items()):
                if isinstance(value, bool) or not isinstance(
                    value, (int, float)
                ):
                    continue  # nested tables (mpWorkerTable) ride /statusz
                fam = _prom_name(f"zipkin_tpu_{_snake(name)}")
                lines.append(f"# HELP {fam} Device-tier gauge {name}.")
                lines.append(f"# TYPE {fam} gauge")
                lines.append(f"{fam} {value}")
            lines.extend(_prom_mp_workers(counters.get("mpWorkerTable")))
            lines.extend(_prom_critpath(counters.get("critpathSegments")))
            # the flat queryLock*/queryWall* gauges rode the loop above;
            # this renders the labelled families (wait/hold histograms,
            # per-label holder attribution, per-segment aggregates)
            lines.extend(_prom_query_lock(counters.get("queryLock")))
            lines.extend(
                _prom_query_segments(counters.get("querySegments"))
            )
        if getattr(self.storage, "sampler", None) is not None:
            # live per-service keep probability (1.0 = keep everything)
            rates = await asyncio.to_thread(self.storage.sampler_rates)
            if rates:
                lines.append(
                    "# HELP zipkin_tpu_sampler_rate Live per-service keep "
                    "probability (1.0 = keep everything)."
                )
                lines.append("# TYPE zipkin_tpu_sampler_rate gauge")
                for svc, rate in sorted(rates.items()):
                    lines.append(
                        f'zipkin_tpu_sampler_rate{{service="{_prom_label(svc)}"}} {rate}'
                    )
        lines.extend(
            _prom_stage_histograms(
                obs.RECORDER.snapshot(), obs.RECORDER.slow_events()
            )
        )
        # accuracy observatory (ISSUE 10): the flat zipkin_tpu_accuracy_*
        # gauges already rode ingest_counters above; this adds the
        # per-service digest-error family (labels need their own render)
        if self._accuracy is not None:
            lines.extend(
                _prom_accuracy(await asyncio.to_thread(self._accuracy.status))
            )
        # SLO watchdog verdicts (ISSUE 9): boolean alert gauge (what pages)
        # plus the per-window burn rates behind it (what to graph)
        if self._obs_slo is not None:
            await asyncio.to_thread(self._windows_catch_up)
            lines.extend(
                _prom_slo(await asyncio.to_thread(self._obs_slo.verdicts))
            )
        # overload control plane (ISSUE 13): zipkin_tpu_overload_*
        # families — ladder posture, the folded signal set, admission
        # accounting, and deadline drops
        if self._overload is not None:
            status = self._overload.status()
            lines.extend(_prom_overload(status))
            # tenant isolation (ISSUE 18): {tenant=}-labelled admission
            # families, bounded by the tenant table's LRU cap
            lines.extend(_prom_tenants(status))
        return web.Response(text="\n".join(lines) + "\n")

    async def get_tpu_statusz(self, request: web.Request) -> web.Response:
        """Flight-recorder debug plane: full stage table, the recent
        over-budget event ring, and the recorder's own measured cost."""
        rec = obs.RECORDER
        snap = rec.snapshot()
        stages = {}
        for st in snap.stages():
            budget = rec.budget_us(st.stage)
            stages[st.stage] = {
                "count": st.count,
                "p50Us": st.p50_us,
                "p99Us": st.p99_us,
                "maxUs": st.max_us,
                "sumUs": st.sum_us,
                "budgetUs": int(budget) if budget != float("inf") else -1,
            }
        body = {
            "stages": stages,
            "slow": rec.slow_events(),
            "recorder": {
                "enabled": rec.enabled,
                "budgetScale": rec.budget_scale,
                "writerThreads": snap.locals_seen,
                "generation": snap.generation,
                "overheadNsPerRecord": await asyncio.to_thread(
                    rec.measure_overhead
                ),
                "selfSpans": self._obs_emitter is not None,
                "selfSpansEmitted": (
                    self._obs_emitter.emitted if self._obs_emitter else 0
                ),
            },
        }
        if (
            getattr(self.storage, "sampler", None) is not None
            and hasattr(self.storage, "ingest_counters")
        ):
            counters = await asyncio.to_thread(self.storage.ingest_counters)
            body["sampler"] = {
                name: counters[name]
                for name in (
                    "budgetUtilization", "samplerPublishes",
                    "samplerPressure", "sampledKept", "sampledDropped",
                )
                if name in counters
            }
        durability = await asyncio.to_thread(self._durability_status)
        if durability:
            body["durability"] = durability
        # windowed telemetry plane + SLO verdicts (ISSUE 9)
        if self._obs_windows is not None:
            await asyncio.to_thread(self._windows_catch_up)
            body["windows"] = await asyncio.to_thread(self._obs_windows.status)
        if self._obs_slo is not None:
            body["slo"] = await asyncio.to_thread(self._obs_slo.status)
        # accuracy observatory (ISSUE 10): the latest rollup's relative-
        # error gauges, per-service digest detail, and shadow occupancy
        if self._accuracy is not None:
            body["accuracy"] = await asyncio.to_thread(self._accuracy.status)
        # device-program observatory: compile counts, per-program device
        # wall, first-compile cost/memory analysis, HBM + transfer gauges
        from zipkin_tpu.obs.device import OBSERVATORY

        # ... and which devices: those of the mesh the store runs on
        agg = getattr(
            getattr(self.storage, "delegate", self.storage), "agg", None)
        body["device"] = await asyncio.to_thread(
            OBSERVATORY.status,
            list(agg.mesh.devices.flat) if agg is not None else None,
        )
        # per-worker attribution table from the fan-out tier (ISSUE 9
        # satellite): dispatcher-side tallies keyed by widx
        ing = getattr(self.storage, "mp_ingester", None)
        if ing is not None:
            stats = await asyncio.to_thread(ing.stats)
            if "mpWorkerTable" in stats:
                body["workers"] = stats["mpWorkerTable"]
            # ingest waterfall (ISSUE 11): exact windowed wire-to-durable,
            # queue-wait vs service decomposition, Little's-law gauges,
            # and the slowest folded chunk's segment timeline
            cp = getattr(ing, "critpath", None)
            if cp is not None:
                body["critpath"] = await asyncio.to_thread(cp.waterfall)
        # query-plane observatory (ISSUE 12): stitched per-query
        # waterfall (segment decomposition, conservation, the slowest
        # query) + the aggregator-lock contention ledger
        if self._querytrace is not None:
            body["queries"] = await asyncio.to_thread(
                self._querytrace.waterfall
            )
        # epoch-published read mirror (ISSUE 14): current snapshot epoch
        # (generation, write version, age) + publish/serve ledger
        if self._mirror is not None:
            body["mirror"] = await asyncio.to_thread(self._mirror.status)
        # scale-out read serving (ISSUE 19): shm segment generation,
        # payload size, and the per-reader heartbeat table (generation
        # lag, serve ages, demand-ring depth, respawn count) — the
        # segment name is here so `python -m zipkin_tpu.serving` can be
        # pointed at it (TPU_MIRROR_SEGMENT=<name>)
        seg = getattr(self.storage, "mirror_segment", None)
        if seg is not None:
            body["serving"] = await asyncio.to_thread(seg.status)
        # overload control plane (ISSUE 13): ladder state, the live
        # signal fold, admission posture, and the transition history
        if self._overload is not None:
            body["overload"] = self._overload.status()
        if self._obs_incidents is not None:
            body["incidents"] = self._obs_incidents.counters()
        return web.json_response(body)

    def _durability_status(self) -> Optional[dict]:
        """Durability section of /statusz (ISSUE 7): retained snapshot
        generations (quarantined ones included — they are the evidence),
        the WAL coverage window [floor, head], boot-restore fallback
        tallies, and the scrubber's last-pass summary. Blocking
        filesystem reads — call via ``asyncio.to_thread``."""
        ckpt = getattr(self.storage, "checkpoint_dir", None)
        scrubber = getattr(self.storage, "scrubber", None)
        wal = getattr(self.storage, "wal", None)
        if not ckpt and scrubber is None and wal is None:
            return None
        out: dict = {}
        if ckpt:
            from zipkin_tpu.tpu import snapshot as snap_mod

            out["generations"] = snap_mod.generation_status(ckpt)
            floor = snap_mod.retained_coverage(ckpt)
            out["walCoverage"] = {
                "floor": floor,
                "head": int(getattr(self.storage.agg, "wal_seq", 0)),
            }
        restore = getattr(self.storage, "restore_stats", None)
        if restore:
            out["restore"] = {
                name: restore[name]
                for name in (
                    "restoreFallbacks", "generationsQuarantined",
                    "walReplayBatches", "restoreMs",
                )
                if name in restore
            }
        if scrubber is not None:
            out["scrub"] = scrubber.status()
        return out

    async def get_ui_config(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "environment": "",
                "queryLimit": self.config.query_limit,
                "defaultLookback": self.config.default_lookback,
                "searchEnabled": self.config.search_enabled,
                "autocompleteKeys": list(self.config.autocomplete_keys),
                "dependency": {"enabled": True},
            }
        )


def _snake(name: str) -> str:
    out = []
    for ch in name:
        if ch.isupper():
            out.append("_")
            out.append(ch.lower())
        else:
            out.append(ch)
    return "".join(out)


def _prom_name(name: str) -> str:
    """Sanitize to the Prometheus metric-name charset ``[a-zA-Z0-9_:]``,
    mapping every other rune (dots included) to ``_`` — real scrapers
    reject the exposition otherwise."""
    out = "".join(
        ch if (ch.isascii() and (ch.isalnum() or ch in "_:")) else "_"
        for ch in name
    )
    if not out or out[0].isdigit():
        out = "_" + out
    return out


def _prom_label(value) -> str:
    """Escape a label value per the exposition format."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace("\n", "\\n")
        .replace('"', '\\"')
    )


def _prom_stage_histograms(snap, slow_events=None) -> List[str]:
    """Flight-recorder stage latencies as one native histogram family.

    Log2-µs buckets become cumulative ``le`` bounds in seconds (the
    exact inclusive bucket bound, ``(2^b - 1)/1e6``); only non-empty
    buckets are emitted — cumulative series stay valid when sparse.

    When the slow-event ring is passed, bucket lines carry OpenMetrics
    exemplars pointing at the self-span trace id of an over-budget
    observation that landed in that bucket — a burning latency SLO
    links straight to a retrievable pipeline trace. Exemplar syntax
    (``# {trace_id="..."} <seconds>``) is an OpenMetrics extension that
    classic text-format parsers treat as a comment, so the exposition
    stays valid for both.
    """
    stats = snap.nonzero()
    if not stats:
        return []
    # newest exemplar per (stage, bucket): the ring is oldest-first and
    # only self-span-enriched events carry a trace id worth linking
    by_bucket: Dict[Tuple[str, int], Tuple[str, float]] = {}
    for ev in slow_events or ():
        trace_id = ev.get("traceId")
        if not trace_id:
            continue
        dur_us = int(ev.get("durUs", 0))
        by_bucket[(ev["stage"], max(dur_us, 0).bit_length())] = (
            trace_id, dur_us / 1e6,
        )
    fam = "zipkin_tpu_stage_latency_seconds"
    lines = [
        f"# HELP {fam} Pipeline stage latency (log2 microsecond buckets).",
        f"# TYPE {fam} histogram",
    ]
    for st in stats:
        cum = 0
        for b, count in enumerate(st.buckets[:-1]):
            if not count:
                continue
            cum += count
            le = obs.bucket_le_us(b) / 1e6
            line = f'{fam}_bucket{{stage="{st.stage}",le="{le}"}} {cum}'
            ex = by_bucket.get((st.stage, b))
            if ex is not None:
                line += f' # {{trace_id="{_prom_label(ex[0])}"}} {ex[1]}'
            lines.append(line)
        lines.append(f'{fam}_bucket{{stage="{st.stage}",le="+Inf"}} {st.count}')
        lines.append(f'{fam}_sum{{stage="{st.stage}"}} {st.sum_us / 1e6}')
        lines.append(f'{fam}_count{{stage="{st.stage}"}} {st.count}')
    return lines


def _prom_accuracy(status) -> List[str]:
    """Per-service digest-error families from the accuracy observatory.
    The scalar gauges (worst-service, HLL, recall, retention bias) ride
    the flat ``zipkin_tpu_accuracy_*`` render in ``get_prometheus``;
    only the service-labelled detail needs its own exposition."""
    rows = status.get("services") or []
    if not rows:
        return []
    lines: List[str] = []
    fields = (
        ("p50RelErr", "p50_relerr", "digest p50 relative error"),
        ("p99RelErr", "p99_relerr", "digest p99 relative error"),
        ("p99Bound", "p99_bound", "stated p99 confidence bound"),
    )
    for field, suffix, help_text in fields:
        fam = f"zipkin_tpu_accuracy_service_{suffix}"
        lines.append(
            f"# HELP {fam} Per-service {help_text} (device vs shadow)."
        )
        lines.append(f"# TYPE {fam} gauge")
        for row in rows:
            lines.append(
                f'{fam}{{service="{_prom_label(row["service"])}"}} '
                f'{row[field]}'
            )
    return lines


def _prom_mp_workers(table) -> List[str]:
    """Fan-out tier per-worker attribution as labelled counter families
    (``worker="<widx>"``). The nested ``mpWorkerTable`` is skipped by the
    flat-gauge loop; this is its exposition-format rendering."""
    if not table:
        return []
    lines: List[str] = []
    fields = (
        ("chunks", "chunks dispatched"),
        ("spans", "spans parsed"),
        ("payloads", "payloads completed"),
        ("parseUs", "parse wall microseconds"),
        ("packUs", "pack wall microseconds"),
        ("routeUs", "route wall microseconds"),
        ("fallbacks", "inline-fallback payloads"),
    )
    for field, help_text in fields:
        fam = _prom_name(f"zipkin_tpu_mp_worker_{_snake(field)}_total")
        lines.append(f"# HELP {fam} Ingest worker {help_text}.")
        lines.append(f"# TYPE {fam} counter")
        for row in table:
            lines.append(
                f'{fam}{{worker="{_prom_label(row["widx"])}"}} {row[field]}'
            )
    # instantaneous queue posture (ISSUE 11 satellite): depth is live
    # occupancy, high-water the worst since boot — gauges, not counters
    gauges = (
        ("queueDepth", "live bounded-queue depth (payloads in flight)"),
        ("queueHighWater", "bounded-queue depth high-water mark"),
    )
    for field, help_text in gauges:
        fam = _prom_name(f"zipkin_tpu_mp_worker_{_snake(field)}")
        lines.append(f"# HELP {fam} Ingest worker {help_text}.")
        lines.append(f"# TYPE {fam} gauge")
        for row in table:
            lines.append(
                f'{fam}{{worker="{_prom_label(row["widx"])}"}} '
                f'{row.get(field, 0)}'
            )
    return lines


def _prom_critpath(segments) -> List[str]:
    """Critical-path segment families from the stitcher's fold
    aggregates. The scalar gauges (timelines, lambda, occupancy,
    saturation, conservation) ride the flat ``zipkin_tpu_critpath_*``
    render; the per-segment table needs segment+kind labels."""
    if not segments:
        return []
    lines: List[str] = []
    fields = (
        ("count", "folded occurrences", "counter", "_total"),
        ("sumUs", "cumulative wall microseconds", "counter", "_total"),
        ("maxUs", "worst single occurrence microseconds", "gauge", ""),
    )
    for field, help_text, typ, suffix in fields:
        fam = _prom_name(f"zipkin_tpu_critpath_segment_{_snake(field)}{suffix}")
        lines.append(f"# HELP {fam} Critical-path segment {help_text}.")
        lines.append(f"# TYPE {fam} {typ}")
        for seg, row in sorted(segments.items()):
            lines.append(
                f'{fam}{{segment="{_prom_label(seg)}",'
                f'kind="{_prom_label(row["kind"])}"}} {row[field]}'
            )
    return lines


def _prom_query_lock(table) -> List[str]:
    """Aggregator-lock contention ledger (ISSUE 12): native wait/hold
    histogram families plus per-label holder attribution. The scalar
    ``zipkin_tpu_query_lock_*`` gauges (acquisitions, waiters,
    high-water, p50/p99) ride the flat render; the histograms and the
    holder table need their own families."""
    if not table:
        return []
    lines: List[str] = []
    hists = (
        ("wait", table.get("waitHist"), table.get("waitSumUs", 0),
         "time a thread waited to acquire the aggregator lock"),
        ("hold", table.get("holdHist"), table.get("holdSumUs", 0),
         "time an outermost acquire held the aggregator lock"),
    )
    for which, hist, sum_us, help_text in hists:
        if not hist or not sum(hist):
            continue
        fam = f"zipkin_tpu_query_lock_{which}_seconds"
        lines.append(f"# HELP {fam} Lock ledger: {help_text}.")
        lines.append(f"# TYPE {fam} histogram")
        total = sum(hist)
        cum = 0
        for b, count in enumerate(hist[:-1]):
            if not count:
                continue
            cum += count
            le = obs.bucket_le_us(b) / 1e6
            lines.append(f'{fam}_bucket{{le="{le}"}} {cum}')
        lines.append(f'{fam}_bucket{{le="+Inf"}} {total}')
        lines.append(f'{fam}_sum {sum_us / 1e6}')
        lines.append(f'{fam}_count {total}')
    holders = table.get("holders") or {}
    if holders:
        count_fam = "zipkin_tpu_query_lock_holds_total"
        sum_fam = "zipkin_tpu_query_lock_hold_sum_us_total"
        lines.append(
            f"# HELP {count_fam} Outermost lock holds by holder label."
        )
        lines.append(f"# TYPE {count_fam} counter")
        for label, row in sorted(holders.items()):
            lines.append(
                f'{count_fam}{{holder="{_prom_label(label)}"}} '
                f'{row["count"]}'
            )
        lines.append(
            f"# HELP {sum_fam} Cumulative hold microseconds by holder "
            "label."
        )
        lines.append(f"# TYPE {sum_fam} counter")
        for label, row in sorted(holders.items()):
            lines.append(
                f'{sum_fam}{{holder="{_prom_label(label)}"}} '
                f'{row["holdSumUs"]}'
            )
    return lines


def _prom_query_segments(segments) -> List[str]:
    """Per-segment query critical-path aggregates, mirroring the
    critpath segment families with segment+kind labels."""
    if not segments:
        return []
    lines: List[str] = []
    fields = (
        ("count", "folded occurrences", "counter", "_total"),
        ("sumUs", "cumulative wall microseconds", "counter", "_total"),
        ("maxUs", "worst single occurrence microseconds", "gauge", ""),
    )
    for field, help_text, typ, suffix in fields:
        fam = _prom_name(f"zipkin_tpu_query_segment_{_snake(field)}{suffix}")
        lines.append(f"# HELP {fam} Query critical-path segment "
                     f"{help_text}.")
        lines.append(f"# TYPE {fam} {typ}")
        for seg, row in sorted(segments.items()):
            lines.append(
                f'{fam}{{segment="{_prom_label(seg)}",'
                f'kind="{_prom_label(row["kind"])}"}} {row[field]}'
            )
    return lines


def _prom_overload(status) -> List[str]:
    """Overload control plane families (ISSUE 13). Scalars carry the
    ladder posture; the per-signal family shows WHICH bottleneck is
    driving the load index (it is a MAX fold, so exactly one signal is
    the story at any instant)."""
    lines: List[str] = []
    gauges = (
        ("level", status["level"],
         "Brownout ladder level (0=B0 normal .. 3=B3 essential-only)"),
        ("load_index", status["loadIndex"],
         "EMA-smoothed load index (max-folded signal pressure)"),
        ("raw_load_index", status["rawLoadIndex"],
         "Unsmoothed load index from the latest tick"),
        ("bulk_admit_p", status["bulkAdmitP"],
         "Bulk-class ingest admit probability (1.0 outside B2)"),
    )
    for suffix, value, help_text in gauges:
        fam = f"zipkin_tpu_overload_{suffix}"
        lines.append(f"# HELP {fam} {help_text}.")
        lines.append(f"# TYPE {fam} gauge")
        lines.append(f"{fam} {value}")
    signals = status.get("signals") or {}
    if signals:
        fam = "zipkin_tpu_overload_signal"
        lines.append(
            f"# HELP {fam} Per-signal pressure ratio "
            "(value over design limit; 1.0 = at the limit)."
        )
        lines.append(f"# TYPE {fam} gauge")
        for name, value in sorted(signals.items()):
            lines.append(
                f'{fam}{{signal="{_prom_label(name)}"}} {value}'
            )
    counters = status.get("counters") or {}
    counter_fields = (
        ("admitted", "admitted_total", "payloads admitted"),
        ("admittedEssential", "admitted_essential_total",
         "error-class payloads admitted under brownout"),
        ("shedBulk", "shed_bulk_total", "bulk-class payloads shed"),
        ("shedTotal", "shed_total", "payloads shed"),
        ("deadlineExpired", "deadline_expired_total",
         "requests dropped already past their deadline"),
        ("transitions", "transitions_total", "ladder level transitions"),
    )
    for field, suffix, help_text in counter_fields:
        if field not in counters:
            continue
        fam = f"zipkin_tpu_overload_{suffix}"
        lines.append(f"# HELP {fam} Overload controller: {help_text}.")
        lines.append(f"# TYPE {fam} counter")
        lines.append(f"{fam} {counters[field]}")
    return lines


def _prom_tenants(status) -> List[str]:
    """Per-tenant admission families (ISSUE 18): every family carries a
    ``{tenant=}`` label, so one flooding tenant's shed curve is
    separable from everyone else's flat zero on the same graph. The
    label values come from ``normalize_tenant``'s bounded alphabet, so
    they are prometheus-label-safe by construction; the row count is
    bounded by the admission table's LRU cap."""
    tenants = (status or {}).get("tenants")
    if not tenants:
        return []
    lines: List[str] = []
    table = tenants.get("tenants") or {}
    scalars = (
        ("table_size", len(table),
         "Live tenants in the bounded admission table", "gauge"),
        ("evictions_total", tenants.get("evictions", 0),
         "Tenant rows LRU-evicted from the admission table", "counter"),
    )
    for suffix, value, help_text, typ in scalars:
        fam = f"zipkin_tpu_tenant_{suffix}"
        lines.append(f"# HELP {fam} {help_text}.")
        lines.append(f"# TYPE {fam} {typ}")
        lines.append(f"{fam} {value}")
    fields = (
        ("level", "level",
         "Per-tenant brownout level (0=admit .. 3=essential-only)",
         "gauge"),
        ("pressure", "pressure",
         "Per-tenant demand pressure EMA (offered rate over budget)",
         "gauge"),
        ("offered", "offered_total", "payloads offered", "counter"),
        ("admitted", "admitted_total", "payloads admitted", "counter"),
        ("shed", "shed_total", "payloads shed (scope=tenant)", "counter"),
        ("retainedSpans", "retained_spans_total",
         "spans retained past sampling", "counter"),
    )
    for field, suffix, help_text, typ in fields:
        fam = f"zipkin_tpu_tenant_{suffix}"
        if typ == "counter":
            lines.append(f"# HELP {fam} Tenant admission: {help_text}.")
        else:
            lines.append(f"# HELP {fam} {help_text}.")
        lines.append(f"# TYPE {fam} {typ}")
        for name, row in sorted(table.items()):
            lines.append(
                f'{fam}{{tenant="{_prom_label(name)}"}} {row[field]}'
            )
    return lines


def _prom_slo(verdicts) -> List[str]:
    """SLO watchdog families: one boolean alert gauge per SLO plus the
    multi-window burn rates it was computed from."""
    if not verdicts:
        return []
    alert_fam = "zipkin_tpu_slo_alert"
    burn_fam = "zipkin_tpu_slo_burn_rate"
    lines = [
        f"# HELP {alert_fam} SLO burn-rate alert (1 = burning).",
        f"# TYPE {alert_fam} gauge",
    ]
    for v in verdicts:
        lines.append(
            f'{alert_fam}{{slo="{_prom_label(v["name"])}"}} {int(v["alert"])}'
        )
    lines.append(
        f"# HELP {burn_fam} Error-budget burn rate per evaluation window."
    )
    lines.append(f"# TYPE {burn_fam} gauge")
    for v in verdicts:
        for wname, wv in sorted(v["windows"].items()):
            lines.append(
                f'{burn_fam}{{slo="{_prom_label(v["name"])}",'
                f'window="{_prom_label(wname)}"}} {wv["burn"]}'
            )
    return lines


def parse_annotation_query(raw: Optional[str]) -> Dict[str, str]:
    """Parse ``"error and http.method=GET"`` into ``{error: '', http.method:
    'GET'}`` — the upstream annotationQuery grammar."""
    out: Dict[str, str] = {}
    if not raw:
        return out
    for token in raw.split(" and "):
        token = token.strip()
        if not token:
            continue
        key, sep, value = token.partition("=")
        out[key] = value if sep else ""
    return out


async def run_server(config: Optional[ServerConfig] = None) -> None:
    server = ZipkinServer(config or ServerConfig.from_env())
    await server.start()
    try:
        await asyncio.Event().wait()
    finally:
        await server.stop()
