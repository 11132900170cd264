"""TpuStorage: the StorageComponent backed by the device aggregation tier.

This is the rebuild's ``zipkin-storage-tpu`` module (BASELINE north
star): it implements the exact SPI of SURVEY.md §2.3 — so the collectors
and server use it interchangeably with the in-memory oracle — while
serving the aggregate read paths (dependencies, latency percentiles,
cardinalities) straight from device sketches.

Division of labor (hybrid by design, SURVEY.md §1 "TPU-rebuild mapping"):

- **Device** (per shard, merged over ICI on read): latency histograms +
  t-digests per (service, spanName), HLL trace cardinality per service,
  dependency-link matrices over the retained span ring.
- **Host archive**: a bounded `InMemoryStorage` keeps raw spans for exact
  trace reads and search (`getTraces`) — the role the reference delegates
  to row storage; beyond its eviction horizon, aggregates remain
  queryable from the device (which is the point of the sketch tier).

Idempotence: at-least-once transports can redeliver (SURVEY.md §3.3). The
archive dedups by (traceId, spanId, ...); device sketches accept bounded
double-count — the documented trade, testable against the oracle.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from zipkin_tpu import obs, readpack
from zipkin_tpu.internal.hex import epoch_minutes
from zipkin_tpu.obs import querytrace
from zipkin_tpu.ops import hll, ttmerge
from zipkin_tpu.model.span import DependencyLink, Span
from zipkin_tpu.storage.memory import InMemoryStorage
from zipkin_tpu.storage.spi import (
    AutocompleteTags,
    QueryRequest,
    ServiceAndSpanNames,
    SpanConsumer,
    SpanStore,
    StorageComponent,
)
from zipkin_tpu.tpu.columnar import SpanColumns, Vocab, pack_spans
from zipkin_tpu.tpu.mirror import ReadMirror
from zipkin_tpu.tpu.state import AggConfig
from zipkin_tpu.utils.call import Call
from zipkin_tpu.utils.component import CheckResult, Component

logger = logging.getLogger(__name__)

# the dashboard's default quantile list (server endpoints default
# ``q=0.5,0.9,0.99``): the mirror seeds these reads at construction so
# the first post-boot dashboard refresh is already lock-free
DEFAULT_QS = (0.5, 0.9, 0.99)

# sentinel returned by _mirror_bound when the request opted out of the
# mirror (staleness_ms <= 0): force the fresh lock-path read
_MIRROR_FRESH = object()


from zipkin_tpu.native import PARSED_FIELDS as _PARSED_FIELDS


def _decode_raw_span(raw: bytes):
    """Decode one archived raw span slice: JSON objects start '{', a
    proto3 Span message starts with a field tag byte — the archive holds
    whichever wire format ingested the span."""
    if raw[:1] == b"{":
        from zipkin_tpu.model import json_v2

        return json_v2.decode_one_span(raw)
    from zipkin_tpu.model import proto3

    return proto3.decode_span(raw)


class TpuStorage(
    StorageComponent, SpanConsumer, SpanStore, ServiceAndSpanNames, AutocompleteTags
):
    def __init__(
        self,
        *,
        config: Optional[AggConfig] = None,
        mesh=None,
        strict_trace_id: bool = True,
        search_enabled: bool = True,
        autocomplete_keys: Sequence[str] = (),
        archive_max_span_count: int = 500_000,
        pad_to_multiple: int = 1024,
        fast_archive_sample: int = 64,
        archive_dir: Optional[str] = None,
        archive_max_bytes: int = 2 << 30,
        archive_segment_bytes: int = 64 << 20,
        sampling_budget: float = 0.0,
        sampling_interval_s: float = 5.0,
        sampling_min_rate: int = 256,
        sampling_tail_quantile: float = 0.99,
        sampling_rare_min: Optional[int] = None,
    ) -> None:
        from zipkin_tpu.parallel.sharded import ShardedAggregator

        self.config = config or AggConfig()
        # NOTE: the archive index packs svc/rsvc ids into 16 bits each
        # (tpu/archive.py COLS row 6). AggConfig already rejects
        # max_services beyond the packed-wire 16-bit limit (state.py /
        # columnar.MAX_WIRE_SERVICES), so a truncating capacity is
        # unconstructable — pinned by
        # tests/test_disk_archive.py::test_service_capacity_guard.
        self.strict_trace_id = strict_trace_id
        self.search_enabled = search_enabled
        self.autocomplete_keys = tuple(autocomplete_keys)
        self.vocab = Vocab(
            max_services=self.config.max_services, max_keys=self.config.max_keys
        )
        self.agg = ShardedAggregator(self.config, mesh=mesh)
        # adaptive tail-sampling tier (zipkin_tpu/sampling): the host
        # reference sampler gates RETENTION (WAL via ingest_fused, disk
        # archive, RAM archive sample) while device sketches keep seeing
        # 100% of spans. Installed on the aggregator immediately for a
        # cold boot; the resume adapter (storage/tpu.py) detaches it
        # around restore/replay and re-installs after the final tables
        # are pushed back to the device.
        self.sampler = None
        self.sampling_controller = None
        if self.config.sampling:
            from zipkin_tpu.sampling import HostSampler, RateController

            self.sampler = HostSampler(
                self.config.max_services,
                self.config.max_keys,
                rare_min=(
                    self.config.sample_rare_min
                    if sampling_rare_min is None
                    else sampling_rare_min
                ),
            )
            self.agg.sampler = self.sampler
            if sampling_budget > 0:
                self.sampling_controller = RateController(
                    self,
                    budget_spans_per_sec=sampling_budget,
                    interval_s=sampling_interval_s,
                    min_rate=sampling_min_rate,
                    tail_quantile=sampling_tail_quantile,
                )
        self._archive = InMemoryStorage(
            max_span_count=archive_max_span_count,
            strict_trace_id=strict_trace_id,
            search_enabled=search_enabled,
            autocomplete_keys=autocomplete_keys,
        )
        self._pad = pad_to_multiple
        # largest single device batch AFTER padding: bounded by the digest
        # pending buffer (dynamic_update_slice of a batch bigger than it
        # cannot trace), rounded DOWN to a pad multiple so a padded chunk
        # never exceeds the bound.
        # A dispatch has a fixed cost, so bigger device batches amortize
        # it; 64k is the default and the cap is an env knob. Hard bound
        # either way: the digest pending buffer (dynamic_update_slice of
        # a batch bigger than it cannot trace).
        import os as _os

        cap = int(_os.environ.get("TPU_MAX_DEVICE_BATCH", 65536))
        bound = min(self.config.digest_buffer, self.config.rollup_segment, cap)
        self.max_batch = (bound // pad_to_multiple) * pad_to_multiple
        if self.max_batch <= 0:
            raise ValueError(
                f"digest_buffer ({self.config.digest_buffer}) must be >= "
                f"pad_to_multiple ({pad_to_multiple})"
            )
        self._closed = False
        # boot-time restore instrumentation (ISSUE 3): zeros on a cold
        # boot; the resume-capable storage adapter (storage/tpu.py)
        # overwrites these with measured restore/replay figures, and
        # they flow to /prometheus + /metrics via ingest_counters()
        self.restore_stats = {
            "restoreMs": 0.0,
            "walReplayBatches": 0,
            "walReplayMs": 0.0,
            # bit-rot accounting (ISSUE 7): how many snapshot
            # generations the last boot quarantined, and whether it had
            # to fall back past the newest one (tpu/snapshot.py)
            "restoreFallbacks": 0,
            "generationsQuarantined": 0,
        }
        # background at-rest CRC scrubber (runtime/scrub.py); installed
        # by the resume-capable adapter when scrubbing is enabled, its
        # counters merge into ingest_counters below
        self.scrubber = None
        # disk-backed raw-span archive (VERDICT r3 order 2): when set,
        # EVERY ingested span's raw JSON is retained on disk behind a
        # trace-id index (retention = a disk-byte budget), so fast-mode
        # get_trace returns the COMPLETE trace for any acked id in the
        # window — not the 1-in-64 RAM sample. See tpu/archive.py.
        self._disk = None
        self._archive_vocab_path = None
        self._archive_vocab_persisted = 0
        if archive_dir:
            from zipkin_tpu.tpu.archive import SpanArchive

            self._disk = SpanArchive(
                archive_dir,
                max_bytes=archive_max_bytes,
                segment_bytes=archive_segment_bytes,
            )
            import os as _os2

            self._archive_vocab_path = _os2.path.join(
                archive_dir, "vocab.json"
            )
        # remote services per service (svc_id -> set of rsvc ids) and the
        # set of ids seen as a LOCAL service: the disk index serves
        # search, but these tiny host maps answer getServiceNames /
        # getRemoteServiceNames without a segment scan. The vocab alone
        # cannot answer either — remote names intern into the same
        # services table, and the reference lists LOCAL names only.
        self._remote_by_svc: dict = {}
        self._local_svc_ids: set = set()
        self._names_lock = threading.Lock()
        # fast-mode archive sampling: 1 in N traces keeps full raw spans
        # (0 disables). Trace-affine so sampled traces are COMPLETE.
        # Kept CONFIGURED even with the disk archive on: the sync fast
        # path then skips RAM sampling (disk holds everything), and the
        # MP tier's workers ship raw records to the disk archive too
        # (mp_ingest remaps worker-local vocab ids and appends) — their
        # RAM sample at this rate then only backs autocompleteTags, or
        # everything when no disk archive is configured.
        self._fast_archive_every = fast_archive_sample
        # optional attached MP fan-out tier (tpu/mp_ingest.py): the
        # server sets this so ingest_counters() surfaces the tier's
        # gauges and close() can tear a forgotten tier down
        self.mp_ingester = None
        # accuracy observatory (obs/shadow.py + obs/accuracy.py): the
        # server attaches both when the shadow plane is enabled; the
        # fast path offers its columnar batches to the shadow and
        # ingest_counters() merges the accuracy gauges
        self.shadow = None
        self.accuracy = None
        # interning id-space coherence: the C-side vocab (fast path) and
        # the Python vocab (object path) assign ids sequentially; any
        # operation that interns must hold this lock so the orders match.
        self._intern_lock = threading.RLock()
        # serializes vocab-sidecar persistence (snapshot + atomic
        # replace) so concurrent writers cannot reorder replaces
        self._persist_lock = threading.Lock()
        self._nvocab = None
        # HLL operating envelope (r5 billion-scale study): cardinality
        # estimates past this are bias-dominated, not noise-dominated.
        # DERIVED from the measured bias curve at this precision, never
        # hard-coded — see ops/hll.envelope_max (~1.8e9 at p=11).
        self._hll_envelope_max = hll.envelope_max(self.config.hll_precision)
        self._hll_envelope_exceeded = 0      # reads that saw such a row
        self._hll_beyond_envelope_rows = 0   # rows beyond, at last read
        # read cache: device pulls (merged digest/sketches) keyed by the
        # write version, so repeated queries between writes cost nothing
        self._read_cache: dict = {}   # key -> (value, born_monotonic)
        self._read_cache_version = -1
        self._read_cache_lock = threading.Lock()
        # cached-read staleness: age-at-serve of the last hit and its
        # high-water — "query_cached is fast" is only good news if the
        # answers are also young; these gauges put a number on it
        self._read_cache_age_ms = 0.0
        self._read_cache_age_max_ms = 0.0
        # overload control plane (runtime/overload.py, ISSUE 13): the
        # server wires its brownout controller here. Under B1/B2 the
        # cached-read path serves CACHE-FIRST — a version-stale entry
        # within the controller's staleness bound beats a device pull
        # that would queue behind a saturated ingest lock; under B3 any
        # cached answer serves (cache-only). Stale serves are counted
        # so "the queries stayed fast" can be audited against "and this
        # many answers were seconds old".
        self.overload = None
        self._read_cache_stale_serves = 0
        # dependency answers additionally tolerate BOUNDED STALENESS
        # under sustained ingest (env TPU_DEPS_MAX_STALE_MS, default 5s;
        # 0 = always fresh): the reference's dependency table is written
        # by an OFFLINE batch job and is hours stale by design (SURVEY.md
        # §3.5), so serving a seconds-old answer instead of queueing a
        # ring re-sort behind every poll is squarely within its
        # semantics. Keyed by window; pruned by age on insert.
        import os as _os

        self._deps_max_stale_ms = float(
            _os.environ.get("TPU_DEPS_MAX_STALE_MS", 5000.0)
        )
        self._deps_cache: dict = {}
        # query-plane observatory (obs/querytrace.py): per-query
        # critical-path traces folded at tick cadence, plus the
        # aggregator-lock contention ledger. lock_provider resolves
        # self.agg lazily so clear()'s wholesale aggregator swap keeps
        # the ledger pointed at the live instrumented lock.
        self.querytrace = querytrace.QueryObservatory()
        self.querytrace.lock_provider = (
            lambda: getattr(self.agg, "lock", None)
        )
        self._query_obs_enabled: Optional[bool] = None
        # epoch-published read mirror (tpu/mirror.py, ISSUE 14): the
        # publisher — windows ticker in production, boot publish in the
        # resume adapter — takes the aggregator lock ONCE per epoch and
        # republishes every demanded read; queries then serve lock-free
        # with a stamped staleness age. The provider resolves self.agg
        # lazily for the same reason the querytrace lock provider does.
        self.mirror = ReadMirror(lambda: getattr(self, "agg", None))
        self._seed_mirror()
        # scale-out read serving (serving/, ISSUE 19): when a shm
        # mirror segment is attached, every mirror epoch additionally
        # serializes into it (outside the aggregator lock) and reader
        # PROCESSES serve from the mapped copy; their missed keys come
        # back through the segment's demand stripes each tick.
        self._segment = None
        self._segment_publisher = None
        self._demand_unparsed = 0
        # time-disaggregated sketch tier (tpu/timetier.py, ISSUE 15):
        # a ticker-driven sealer freezes finished device time buckets
        # into host-side mergeable segments; windowed [lookback, endTs]
        # quantile/cardinality/dependency reads then merge the covering
        # segments in numpy, with at most one device pull for the
        # unsealed current bucket. Segments persist under the archive
        # dir (when configured) so old windows survive restarts.
        self.timetier = None
        if self.config.timetier_enabled:
            from zipkin_tpu.tpu.timetier import TimeTier

            self.timetier = TimeTier(
                self.config,
                directory=(
                    _os.path.join(archive_dir, "timetier")
                    if archive_dir else None
                ),
            )
        # archive-only restart: segment columns store vocab IDS, so the
        # ids must survive the process or every recovered segment becomes
        # unsearchable. A snapshot restore (storage/tpu.py) replaces the
        # vocab wholesale afterwards — its id stream is the same stream,
        # so both sources agree on every id they share; WAL replay then
        # re-adds any post-snapshot tail (r4 review finding).
        self._load_archive_vocab()

    # zt-lint: disable=ZT04 — runs once from __init__, before any other
    # thread holds a reference to the store; _persist_archive_vocab's
    # lock protects later concurrent writers, not construction
    def _load_archive_vocab(self) -> None:
        if self._archive_vocab_path is None:
            return
        import json
        import os as _os

        if not _os.path.exists(self._archive_vocab_path):
            return
        if len(self.vocab.services) > 1 or self.vocab.num_keys > 1:
            return  # a live vocab wins (tests reuse dirs)
        try:
            with open(self._archive_vocab_path) as f:
                meta = json.load(f)
        except Exception:  # pragma: no cover - torn sidecar
            logger.warning("archive vocab sidecar unreadable; search over "
                           "recovered segments will miss pre-restart spans")
            return
        # digest coverage (ISSUE 7): the sidecar self-records a crc32 of
        # its canonical payload; rot here would silently remap every id
        # on recovered segments. A bad sidecar is quarantined (renamed,
        # never unlinked) and the boot degrades exactly like a missing
        # one. Pre-digest sidecars (no crc32 key) load unchecked.
        want_crc = meta.pop("crc32", None)
        if want_crc is not None:
            import zlib as _zlib

            got = _zlib.crc32(
                json.dumps(meta, sort_keys=True, separators=(",", ":")).encode()
            )
            if got != int(want_crc):
                logger.warning(
                    "archive vocab sidecar digest mismatch (crc32 %08x != "
                    "recorded %08x) — bit rot; quarantining. Search over "
                    "recovered segments will miss pre-restart spans",
                    got, int(want_crc),
                )
                try:
                    _os.replace(
                        self._archive_vocab_path,
                        self._archive_vocab_path + ".quarantine",
                    )
                except OSError:
                    pass
                return
        v = self.vocab
        v.services._names = list(meta["services"])
        v.services._ids = {
            n: i for i, n in enumerate(meta["services"]) if i
        }
        v.span_names._names = list(meta["span_names"])
        v.span_names._ids = {
            n: i for i, n in enumerate(meta["span_names"]) if i
        }
        v._key_list = [tuple(k) for k in meta["keys"]]
        v._keys = {tuple(k): i for i, k in enumerate(meta["keys"]) if i}
        with self._names_lock:
            self._local_svc_ids = set(meta.get("local_svc_ids", ()))
            self._remote_by_svc = {
                int(k): set(vv)
                for k, vv in meta.get("remote_by_svc", {}).items()
            }
        self._archive_vocab_persisted = len(v._key_list) + len(
            v.services._names
        ) + len(v.span_names._names)

    def _persist_archive_vocab(self) -> None:
        """Write the vocab sidecar when it grew since the last write
        (atomic rename; amortized to vocab growth, which is bounded).
        The whole snapshot+write+replace runs under a dedicated persist
        lock: without it a delayed writer (object path racing the sync
        fast path) could os.replace a NEWER sidecar with an older
        snapshot after `_archive_vocab_persisted` already moved past it
        — a crash in that window would leave recovered segments holding
        ids missing from the sidecar (ADVICE r4). The intern lock is
        held only for the snapshot so persistence IO never stalls
        line-rate interning."""
        if self._archive_vocab_path is None:
            return
        import json
        import os as _os
        import tempfile as _tempfile

        v = self.vocab
        # lock-free pre-check: the overwhelmingly common call sees an
        # unchanged vocab and must NOT queue behind a concurrent
        # writer's sidecar IO (every disk append calls this)
        with self._intern_lock:
            size = len(v._key_list) + len(v.services._names) + len(
                v.span_names._names
            )
            if size == self._archive_vocab_persisted:
                return
        with self._persist_lock:
            with self._intern_lock:
                size = len(v._key_list) + len(v.services._names) + len(
                    v.span_names._names
                )
                if size == self._archive_vocab_persisted:
                    return
                with self._names_lock:
                    meta = {
                        "services": list(v.services._names),
                        "span_names": list(v.span_names._names),
                        "keys": [list(k) for k in v._key_list],
                        "local_svc_ids": sorted(self._local_svc_ids),
                        "remote_by_svc": {
                            str(k): sorted(vv)
                            for k, vv in self._remote_by_svc.items()
                        },
                    }
                self._archive_vocab_persisted = size
            import zlib as _zlib

            # self-digest over the canonical payload (see
            # _load_archive_vocab's verification)
            meta["crc32"] = _zlib.crc32(
                json.dumps(meta, sort_keys=True, separators=(",", ":")).encode()
            )
            d = _os.path.dirname(self._archive_vocab_path)
            fd, tmp = _tempfile.mkstemp(dir=d, suffix=".json.tmp")
            with _os.fdopen(fd, "w") as f:
                json.dump(meta, f)
            _os.replace(tmp, self._archive_vocab_path)

    # -- sampling tier hooks ---------------------------------------------

    def on_restored_leaves(self, leaves: dict) -> None:
        """Snapshot-restore callback (tpu/snapshot.maybe_restore): seed
        the sampling tier's host mirror from the restored device leaves
        (shard 0's copy — the published tables are replicated across
        shards by construction)."""
        if self.sampler is None or "s_rate" not in leaves:
            return
        self.sampler.restore_tables(
            leaves["s_rate"][0], leaves["s_tail"][0], leaves["s_link"][0]
        )

    def apply_sctl(self, delta: dict) -> None:
        """WAL-replay callback (tpu/wal.replay): apply one replayed
        controller publish to the host mirror at its exact point of the
        batch stream, so later replayed verdicts read the same tables
        the live run did. The device leaves are pushed to match when the
        resume adapter re-installs the sampler (storage/tpu.py)."""
        if self.sampler is not None:
            self.sampler.apply_sctl(delta)

    def install_sampler(self) -> None:
        """(Re-)arm the sampling gate after boot restore/replay: push the
        host mirror's tables to the device leaves and attach the sampler
        to the ingest funnel. No-op when the tier is off."""
        if self.sampler is None:
            return
        self.agg.set_sampler_tables(
            self.sampler.rate, self.sampler.tail, self.sampler.link
        )
        self.agg.sampler = self.sampler

    # -- SPI factories ---------------------------------------------------

    def span_consumer(self) -> SpanConsumer:
        return self

    def span_store(self) -> SpanStore:
        return self

    def service_and_span_names(self) -> ServiceAndSpanNames:
        return self

    def autocomplete_tags(self) -> AutocompleteTags:
        return self._archive

    # -- write path ------------------------------------------------------

    def accept(self, spans: Sequence[Span]) -> Call[None]:
        def run() -> None:
            if not spans:
                return
            # chunk: a giant POST must not exceed the device batch bound
            # (state transitions serialize on the aggregator's own lock).
            # With the sampling tier on, archive/disk retention keeps
            # only verdict-kept spans — the device (below) still ingests
            # the FULL batch so sketches see 100%.
            for lo in range(0, len(spans), self.max_batch):
                chunk = spans[lo : lo + self.max_batch]
                t0 = time.perf_counter()
                with self._intern_lock:
                    cols = pack_spans(chunk, self.vocab, self._pad)
                obs.record("pack", time.perf_counter() - t0)
                kept = chunk
                if self.agg.sampler is not None:
                    keep = self.agg.sampler.verdict_cols(cols)[: len(chunk)]
                    kept = [s for s, k in zip(chunk, keep) if k]
                if kept:
                    t0 = time.perf_counter()
                    self._archive.accept(kept).execute()
                    if self._disk is not None:
                        self._disk_append_spans(kept)
                    obs.record("archive_write", time.perf_counter() - t0)
                self.agg.ingest(cols)

        return Call.of(run)

    def _disk_append_spans(self, spans: Sequence[Span]) -> None:
        """Object-path mirror of :meth:`_disk_append_parsed`: encode each
        span once (the slow path already pays per-span object costs) so
        the disk archive is complete whichever ingest path ran. The
        intern lock covers ONLY the vocab pass — encoding and the disk
        write happen outside it, so a large object-path POST cannot
        stall line-rate ingest behind its IO (r4 review finding)."""
        from zipkin_tpu.internal.hex import normalize_trace_id
        from zipkin_tpu.model import json_v2

        n = len(spans)
        parts: List[bytes] = []
        off = np.zeros(n, np.uint32)
        ln = np.zeros(n, np.uint32)
        lanes = np.zeros((n, 4), np.uint32)  # tl0 tl1 th0 th1
        svc = np.zeros(n, np.uint32)
        rsvc = np.zeros(n, np.uint32)
        name = np.zeros(n, np.uint32)
        key = np.zeros(n, np.uint32)
        ts_min = np.zeros(n, np.uint32)
        dur = np.zeros(n, np.uint64)
        err = np.zeros(n, bool)
        pos = 0
        for i, s in enumerate(spans):
            enc = json_v2.encode_span(s)
            parts.append(enc)
            off[i] = pos
            ln[i] = len(enc)
            pos += len(enc)
            full = int(normalize_trace_id(s.trace_id), 16)
            lo64, hi64 = full & ((1 << 64) - 1), full >> 64
            lanes[i] = (
                lo64 & 0xFFFFFFFF, lo64 >> 32,
                hi64 & 0xFFFFFFFF, hi64 >> 32,
            )
            ts_min[i] = (s.timestamp or 0) // 60_000_000
            dur[i] = s.duration or 0
            err[i] = "error" in (s.tags or {})
        with self._intern_lock:
            for i, s in enumerate(spans):
                sid = self.vocab.services.intern(s.local_service_name)
                rid = self.vocab.services.intern(s.remote_service_name)
                nid = self.vocab.span_names.intern(s.name)
                svc[i], rsvc[i], name[i] = sid, rid, nid
                key[i] = self.vocab.key_id(sid, nid)
        self._track_remotes(svc, rsvc)
        self._disk.append_batch(
            b"".join(parts), off, ln,
            lanes[:, 0], lanes[:, 1], lanes[:, 2], lanes[:, 3],
            svc, rsvc, name, key, ts_min, dur, err,
        )
        self._persist_archive_vocab()

    def ingest_json_fast(self, data: bytes, sampler=None):
        """Line-rate ingest: raw JSON v2 OR proto3 ``ListOfSpans`` bytes
        -> device aggregates via the native columnar parser (format
        sniffed by first byte), skipping Span objects for the bulk of
        the stream. A trace-affine 1/N sample IS archived at full fidelity
        (the parser records each span's byte extent; sampled slices are
        re-decoded by the reference codec), so ``/api/v2/trace/{id}`` and
        search stay alive in fast mode — the round-1 gap where the
        benchmark configuration and the queryable configuration were
        different systems. N = TPU_FAST_ARCHIVE_SAMPLE (default 64,
        0 disables).

        Returns (accepted, sample_dropped), or None when the native path
        can't take this payload (caller falls back to the object path).
        """
        work = self._fast_parse(data, sampler)
        if work is None:
            return None
        accepted, dropped, chunks = work
        for parsed, cols in chunks:
            self._fast_dispatch(parsed, cols)
        return accepted, dropped

    def _fast_parse(self, data: bytes, sampler=None):
        """Host half of the fast path: native parse + intern + sample +
        chunk + columnar pack. Returns (accepted, dropped, [(parsed,
        cols), ...]) or None for payloads the fast parser can't take.
        Split from :meth:`_fast_dispatch`, the device half:
        :meth:`warm` runs this half alone."""
        from zipkin_tpu import native
        from zipkin_tpu.tpu.columnar import pack_parsed

        if not native.available():
            return None
        with self._intern_lock:
            if self._nvocab is None:
                self._nvocab = native.NativeVocab(self.vocab)
            with obs.span("parse") as parse:
                self._nvocab.ensure_synced()
                parsed = native.parse_spans(data, nvocab=self._nvocab)
                if parsed is None:
                    parse.drop()  # not a payload for this path
                    return None
                self._nvocab.sync()
            n = parsed.n
            dropped = 0
            if sampler is not None and sampler.rate < 1.0 and n:
                keep = native.sampler_keep(parsed, n, sampler._boundary)
                dropped = int(n - keep.sum())
                if dropped:
                    idx = np.nonzero(keep)[0]
                    for field in _PARSED_FIELDS:
                        col = getattr(parsed, field, None)
                        if col is not None:
                            setattr(parsed, field, col[:n][idx])
                    parsed.n = n = len(idx)
            if n == 0:
                return 0, dropped, []
            chunks = []
            with obs.span("pack"):
                for lo_i in range(0, n, self.max_batch):
                    hi_i = min(lo_i + self.max_batch, n)
                    if lo_i == 0 and hi_i == n:
                        sub = parsed
                    else:
                        sub = native.ParsedColumns()
                        sub.data = parsed.data
                        for f in _PARSED_FIELDS:
                            col = getattr(parsed, f, None)
                            setattr(
                                sub, f,
                                None if col is None else col[lo_i:hi_i],
                            )
                        sub.n = hi_i - lo_i
                    chunks.append(
                        (sub, pack_parsed(sub, self.vocab, self._pad))
                    )
        return n, dropped, chunks

    def _fast_dispatch(self, parsed, cols) -> None:
        """Device half of the fast path: raw-span archive + sharded ingest.

        With the sampling tier armed, the archive halves see only the
        verdict-kept spans (the cols lane order matches the parsed lane
        order, so one verdict pass gates both); ``agg.ingest`` still
        feeds the FULL batch so the device sketches stay unbiased."""
        keep = None
        if self.agg.sampler is not None:
            keep = self.agg.sampler.verdict_cols(cols)[: parsed.n]
        retained = self._sampled_parsed(parsed, keep)
        t0 = time.perf_counter()
        if self._disk is not None:
            self._disk_append_parsed(retained)
            if self.autocomplete_keys:
                # autocompleteTags is served from the RAM archive only
                # (the disk index has no tag lanes): keep the 1-in-N
                # sample flowing or fast-path traffic would never
                # surface tag values (ADVICE r4)
                self._archive_fast_sample(retained, retained.n)
        else:
            self._archive_fast_sample(retained, retained.n)
        obs.record("archive_write", time.perf_counter() - t0)
        if self.shadow is not None:
            # ground-truth tap: the shadow audits the same full batch
            # the device sketches see (pre-retention), O(1) append
            self.shadow.offer_cols(cols)
        self.agg.ingest(cols)

    def _sampled_parsed(self, parsed, keep):
        """Filter a ParsedColumns view down to verdict-kept lanes (the
        same hole-punching shape the boundary sampler uses in
        :meth:`_fast_parse`; archive.parsed_record compacts the byte
        holes). ``keep=None`` (sampling off) or all-kept returns the
        input untouched."""
        if keep is None or bool(keep.all()):
            return parsed
        from zipkin_tpu import native

        idx = np.nonzero(keep)[0]
        sub = native.ParsedColumns()
        sub.data = parsed.data
        for f in _PARSED_FIELDS:
            col = getattr(parsed, f, None)
            setattr(sub, f, None if col is None else col[: parsed.n][idx])
        sub.n = len(idx)
        return sub

    def _disk_append_parsed(self, parsed) -> None:
        """Write one fast-path chunk's raw spans + index columns to the
        disk archive. A chunk's spans are contiguous in the payload, so
        only that byte range is written (no duplication when a giant
        payload chunks); sampler-punched holes compact to the kept
        slices (see archive.parsed_record)."""
        from zipkin_tpu.tpu.archive import parsed_record

        rec = parsed_record(parsed)
        if rec is None:
            return
        self.disk_append_record(rec)

    def disk_append_record(self, rec: tuple) -> None:
        """Append one prebuilt archive record (archive.parsed_record
        tuple, GLOBAL vocab ids) — the seam the MP dispatcher uses to
        feed worker-parsed batches into the disk archive."""
        svc, rsvc = rec[7], rec[8]
        self._track_remotes(svc, rsvc)
        self._disk.append_batch(*rec)
        self._persist_archive_vocab()

    def _track_remotes(self, svc: np.ndarray, rsvc: np.ndarray) -> None:
        pairs = np.unique(
            svc.astype(np.uint64) << np.uint64(32) | rsvc.astype(np.uint64)
        )
        with self._names_lock:
            for p in pairs.tolist():
                s, r = p >> 32, p & 0xFFFFFFFF
                if s:
                    self._local_svc_ids.add(int(s))
                if s and r:
                    self._remote_by_svc.setdefault(int(s), set()).add(int(r))

    def warm(self, data: bytes) -> None:
        """Compile every ingest-path program against a real payload (the
        sample is INGESTED repeatedly — serving/benchmark warm-up only).
        Compiles take minutes and must precede any timed window."""
        work = self._fast_parse(data)
        if work is None:
            # payload the fast parser can't take: warm through the object
            # path instead — this still must reach agg.warm_programs or
            # the fused/flush/rollup programs first-compile mid-traffic
            from zipkin_tpu.model import codec
            from zipkin_tpu.tpu.columnar import pack_spans

            spans = codec.decode_spans(data)
            self._archive.accept(spans).execute()
            with self._intern_lock:
                cols = pack_spans(
                    spans[: self.max_batch], self.vocab, self._pad
                )
            self.agg.warm_programs(cols)
            return
        _, _, chunks = work
        if chunks:
            self.agg.warm_programs(chunks[0][1])

    def _archive_fast_sample(self, parsed, n: int) -> None:
        """Archive a trace-affine 1/N sample of a fast-ingest batch at
        full fidelity by re-decoding each sampled span's exact JSON slice
        (extents recorded by the native parser)."""
        every = self._fast_archive_every
        if every <= 0:
            return
        from zipkin_tpu.tpu.columnar import _mix32

        tid = (
            parsed.tl0[:n] ^ parsed.tl1[:n] ^ parsed.th0[:n] ^ parsed.th1[:n]
        )
        pick = np.nonzero(_mix32(tid) % np.uint32(every) == 0)[0]
        if not len(pick):
            return
        data = parsed.data
        off, ln = parsed.span_off, parsed.span_len
        spans = []
        for i in pick:
            try:
                # format-aware: fast-path slices are JSON objects or
                # proto3 Span messages, whichever wire ingested them
                spans.append(
                    _decode_raw_span(bytes(data[off[i] : off[i] + ln[i]]))
                )
            except Exception:  # a slice the strict codec rejects: skip
                continue
        if spans:
            self._archive.accept(spans).execute()

    # -- raw trace reads: disk archive + host archive ---------------------

    def _disk_trace_spans(self, trace_id: str, views=None) -> List[Span]:
        """Decode every archived span matching ``trace_id`` under the
        store's strictness (exact low-64 match; high lanes + the decoded
        id string verified when strict). Pass ``views`` (an archive
        ``views()`` snapshot) when calling in a loop — without it every
        call re-sorts the live segment (the 1881-argsort search the
        views() docstring records)."""
        from zipkin_tpu.internal.hex import normalize_trace_id
        from zipkin_tpu.model import json_v2

        normalized = normalize_trace_id(trace_id)
        full = int(normalized, 16)
        lo, hi = full & ((1 << 64) - 1), full >> 64
        slices = self._disk.fetch_trace_raw(
            lo & 0xFFFFFFFF, lo >> 32, hi & 0xFFFFFFFF, hi >> 32,
            strict=self.strict_trace_id, views=views,
        )
        spans = []
        for raw in slices:
            try:
                s = _decode_raw_span(raw)
            except Exception:  # pragma: no cover - parser accepted it
                continue
            if self.strict_trace_id and normalize_trace_id(
                s.trace_id
            ) != normalized:
                continue
            spans.append(s)
        return spans

    def get_trace(self, trace_id: str) -> Call[List[Span]]:
        if self._disk is None:
            return self._archive.get_trace(trace_id)

        def run() -> List[Span]:
            from zipkin_tpu.internal.span_node import merge_trace

            spans = self._disk_trace_spans(trace_id)
            spans += self._archive.get_trace(trace_id).execute()
            return merge_trace(spans)

        return Call.of(run)

    def get_traces(self, trace_ids: Sequence[str]) -> Call[List[List[Span]]]:
        if self._disk is None:
            return self._archive.get_traces(trace_ids)

        def run() -> List[List[Span]]:
            from zipkin_tpu.storage.spi import trace_id_key

            out, seen = [], set()
            for tid in trace_ids:
                key = trace_id_key(tid, self.strict_trace_id)
                if key in seen:
                    continue
                seen.add(key)
                spans = self.get_trace(tid).execute()
                if spans:
                    out.append(spans)
            return out

        return Call.of(run)

    def get_traces_query(self, request: QueryRequest) -> Call[List[List[Span]]]:
        if self._disk is None:
            return self._archive.get_traces_query(request)

        def run() -> List[List[Span]]:
            if not self.search_enabled:
                return []
            return self._disk_query(request)

        return Call.of(run)

    def _disk_query(self, request: QueryRequest) -> List[List[Span]]:
        """getTraces over the disk archive: vectorized candidate masks on
        the INDEXED columns (service/span-name/remote/duration/window),
        then decode candidate traces and apply the exact
        ``QueryRequest.test`` predicate — annotationQuery and every other
        non-indexed clause are exact by post-filtering, the reference's
        fetch-then-filter row-store shape. Candidates scan newest
        segments first; if the post-filter starves the limit the scan
        widens once (the bounded-scan trade of a windowed store)."""
        from zipkin_tpu.internal.span_node import merge_trace
        from zipkin_tpu.model import json_v2
        from zipkin_tpu.storage.spi import group_by_trace_id, trace_id_key

        svc_id = rsvc_id = name_id = None
        if request.service_name:
            svc_id = self.vocab.services.get(request.service_name.lower())
            if svc_id is None:
                return []
        if request.remote_service_name:
            rsvc_id = self.vocab.services.get(
                request.remote_service_name.lower()
            )
            if rsvc_id is None:
                return []
        if request.span_name:
            name_id = self.vocab.span_names.get(request.span_name.lower())
            if name_id is None:
                return []
        lo_min = epoch_minutes(request.end_ts - request.lookback)
        hi_min = epoch_minutes(request.end_ts)

        def fetch(cand_limit: int) -> Tuple[List[List[Span]], bool]:
            # ONE view snapshot for the whole query: the live segment
            # sorts its rows when a view is taken, so per-trace
            # re-snapshots would re-sort per candidate
            views = self._disk.views()
            cands = self._disk.candidate_trace_ids(
                ts_lo_min=lo_min, ts_hi_min=hi_min,
                svc_id=svc_id, rsvc_id=rsvc_id, name_id=name_id,
                min_dur=request.min_duration, max_dur=request.max_duration,
                limit=cand_limit, views=views,
            )
            # RAM-archive union first (object-path spans of the same
            # traces plus traces only it holds) — cheap, no disk IO
            ram: dict = {}
            for trace in self._archive.get_traces_query(request).execute():
                key = trace_id_key(trace[0].trace_id, self.strict_trace_id)
                ram.setdefault(key, []).extend(trace)
            # INCREMENTAL candidate processing (r5, VERDICT r4 order 6's
            # other half): candidates arrive newest-first, so fetching
            # + decoding stops once `limit` traces PASS the exact
            # predicate — a broad query (e.g. service-only) decodes
            # ~limit traces, not the whole cand_limit over-fetch. The
            # bounded-scan trade is unchanged: a trace whose candidate
            # ts is older than the collected set but whose max span ts
            # is newer can still be missed, exactly as when cand_limit
            # bounded the scan.
            out = []
            seen_keys: set = set()
            for id64, _ in cands:
                if len(out) >= request.limit:
                    break
                raw = self._disk.fetch_trace_raw(
                    id64 & 0xFFFFFFFF, id64 >> 32, 0, 0, strict=False,
                    views=views,
                )
                spans = []
                for r in raw:
                    try:
                        spans.append(_decode_raw_span(r))
                    except Exception:  # pragma: no cover
                        continue
                for group in group_by_trace_id(spans, self.strict_trace_id):
                    key = trace_id_key(
                        group[0].trace_id, self.strict_trace_id
                    )
                    if key in seen_keys:
                        continue
                    seen_keys.add(key)
                    merged = merge_trace(group + ram.pop(key, []))
                    if request.test(merged):
                        out.append(merged)
            # Traces the disk walk never touched but the RAM archive
            # matched: their spans may ALSO exist on disk (an early
            # break above skips candidates once `limit` passed), so
            # fetch the disk half by trace id before merging — a
            # returned trace is always complete, never RAM-only
            # (r5 review finding). Bounded: the RAM query returns at
            # most `limit` traces.
            for key, spans in ram.items():
                merged = merge_trace(
                    spans
                    + self._disk_trace_spans(spans[0].trace_id, views=views)
                )
                if request.test(merged):
                    out.append(merged)
            out.sort(
                key=lambda t: max((s.timestamp or 0) for s in t),
                reverse=True,
            )
            return out[: request.limit], len(cands) >= cand_limit

        results, capped = fetch(request.limit * 4 + 16)
        if capped and len(results) < request.limit:
            # the post-filter starved the limit inside the first scan
            # window: widen once before settling for fewer results
            results, _ = fetch((request.limit * 4 + 16) * 8)
        return results

    def get_service_names(self) -> Call[List[str]]:
        if self._disk is None:
            return self._archive.get_service_names()

        def run() -> List[str]:
            if not self.search_enabled:
                return []
            # ids seen as a LOCAL service (remote names share the vocab
            # table but must not list — upstream ServiceAndSpanNames
            # semantics); bounded by max_services, listed without a
            # retention cutoff
            with self._names_lock:
                ids = list(self._local_svc_ids)
            names = {self.vocab.services.lookup(s) for s in ids}
            return sorted(n for n in names if n)

        return Call.of(run)

    def get_remote_service_names(self, service_name: str) -> Call[List[str]]:
        if self._disk is None:
            return self._archive.get_remote_service_names(service_name)

        def run() -> List[str]:
            if not self.search_enabled:
                return []
            sid = self.vocab.services.get(service_name.lower())
            with self._names_lock:
                rids = list(self._remote_by_svc.get(sid or -1, ()))
            names = {self.vocab.services.lookup(r) for r in rids}
            names |= set(
                self._archive.get_remote_service_names(service_name).execute()
            )
            return sorted(n for n in names if n)

        return Call.of(run)

    def get_span_names(self, service_name: str) -> Call[List[str]]:
        if self._disk is None:
            return self._archive.get_span_names(service_name)

        def run() -> List[str]:
            if not self.search_enabled:
                return []
            sid = self.vocab.services.get(service_name.lower())
            if sid is None:
                return []
            with self.vocab._lock:
                pairs = list(self.vocab._key_list)
            names = {
                self.vocab.span_names.lookup(nid)
                for s, nid in pairs
                if s == sid
            }
            return sorted(n for n in names if n)

        return Call.of(run)

    def get_keys(self) -> Call[List[str]]:
        return self._archive.get_keys()

    def get_values(self, key: str) -> Call[List[str]]:
        return self._archive.get_values(key)

    # -- aggregate reads: device ----------------------------------------

    def _cached_read(self, key: str, compute):
        """Memoize a device pull until the next QUERY-VISIBLE state
        mutation: the aggregator bumps write_version on step, rollup and
        restore — deliberately NOT on a digest flush, which changes no
        answer (the pend-fold and no-pend reads are bit-identical), so a
        read-triggered flush keeps every cached answer valid. The whole
        cache drops when the version advances — keys embed window
        minutes and quantile lists, so per-key staleness checks alone
        would let dead entries accumulate forever under a polling UI.

        Brownout read modes (runtime/overload.py, ISSUE 13): under
        B1/B2 (``cache_first``) a version-stale entry still serves if
        younger than the controller's staleness bound — the device pull
        it avoids would queue behind a saturated ingest lock; under B3
        (``cache_only``) any cached answer serves. Entries carry the
        write version they were computed at, so the staleness of every
        serve is exact; a cold key still computes (serving an error
        would turn a brownout into an outage for first-touch queries),
        and the first normal-mode read after recovery drops every
        stale entry wholesale."""
        t0 = time.perf_counter()
        t0_ns = time.perf_counter_ns()
        version = self.agg.write_version
        ctl = self.overload
        mode = ctl.read_mode() if ctl is not None else "normal"
        with self._read_cache_lock:
            if mode == "normal" and self._read_cache_version != version:
                self._read_cache.clear()
                self._read_cache_version = version
            hit = self._read_cache.get(key)
            if hit is not None:
                value, born, born_version = hit
                age_ms = (time.monotonic() - born) * 1000.0
                fresh = born_version == version
                serve = fresh or mode == "cache_only" or (
                    mode == "cache_first"
                    and age_ms <= ctl.max_stale_ms
                )
                if serve:
                    if not fresh:
                        self._read_cache_stale_serves += 1
                    self._read_cache_age_ms = age_ms
                    if age_ms > self._read_cache_age_max_ms:
                        self._read_cache_age_max_ms = age_ms
                    obs.record("query_cached", time.perf_counter() - t0)
                    querytrace.stamp_active(
                        querytrace.QSEG_CACHE_PROBE, t0_ns,
                        time.perf_counter_ns(),
                    )
                    return value
        # the probe segment ends where compute() begins — on a miss the
        # rest of the wall belongs to dispatch/transfer/unpack stamps
        querytrace.stamp_active(
            querytrace.QSEG_CACHE_PROBE, t0_ns, time.perf_counter_ns()
        )
        value = compute()
        obs.record("query_fresh", time.perf_counter() - t0)
        with self._read_cache_lock:
            if mode != "normal" or self._read_cache_version == version:
                self._read_cache[key] = (value, time.monotonic(), version)
        return value

    def invalidate_read_cache(self) -> None:
        """Drop memoized device pulls, including cached dependency
        answers (keeps the aggregator's link context). For harnesses
        that must re-measure device reads."""
        with self._read_cache_lock:
            self._read_cache.clear()
            self._deps_cache.clear()

    # -- epoch-published read mirror (tpu/mirror.py, ISSUE 14) -----------

    def _seed_mirror(self) -> None:
        """Pin the dashboard's default reads into the mirror's demand
        registry so the FIRST publish (boot, before the ticker starts)
        already carries them — the first post-boot dashboard refresh is
        lock-free, not a warming miss. Keys match `_cached_read`'s so
        mirror and fresh paths memoize the same computes."""
        qs = DEFAULT_QS
        qkey = ",".join(f"{q:.6g}" for q in qs)
        self.mirror.register(
            f"overview:{qkey}",
            lambda: self.agg.sketch_overview(qs), pinned=True,
        )
        self.mirror.register(
            "card", lambda: self.agg.cardinalities(), pinned=True,
        )
        self.mirror.register(
            f"quant:digest:{qkey}",
            lambda: self.agg.quantiles(qs, source="digest"), pinned=True,
        )

    def publish_mirror(self, force: bool = False,
                       paced: bool = False) -> bool:
        """One mirror epoch (see ReadMirror.publish): the windows ticker
        calls this each tick (``paced=True`` — the duty-cycle cap); the
        resume adapter calls it at boot. Reader-process demand drains
        FIRST, so a key a reader missed is carried by this very epoch —
        a shm-side miss costs one tick, like an in-process miss costs
        one lock-path read."""
        pub = self._segment_publisher
        if pub is not None:
            for key in pub.drain_demand():
                self.mirror_register_key(key)
        return self.mirror.publish(force=force, paced=paced)

    def attach_mirror_segment(self, segment) -> None:
        """Wire a shm mirror segment (serving/segment.py) into the
        publish path: each ReadMirror epoch is sanitized + serialized
        into the segment AFTER the snapshot swap — outside the
        aggregator lock, so publication stays ONE hold per tick. Call
        before the boot publish so crash-resume readers attach to a
        segment that already carries the restored epoch."""
        from zipkin_tpu.serving.publisher import SegmentPublisher

        pub = SegmentPublisher(segment)
        self._segment = segment
        self._segment_publisher = pub

        def sink(snap) -> None:
            tt = self.timetier
            pub.publish_snapshot(
                snap,
                vocab=self.vocab,
                max_stale_ms=self.mirror.max_stale_ms,
                deps_max_stale_ms=self._deps_max_stale_ms,
                time_bucket_minutes=self.config.time_bucket_minutes,
                global_hll_row=self.config.global_hll_row,
                tt_sealed_through=(
                    tt.sealed_through if tt is not None else None
                ),
                counters=self.ingest_counters(),
                mirror_generation=self.mirror.gen,
            )

        self.mirror.segment_sink = sink

    def mirror_register_key(self, key: str) -> bool:
        """Parse a reader-demanded mirror key string back into its
        compute closure and register it (unpinned, TTL'd — exactly the
        PR 14 demand-registry contract). The grammar is the closed set
        of key forms the store itself mints; anything else (including
        tenant-prefixed keys, whose scoped read planes do not exist
        yet) is refused and counted, never guessed at."""
        try:
            if key == "card":
                return self.mirror.register(
                    key, lambda: self.agg.cardinalities()
                )
            if key.startswith("overview:"):
                qs = tuple(
                    float(x) for x in key.split(":", 1)[1].split(",") if x
                )
                if qs:
                    return self.mirror.register(
                        key, lambda: self.agg.sketch_overview(qs)
                    )
            if key.startswith("quant:w:"):
                _, _, lo, hi, qstr = key.split(":", 4)
                lo_min, hi_min = int(lo), int(hi)
                qs = tuple(float(x) for x in qstr.split(",") if x)
                if qs:
                    return self.mirror.register(
                        key,
                        lambda: self.agg.quantiles(
                            qs, ts_lo_min=lo_min, ts_hi_min=hi_min
                        ),
                    )
            elif key.startswith("quant:"):
                _, src, qstr = key.split(":", 2)
                qs = tuple(float(x) for x in qstr.split(",") if x)
                if src in ("digest", "hist") and qs:
                    return self.mirror.register(
                        key, lambda: self.agg.quantiles(qs, source=src)
                    )
            if key.startswith("deps:"):
                _, lo, hi = key.split(":")
                lo_min, hi_min = int(lo), int(hi)
                return self.mirror.register(
                    key, lambda: self._dependency_links(lo_min, hi_min)
                )
            if key.startswith("ttq:") and self.timetier is not None:
                _, lo, hi = key.split(":")
                lo_ep, hi_ep = int(lo), int(hi)
                return self.mirror.register(
                    key,
                    lambda: self.timetier.window(self.agg, lo_ep, hi_ep),
                )
        except (ValueError, TypeError):
            pass
        self._demand_unparsed += 1
        return False

    def _mirror_bound(
        self, staleness_ms: Optional[float], default_ms: float
    ):
        """Fold the per-request staleness bound with the brownout read
        mode into ONE effective bound: ms the serve may be stale, None
        for any age (B3 cache-only), or _MIRROR_FRESH when the request
        opted out (``staleness_ms <= 0`` — the escape hatch for
        staleness-intolerant queries). Under B1/B2 cache-first the
        controller's bound can only LOOSEN the request's — brownout
        never makes answers fresher, it keeps them cheap."""
        if staleness_ms is not None and staleness_ms <= 0:
            return _MIRROR_FRESH
        bound = (
            float(staleness_ms) if staleness_ms is not None
            else float(default_ms)
        )
        ctl = self.overload
        mode = ctl.read_mode() if ctl is not None else "normal"
        if mode == "cache_first":
            bound = max(bound, float(ctl.max_stale_ms))
        elif mode == "cache_only":
            return None
        return bound

    def _mirror_serve(self, key: str, bound_ms, allow_stale: bool = True):  # zt-mirror-served: the whole point — a mirror serve must never acquire the aggregator lock (ZT10)
        """Serve ``key`` from the published mirror epoch, entirely
        lock-free: seqlock snapshot read, staleness check against the
        live write_version, stamp + record. None on a miss (caller
        falls through to the lock path and registers demand)."""
        mirror = self.mirror
        if mirror is None or not mirror.enabled:
            return None
        t0 = time.perf_counter()
        t0_ns = time.perf_counter_ns()
        hit = mirror.serve(
            key, bound_ms, self.agg.write_version, allow_stale
        )
        if hit is None:
            return None
        obs.record("query_mirror", time.perf_counter() - t0)
        querytrace.stamp_active(
            querytrace.QSEG_MIRROR_SERVE, t0_ns, time.perf_counter_ns()
        )
        return hit

    def _mirror_allow_stale(self, staleness_ms) -> bool:
        """May THIS request see a version-stale epoch? Yes when the
        caller opted in (explicit positive ``staleness_ms``), a
        brownout read mode is in force, or the aggregator lock is
        contended right now (non-blocking probe) — otherwise an exact
        read is cheap and default requests stay exact, the same
        posture ``_cached_read`` takes outside brownout. The probe is
        deliberately last: single-threaded callers never pay it a
        surprise stale answer, and under the load the mirror exists
        for, it is what keeps readers off the lock."""
        if staleness_ms is not None:
            return True
        ctl = self.overload
        if ctl is not None and ctl.read_mode() != "normal":
            return True
        probe = getattr(self.agg.lock, "would_block", None)
        return bool(probe is not None and probe())

    def _mirror_read(self, key: str, compute, staleness_ms=None):
        """Mirror-first read: serve lock-free from the published epoch
        when the age allows; otherwise register the key for the next
        epoch and fall through to the versioned read cache (which is
        where the aggregator lock — and the brownout cache-first logic
        for version-stale entries — lives). A cold key still computes
        fresh, so a brownout never turns into an outage for
        first-touch queries."""
        bound = self._mirror_bound(staleness_ms, self.mirror.max_stale_ms)
        if bound is not _MIRROR_FRESH:
            hit = self._mirror_serve(
                key, bound, self._mirror_allow_stale(staleness_ms)
            )
            if hit is not None:
                return hit[0]
            self.mirror.register(key, compute)
        return self._cached_read(key, compute)

    # -- time-disaggregated sketch tier (tpu/timetier.py, ISSUE 15) ------

    def tt_seal(self, limit: Optional[int] = None) -> int:
        """Ticker seam: seal every finished device time bucket into the
        host time tier (the windows ticker calls this each tick, next
        to publish_mirror). Returns segments sealed; 0 when the tier is
        disabled (``time_buckets=0``) or nothing is due."""
        if self.timetier is None:
            return 0
        return self.timetier.seal_up_to(self.agg, limit=limit)

    def _tt_epochs(self, end_ts: int, lookback: Optional[int]):
        """Bucket-aligned epoch range for a windowed sketch read — the
        mirror-key canonicalization: every (endTs, lookback) pair whose
        endpoints land in the same time buckets maps to the same
        (lo_ep, hi_ep), so a polling client stepping endTs by seconds
        reuses ONE ``ttq:`` demand key instead of registering a fresh
        key (and a fresh publish-time merge) per request."""
        g = self.config.time_bucket_minutes
        lb = lookback if lookback is not None else end_ts
        lo_ep = max(0, epoch_minutes(end_ts - lb) // g)
        hi_ep = max(0, epoch_minutes(end_ts) // g)
        return lo_ep, hi_ep

    def _tt_window(self, lo_ep: int, hi_ep: int, staleness_ms=None):
        """Mirror-first windowed sketch read: ONE demand key per
        bucket-aligned epoch range (``ttq:<lo_ep>:<hi_ep>``) carrying
        the merged WindowAnswer for all three windowed routes
        (quantiles, cardinalities, dependencies). A sealed-only
        window's compute never touches the aggregator lock; a range
        reaching past ``sealed_through`` re-enters it only for the one
        packed device pull of the unsealed current bucket."""
        key = f"ttq:{lo_ep}:{hi_ep}"
        return self._mirror_read(
            key,
            # lambda derefs self.agg/self.timetier at CALL time
            # (clear() swaps the aggregator wholesale)
            lambda: self.timetier.window(self.agg, lo_ep, hi_ep),
            staleness_ms,
        )

    def _tt_dependency_links(self, ans) -> List[DependencyLink]:
        """Shape a merged WindowAnswer's dense edge planes into API
        links (the dense-pull shaping from _dependency_links)."""
        t0_ns = time.perf_counter_ns()
        s = self.config.max_services
        dense_c = np.asarray(ans.calls)
        dense_e = np.asarray(ans.errs)
        p_idx, c_idx = np.nonzero(dense_c)
        out: List[DependencyLink] = []
        for p, c in zip(p_idx, c_idx):
            parent = self.vocab.services.lookup(int(p))
            child = self.vocab.services.lookup(int(c))
            if not parent or not child:
                continue
            out.append(
                DependencyLink(
                    parent=parent,
                    child=child,
                    call_count=int(dense_c[p, c]),
                    error_count=int(dense_e[p, c]),
                )
            )
        querytrace.stamp_active(
            querytrace.QSEG_LINK_RESOLVE, t0_ns, time.perf_counter_ns()
        )
        return out

    def get_dependencies(
        self, end_ts: int, lookback: int,
        staleness_ms: Optional[float] = None,
    ) -> Call[List[DependencyLink]]:
        def run() -> List[DependencyLink]:
            qt = self.querytrace.begin("dependencies")
            try:
                return self._get_dependencies(end_ts, lookback, staleness_ms)
            finally:
                self.querytrace.finish(qt)

        return Call.of(run)

    def _get_dependencies(
        self, end_ts: int, lookback: int,
        staleness_ms: Optional[float] = None,
    ) -> List[DependencyLink]:
            tt = self.timetier
            if tt is not None:
                lo_ep, hi_ep = self._tt_epochs(end_ts, lookback)
                if lo_ep <= tt.sealed_through:
                    # time-tier route (ISSUE 15): some of the window is
                    # already sealed — merge the covering segments
                    # host-side (exact per-bucket edge counts, verified
                    # bit-equal to the dense ring pull) instead of
                    # re-sorting the span ring; windows the sealer has
                    # not reached yet stay on the ring path below
                    ans = self._tt_window(lo_ep, hi_ep, staleness_ms)
                    return self._tt_dependency_links(ans)
            lo_min = epoch_minutes(end_ts - lookback)
            hi_min = epoch_minutes(end_ts)
            # mirror-first: the published epoch carries the final link
            # list (resolved on the publisher thread), so a hit returns
            # without touching the aggregator lock OR the deps cache.
            # Dependencies already tolerate bounded staleness by design
            # (the reference's table is an offline batch job), so the
            # deps bound — not the general mirror bound — is the default.
            bound = self._mirror_bound(staleness_ms, self._deps_max_stale_ms)
            if bound is not _MIRROR_FRESH:
                mkey = f"deps:{lo_min}:{hi_min}"
                hit = self._mirror_serve(mkey, bound)
                if hit is not None:
                    return hit[0]
                self.mirror.register(
                    mkey,
                    lambda: self._dependency_links(lo_min, hi_min),
                )
            fresh = self.agg.write_version
            now = time.monotonic()
            t0 = time.perf_counter()
            t0_ns = time.perf_counter_ns()
            with self._read_cache_lock:
                hit = self._deps_cache.get((lo_min, hi_min))
                if hit is not None:
                    value, version, t = hit
                    if version == fresh or (
                        (now - t) * 1000.0 < self._deps_max_stale_ms
                    ):
                        age_ms = (now - t) * 1000.0
                        self._read_cache_age_ms = age_ms
                        if age_ms > self._read_cache_age_max_ms:
                            self._read_cache_age_max_ms = age_ms
                        obs.record("query_cached", time.perf_counter() - t0)
                        querytrace.stamp_active(
                            querytrace.QSEG_CACHE_PROBE, t0_ns,
                            time.perf_counter_ns(),
                        )
                        return value
            querytrace.stamp_active(
                querytrace.QSEG_CACHE_PROBE, t0_ns, time.perf_counter_ns()
            )
            value = self._compute_dependencies(lo_min, hi_min)
            with self._read_cache_lock:
                self._deps_cache[(lo_min, hi_min)] = (value, fresh, now)
                # prune by age so shifting endTs windows can't grow this
                stale = [
                    k for k, (_, _, t) in self._deps_cache.items()
                    if (now - t) * 1000.0 >= self._deps_max_stale_ms
                ]
                for k in stale:
                    if k != (lo_min, hi_min):
                        del self._deps_cache[k]
            return value

    def _compute_dependencies(
        self, lo_min: int, hi_min: int
    ) -> List[DependencyLink]:
        return self._dependency_links(
            lo_min, hi_min, fetch=self._cached_read
        )

    def _dependency_links(
        self, lo_min: int, hi_min: int, fetch=None
    ) -> List[DependencyLink]:
            # edge pull + vocab resolution, parameterized by the fetch
            # seam: the query path memoizes through _cached_read; the
            # mirror publisher (already holding the aggregator lock for
            # its one epoch hold) calls the aggregator directly so a
            # publish never populates the versioned read cache
            if fetch is None:
                def fetch(_key, compute):
                    return compute()
            # edges compacted on device: [E] vectors, not dense [S, S]
            idx, calls, errors = fetch(
                f"edges:{lo_min}:{hi_min}",
                lambda: self.agg.dependency_edges(lo_min, hi_min),
            )
            s = self.config.max_services
            live = calls > 0
            if bool(live.all()) and len(calls) < s * s:
                # every top-k slot is occupied: the graph has more edges
                # than the compaction width — fall back to the dense
                # matrices so no edge is silently dropped (the compact
                # path stays the common case; real service graphs are
                # sparse)
                logger.debug(
                    "dependency edge compaction full (%d); using dense pull",
                    len(calls),
                )
                lo2, hi2 = lo_min, hi_min
                dense_c, dense_e = fetch(
                    f"depmat:{lo2}:{hi2}",
                    lambda: self.agg.dependency_matrices(lo2, hi2),
                )
                p_idx, c_idx = np.nonzero(dense_c)
                flat_idx = p_idx * s + c_idx
                idx, calls, errors = (
                    flat_idx, dense_c[p_idx, c_idx], dense_e[p_idx, c_idx]
                )
                live = calls > 0
            t0_ns = time.perf_counter_ns()
            out: List[DependencyLink] = []
            for flat, n_calls, n_errs in zip(idx[live], calls[live], errors[live]):
                parent = self.vocab.services.lookup(int(flat) // s)
                child = self.vocab.services.lookup(int(flat) % s)
                if not parent or not child:
                    continue
                out.append(
                    DependencyLink(
                        parent=parent,
                        child=child,
                        call_count=int(n_calls),
                        error_count=int(n_errs),
                    )
                )
            querytrace.stamp_active(
                querytrace.QSEG_LINK_RESOLVE, t0_ns, time.perf_counter_ns()
            )
            return out

    def latency_quantiles(
        self,
        qs: Sequence[float],
        service_name: Optional[str] = None,
        span_name: Optional[str] = None,
        use_digest: bool = True,
        end_ts: Optional[int] = None,
        lookback: Optional[int] = None,
        staleness_ms: Optional[float] = None,
    ) -> List[dict]:
        """Latency percentile rows per (service, spanName) — the read the
        Lens duration-percentile context needs, served from sketches.

        With ``end_ts``/``lookback`` (epoch ms, as in the query API) the
        rows come from the time tier when its sealer has reached the
        window (per-bucket t-digests merged host-side over the covering
        sealed segments — ARBITRARY ranges, ISSUE 15), else from the
        time-sliced histograms covering the most recent
        T*slice_minutes of traffic (``use_digest=False`` forces the
        hist-slice path). Returns dicts: {service, spanName, count,
        quantiles: {q: µs}}.

        ``staleness_ms`` tunes the mirror-first serve: None accepts the
        mirror's published bound, a positive value tightens/loosens it
        per request, and <= 0 forces a fresh lock-path read.
        """
        qt = self.querytrace.begin("quantiles")
        try:
            if end_ts is None and lookback is not None:
                # Zipkin query convention: endTs defaults to "now" when
                # only lookback is given (QueryRequest semantics,
                # SURVEY.md §2.3)
                end_ts = int(time.time() * 1000)
            qkey = ",".join(f"{q:.6g}" for q in qs)
            if end_ts is not None:
                tt = self.timetier
                lo_ep, hi_ep = (
                    self._tt_epochs(end_ts, lookback)
                    if tt is not None else (0, -1)
                )
                if (
                    use_digest and tt is not None
                    and lo_ep <= tt.sealed_through
                ):
                    # time-tier route (ISSUE 15): per-bucket t-digests
                    # merged host-side over the covering sealed
                    # segments (ops/ttmerge.py) — arbitrary [lookback,
                    # endTs] ranges, not just the hist-slice horizon;
                    # the unsealed current bucket is the one device
                    # pull when the range reaches it
                    ans = self._tt_window(lo_ep, hi_ep, staleness_ms)
                    source_q = ttmerge.digest_quantile(ans.digest, qs)
                    counts = ttmerge.digest_total(ans.digest)
                else:
                    lb = lookback if lookback is not None else end_ts
                    lo_min = epoch_minutes(end_ts - lb)
                    hi_min = epoch_minutes(end_ts)
                    source_q, counts = self._mirror_read(
                        f"quant:w:{lo_min}:{hi_min}:{qkey}",
                        lambda: self.agg.quantiles(
                            qs, ts_lo_min=lo_min, ts_hi_min=hi_min
                        ),
                        staleness_ms,
                    )
            else:
                src = "digest" if use_digest else "hist"
                source_q, counts = self._mirror_read(
                    f"quant:{src}:{qkey}",
                    lambda: self.agg.quantiles(qs, source=src),
                    staleness_ms,
                )

            return self._quantile_rows(
                qs, source_q, counts, service_name, span_name
            )
        finally:
            self.querytrace.finish(qt)

    def _quantile_rows(
        self,
        qs: Sequence[float],
        source_q: np.ndarray,
        counts: np.ndarray,
        service_name: Optional[str],
        span_name: Optional[str],
    ) -> List[dict]:
        """Shape pulled ([K, Q], [K]) quantile arrays into API rows —
        shared by latency_quantiles and the coalesced sketch_overview."""
        t0_ns = time.perf_counter_ns()
        try:
            return self._quantile_rows_inner(
                qs, source_q, counts, service_name, span_name
            )
        finally:
            querytrace.stamp_active(
                querytrace.QSEG_SERIALIZE, t0_ns, time.perf_counter_ns()
            )

    def _quantile_rows_inner(
        self,
        qs: Sequence[float],
        source_q: np.ndarray,
        counts: np.ndarray,
        service_name: Optional[str],
        span_name: Optional[str],
    ) -> List[dict]:
        want_svc = (
            self.vocab.services.get(service_name.lower()) if service_name else None
        )
        if service_name and want_svc is None:
            return []
        # vectorized row selection over the key vocab (the round-1 per-key
        # Python loop scanned all max_keys rows per query)
        with self.vocab._lock:
            pairs = np.asarray(self.vocab._key_list, np.int32)  # [num_keys, 2]
        kids = np.arange(1, pairs.shape[0])
        mask = counts[kids] > 0
        if want_svc is not None:
            mask &= pairs[kids, 0] == want_svc
        if span_name:
            want_name = self.vocab.span_names.get(span_name.lower())
            if want_name is None:
                return []
            mask &= pairs[kids, 1] == want_name
        out = []
        for kid in kids[mask]:
            out.append(
                {
                    "serviceName": self.vocab.services.lookup(int(pairs[kid, 0])),
                    "spanName": self.vocab.span_names.lookup(int(pairs[kid, 1])),
                    "count": int(counts[kid]),
                    "quantiles": {
                        float(q): float(source_q[kid, i]) for i, q in enumerate(qs)
                    },
                }
            )
        return out

    def _cardinality_rows(self, est: np.ndarray) -> dict:
        # operating-envelope guard: past envelope_max the estimator's
        # bias exceeds half its 3σ noise gate, so the number reads as a
        # lower bound, not an estimate — count it, gauge it, say it once
        beyond = int((est > self._hll_envelope_max).sum())
        if beyond:
            self._hll_envelope_exceeded += 1
            if not self._hll_beyond_envelope_rows:
                logger.warning(
                    "%d HLL row(s) estimate beyond the p=%d operating "
                    "envelope (%.3g): bias now dominates noise; treat "
                    "these cardinalities as lower bounds",
                    beyond,
                    self.config.hll_precision,
                    self._hll_envelope_max,
                )
        self._hll_beyond_envelope_rows = beyond
        out = {"_global": float(est[self.config.global_hll_row])}
        for name in self.vocab.services.names:
            sid = self.vocab.services.get(name)
            if sid:
                out[name] = float(est[sid])
        return out

    def trace_cardinalities(
        self, staleness_ms: Optional[float] = None,
        end_ts: Optional[int] = None,
        lookback: Optional[int] = None,
    ) -> dict:
        """Estimated distinct trace counts: {"_global": n, service: n, ...}.

        With ``end_ts``/``lookback`` (epoch ms) the registers come from
        the time tier's covering bucket segments (HLL register-max
        merge, ops/ttmerge.py) — windowed cardinality over arbitrary
        ranges; without a window the all-time cumulative registers
        serve, as before."""
        qt = self.querytrace.begin("cardinalities")
        try:
            if end_ts is None and lookback is not None:
                # endTs defaults to "now" when only lookback is given
                # (QueryRequest semantics, SURVEY.md §2.3)
                end_ts = int(time.time() * 1000)
            if end_ts is not None and self.timetier is not None:
                lo_ep, hi_ep = self._tt_epochs(end_ts, lookback)
                ans = self._tt_window(lo_ep, hi_ep, staleness_ms)
                return self._cardinality_rows(ttmerge.hll_estimate(ans.hll))
            # lambda, not the bound method: a registered demand closure
            # must deref self.agg at CALL time (clear() swaps it)
            est = self._mirror_read(
                "card", lambda: self.agg.cardinalities(), staleness_ms
            )
            return self._cardinality_rows(est)
        finally:
            self.querytrace.finish(qt)

    def sketch_overview(
        self,
        qs: Sequence[float],
        service_name: Optional[str] = None,
        span_name: Optional[str] = None,
        staleness_ms: Optional[float] = None,
    ) -> dict:
        """Everything the UI sketch page shows, from ONE device dispatch
        and ONE device→host transfer: {"percentiles": latency_quantiles
        rows, "cardinalities": trace_cardinalities dict, "counters":
        ingest_counters dict}. Replaces three aggregator reads (and three
        HTTP round trips) per page refresh. Mirror-served by default:
        the raw packed triple comes from the published epoch (row
        shaping and the live counters dict still run per request)."""
        qt = self.querytrace.begin("overview")
        try:
            qkey = ",".join(f"{q:.6g}" for q in qs)
            source_q, counts, est = self._mirror_read(
                f"overview:{qkey}",
                lambda: self.agg.sketch_overview(qs),
                staleness_ms,
            )
            return {
                "percentiles": self._quantile_rows(
                    qs, source_q, counts, service_name, span_name
                ),
                "cardinalities": self._cardinality_rows(est),
                "counters": self.ingest_counters(),
            }
        finally:
            self.querytrace.finish(qt)

    def ingest_counters(self) -> dict:
        from zipkin_tpu.obs.device import OBSERVATORY

        _dev_totals = OBSERVATORY.totals()
        # host counters: exact and wrap-free (device counters are u32)
        return {
            **self.agg.host_counters,
            # read-side ledger: hostTransfers / query counts ≈ 1 is the
            # one-transfer invariant, observable in production
            "hostTransfers": self.agg.read_stats["host_transfers"],
            "rolledOnlyReads": self.agg.read_stats["rolled_only_reads"],
            "ctxReads": self.agg.read_stats["ctx_reads"],
            # process-wide transfer volume through the readpack
            # chokepoint, next to the per-store transfer count above
            "hostTransferBytes": readpack.transfer_bytes(),
            # device-program observatory aggregates (process-global):
            # steady state must hold deviceRecompiles at 0 after warmup
            "deviceProgramCalls": _dev_totals["calls"],
            "deviceCompiles": _dev_totals["compiles"],
            "deviceRecompiles": _dev_totals["recompiles"],
            # its completion clock (process-global too): steps the device
            # has run, plain against maintenance-fused, their device and
            # queue time, and how far the host is ahead of the device
            # (step* / deviceQueue*; all 0 with the observatory off)
            **OBSERVATORY.queue.counters(),
            # incremental link-ctx gauges (ISSUE 5): lanes the next
            # fresh read must delta-merge (bounded by rollup_segment),
            # ctx advances run, and the device time of the last
            # ctx-advancing (rollup-fused) step, from the completion clock
            "ctxDeltaLanes": self.agg._lanes_since_rollup,
            "ctxAdvances": self.agg.ctx_stats["ctx_advances"],
            "ctxMaintenanceMs": self.agg.ctx_stats["ctx_maintenance_ms"],
            # HLL envelope guard: reads that saw a bias-dominated row /
            # rows beyond at the last read (both 0 in healthy operation)
            "hllEnvelopeExceeded": self._hll_envelope_exceeded,
            "hllBeyondEnvelopeRows": self._hll_beyond_envelope_rows,
            "serviceVocabOverflow": self.vocab.services.overflow,
            "keyVocabOverflow": self.vocab._overflow,
            # the fast path interns in C; rejected entries never reach
            # the Python journal so the C counter is separate
            "nativeVocabOverflow": (
                self._nvocab.overflow if self._nvocab is not None else 0
            ),
            # boot-time restore gauges (restoreMs / walReplayBatches /
            # walReplayMs): how much recovery cost the last boot
            **self.restore_stats,
            **(self._disk.counters() if self._disk is not None else {}),
            # at-rest integrity gauges (scrubBytes / segmentsQuarantined
            # / spansQuarantined / ...): what the background scrubber
            # verified and what it had to pull from service
            **(self.scrubber.counters() if self.scrubber is not None else {}),
            # sampling-tier gauges (samplerPublishes / samplerPressure /
            # budgetUtilization / samplerRate*) — sampledKept/Dropped
            # come exact from agg.host_counters above
            **(
                self.sampling_controller.counters()
                if self.sampling_controller is not None
                else {}
            ),
            # fan-out tier gauges (mpWorkersAlive / mpInflight /
            # mpRejected ...): present only when the MP tier is attached
            **(
                self.mp_ingester.stats() if self.mp_ingester is not None else {}
            ),
            # accuracy-observatory gauges (accuracyDigestP99RelErr /
            # accuracyHllRelErr / accuracyLinkRecall / shadow* ...):
            # present only when the shadow plane is attached
            **(
                self.accuracy.export_counters()
                if self.accuracy is not None
                else {}
            ),
            # query-plane observatory (obs/querytrace.py): stitched
            # per-query aggregates + the aggregator-lock contention
            # ledger (queryLock* gauges; the nested queryLock table is
            # skipped by flat consumers, rendered by /prometheus)
            **self.querytrace.counters(),
            # cached-read staleness: age-at-serve of the last cache hit
            # (read cache or bounded-stale deps cache), its high-water,
            # and the live read-cache entry count
            "readCacheServeAgeMs": round(self._read_cache_age_ms, 3),
            "readCacheServeAgeMaxMs": round(self._read_cache_age_max_ms, 3),
            "readCacheEntries": len(self._read_cache),
            # brownout cache-first/cache-only serves (ISSUE 13):
            # version-stale answers served under overload read modes
            "readCacheStaleServes": self._read_cache_stale_serves,
            # epoch-published read mirror (ISSUE 14): generation,
            # publish cost, lock-free serve tallies, staleness-at-serve
            # gauges — mirrorServeAgeMs backs the query_mirror_staleness
            # SLO and the zipkin_tpu_mirror_* prometheus families
            **self.mirror.counters(),
            # scale-out serving segment (serving/, ISSUE 19): publish /
            # overflow / demand-backchannel tallies plus the worst live
            # reader's age-at-serve (readerServeAgeMs — backs the
            # reader_staleness SLO) and generation lag
            "mirrorSegmentSinkErrors": self.mirror.segment_sink_errors,
            "readerDemandUnparsed": self._demand_unparsed,
            **(
                self._segment_publisher.counters()
                if self._segment_publisher is not None
                else {}
            ),
            # time-disaggregated sketch tier (ttSeals / ttSegments* /
            # ttWindowReads / ttMissingEpochs ...): seal cadence, ring
            # occupancy, and windowed-read merge cost
            **(
                self.timetier.export_counters()
                if self.timetier is not None
                else {}
            ),
        }

    def set_query_observatory(self, on: bool) -> None:
        """Enable/disable per-query tracing and the lock ledger together
        (server config plumb-through). Remembered so :meth:`clear`'s
        aggregator swap — which builds a fresh instrumented lock with
        the env default — reapplies the configured state."""
        self._query_obs_enabled = bool(on)
        self.querytrace.enabled = bool(on)
        lk = getattr(self.agg, "lock", None)
        if lk is not None and hasattr(lk, "set_enabled"):
            lk.set_enabled(on)

    def sampler_rates(self) -> dict:
        """{service: keep fraction} from the published rate table — the
        perServiceRate gauge surface (labels, so not in the flat
        ingest_counters dict). Empty when the sampling tier is off."""
        sampler = self.agg.sampler
        if sampler is None:
            return {}
        from zipkin_tpu.sampling import RATE_ONE

        out = {}
        for name in self.vocab.services.names:
            sid = self.vocab.services.get(name)
            if sid:
                out[name] = float(sampler.rate[sid]) / RATE_ONE
        return out

    # -- lifecycle -------------------------------------------------------

    def check(self) -> CheckResult:
        try:
            # zt-lint: disable=ZT06 — the health check's contract is to
            # prove the device round-trips; blocking IS the probe
            self.agg.block_until_ready()
            return CheckResult.OK
        except Exception as e:  # pragma: no cover - device failure path
            return CheckResult.failed(e)

    def close(self) -> None:
        self._closed = True
        if self.scrubber is not None:
            self.scrubber.stop()
        if self.sampling_controller is not None:
            self.sampling_controller.stop()
        if self._disk is not None:
            self._disk.close()
        self._archive.close()

    def clear(self) -> None:
        """Test helper: drop archive + reset device state."""
        from zipkin_tpu.parallel.sharded import ShardedAggregator

        self._archive.clear()
        self.agg = ShardedAggregator(self.config, mesh=self.agg.mesh)
        # sealed segments were cut from the old aggregator's buckets —
        # a windowed read must not merge them with the new one's
        if self.timetier is not None:
            self.timetier.clear()
        # the swap replaced the aggregator: the published mirror epoch
        # was cut against versions that no longer compare — drop it
        # (demand keys survive; the next publish refills)
        self.mirror.reset()
        # the swap replaced the instrumented lock; drop stitched state
        # from the old aggregator and reapply configured enablement
        self.querytrace.reset()
        if self._query_obs_enabled is not None:
            self.set_query_observatory(self._query_obs_enabled)
