"""Multi-process parse/pack fan-out feeding the single dispatch core.

The reference scales ingest horizontally with N collector workers/nodes
(Kafka partition parallelism, ``KafkaCollector.java`` — SURVEY.md §2.8);
under CPython one process cannot: the r2 profile measured the device path
at ~490k spans/s/chip with the host parse GIL-serialized, and a threaded
pipeline in one process measured SLOWER than the synchronous loop
(PERF.md section 6). This module is the multi-process
fan-out tier (ISSUE 8, rebuilt around the span ring in ISSUE 16), the
collector's real fast path for both JSON v2 and proto3 payloads over
HTTP and gRPC:

- **N parse workers** (``spawn``, never importing jax): raw JSON/proto3
  bytes -> native C parse + LOCAL vocab interning -> columnar pack ->
  trace-affine shard routing -> the packed 11-row wire image written
  straight into a **shared-memory span-ring slot** (tpu/ring.py)
  together with the chunk's pickled sidecar (vocab journal, archive
  slices, disk record). No per-chunk metadata message, no pickling of
  the image: publishing a slot is a handful of word stores behind a
  seqlock generation, and the per-worker stripe makes the handoff
  lock-free in both directions.
- **One dispatcher thread** (main process, owns the device): drains
  contiguous runs of READY slots per stripe, replays each chunk's vocab
  journal into the GLOBAL vocab, then flushes completed payloads in
  **coalesced groups**: up to ``coalesce_max`` chunks (bounded by the
  aggregator's lane cap) become ONE ``concat_remap`` gather into a
  bucket-padded image + ONE jitted ingest step + ONE WAL record, acked
  together — amortizing the ~16 µs/span per-chunk dispatch overhead
  the r08 run measured. The chunk image is consumed as a zero-copy view
  into its ring slot; the coalesce gather (or, at ``coalesce_max=1``,
  the same per-chunk copy+remap as before) is the only copy it takes.
  WAL append and sampling verdicts ride ``ingest_fused`` on this side,
  so ack-after-durability semantics are bit-identical to the serial
  path. Remapping is what lets workers intern lock-free: ids only need
  to be consistent per-worker; the journal replays them into one global
  id space.

Ordering across the two channels (ring slots for images, the result
queue for oversized sidecars / strict-codec punts / EOF) is pinned by a
per-worker chunk sequence number: the dispatcher applies a worker's
chunks strictly in ``wseq`` order, holding back whichever channel runs
ahead, so a payload's chunks — and its vocab-journal deltas — replay in
exactly the order the worker produced them.

Backpressure contract: ring occupancy is the tier's backpressure basis.
A full stripe stalls its worker's blocking ``claim()``, the stalled
worker stops pulling from its bounded delivery queue, and the queue
fills — so ring congestion propagates to the submit boundary without
ever rejecting while a queue slot is free (routing merely PREFERS
workers with stripe headroom). ``submit(..., block=False)`` — the
server-boundary mode — raises :class:`IngestBackpressure` only when
every live worker's delivery queue is full. The HTTP site maps it to 429
and the gRPC site to RESOURCE_EXHAUSTED so senders back off instead of
the tier buffering unboundedly. Since ISSUE 13 that rejection is the
LAST backpressure surface, not the only one: the overload control plane
(runtime/overload.py) sheds at the collector boundary first — per-tenant
budget sheds (scope ``tenant``: one flooding tenant is limited while
everyone else rides B0) and then global B2/B3 brownout admission (scope
``global``) — tightens the sampling tier's budget under sustained
pressure, and stamps every rejection with backoff guidance AND its
shedding scope (``Retry-After`` / ``X-Shed-Scope`` on HTTP,
``retry-delay`` / ``shed-scope`` gRPC trailers). A saturation rejection
from this tier is a global-scope shed: every tenant's traffic funnels
through the same worker queues.

Zero-loss worker death: the dispatcher retains every submitted payload
(``_pending``) until its results are APPLIED, and buffers per-payload
state mutations until the payload's completion chunk arrives. A worker
that dies mid-payload therefore loses nothing: its ring stripe is
reclaimed (published-but-unconsumed slots discarded, the torn
mid-write slot a SIGKILL leaves reset via the pid guard), its buffered
chunks are discarded (never applied, so no double-ingest) and every
payload it owned — queued or in-process — re-ingests on the slow path.
The pool keeps serving on the survivors; only a dead DISPATCHER (device
failure) surfaces as an error to submit()/drain().

Sampled archive parity: workers extract the same trace-affine 1/N span
slices the synchronous fast path archives (byte extents from the native
parser); the dispatcher re-decodes them with the reference codec
(format-sniffing, so proto3 payloads archive too), and
``/api/v2/trace/{id}`` serves identical spans whichever tier ingested.

On a single-core host this tier cannot beat the synchronous path (the
workers and the PJRT client time-slice one core — measured and recorded
in PROFILE_r03.md); it exists for multi-core hosts, where parse scales
with worker count while the dispatcher stays a thin device feeder.
"""

from __future__ import annotations

import logging
import multiprocessing as mp
import queue
import threading
import time
from typing import Dict, List, Optional, Set

import numpy as np

from zipkin_tpu import faults, obs
from zipkin_tpu.obs import critpath as _critpath
from zipkin_tpu.tpu import ring as ring_mod

logger = logging.getLogger(__name__)

# worker -> dispatcher result-queue message kinds. Chunk IMAGES travel
# through the span ring; the queue carries only what cannot ride a
# bounded slot (oversized sidecars, empty-payload completions), the
# strict-codec punts, and EOF.
_KIND_BATCH = 0      # (kind, widx, pid, wseq, fused|None, n_spans, n_dur,
#                       n_err, dropped, svc_new, name_new, pairs_new,
#                       arch, ts_range, rec, parse_s, pack_s, route_s)
_KIND_FALLBACK = 1   # (kind, widx, pid, wseq)
_KIND_EOF = 2        # (kind, widx)
_KIND_NUDGE = 3      # (kind,) — wakeup only: a ring slot was published


class IngestBackpressure(RuntimeError):
    """The ingest tier refused a payload it could not absorb: every
    live parse worker's delivery queue is full — each backed up behind
    a congested ring stripe or a busy worker — in
    ``submit(..., block=False)``, the admission chokepoint shed it
    (per-tenant budget or global brownout ladder, ISSUEs 13/18), or an
    injected allocation failure fired. The server boundary maps it to
    HTTP 429 / gRPC RESOURCE_EXHAUSTED — with backoff guidance and the
    shedding ``scope`` attached, so a client can tell "you are being
    limited" (scope ``tenant``, guidance from that tenant's own budget)
    from "the system is browning out" (scope ``global``, guidance from
    the load index) — and senders back off and retry instead of the
    tier buffering unboundedly."""

    def __init__(self, msg: str = "", *, scope: str = "global",
                 tenant: Optional[str] = None,
                 retry_after_s: Optional[float] = None) -> None:
        super().__init__(msg)
        self.scope = scope
        self.tenant = tenant
        self.retry_after_s = retry_after_s


def _extract_archive_slices(parsed, every: int) -> List[bytes]:
    """The worker half of TpuStorage._archive_fast_sample: the exact raw
    byte extents of the trace-affine 1/N sample (same hash rule, so the
    MP tier archives the same spans the sync path would)."""
    from zipkin_tpu.tpu.columnar import _mix32

    if every <= 0:
        return []
    n = parsed.n
    tid = parsed.tl0[:n] ^ parsed.tl1[:n] ^ parsed.th0[:n] ^ parsed.th1[:n]
    pick = np.nonzero(_mix32(tid) % np.uint32(every) == 0)[0]
    data = parsed.data
    off, ln = parsed.span_off, parsed.span_len
    return [bytes(data[off[i] : off[i] + ln[i]]) for i in pick]


def _worker_main(
    widx: int,
    work_q,
    result_q,
    ring_params: dict,
    params: dict,
) -> None:
    """Parse worker entry point (child process; numpy + C parser only —
    importing jax here would drag a PJRT client into every worker)."""
    from zipkin_tpu import native
    from zipkin_tpu.native import PARSED_FIELDS
    from zipkin_tpu.obs.critpath import (
        SEG_PACK,
        SEG_PARSE,
        SEG_RING_WAIT,
        SEG_ROUTE,
        CritPathWorkerView,
    )
    from zipkin_tpu.tpu.archive import parsed_record
    from zipkin_tpu.tpu.columnar import Vocab, pack_parsed, route_fused
    from zipkin_tpu.tpu.ring import RingProducer, pack_aux

    prod = RingProducer(ring_params, widx)
    cp_params = params.get("critpath")
    cview = (
        CritPathWorkerView(cp_params, widx) if cp_params is not None else None
    )
    vocab = Vocab(params["max_services"], params["max_keys"])
    nvocab = native.NativeVocab(vocab) if native.available() else None
    n_shards = params["n_shards"]
    max_batch = params["max_batch"]
    pad = params["pad"]
    every = params["archive_every"]
    disk = params["archive_disk"]  # ship per-chunk raw records for the
    # disk archive (worker-LOCAL vocab ids; dispatcher remaps to global)
    boundary = params["sample_boundary"]  # None = keep everything
    # journal cursors: how much of the local vocab has been reported
    sent_svc, sent_name, sent_pair = 1, 1, 1

    def handle(pid: int, payload: bytes, state: dict, cslot: int,
               tidx: int) -> None:
        nonlocal sent_svc, sent_name, sent_pair
        traced = cview is not None and cslot >= 0
        if traced:
            # per-payload recalibration keeps the cross-process clock
            # bridge fresh; perf_counter floats convert losslessly to ns
            # at process-uptime magnitudes, so the stamps below reuse
            # the timestamps the stage timings already take
            cview.calibrate()
        t0 = time.perf_counter()
        # parse_spans sniffs the wire format: JSON v2 and proto3
        # ListOfSpans both land here, so the fan-out is format-agnostic
        parsed = (
            native.parse_spans(payload, nvocab=nvocab)
            if nvocab is not None
            else None
        )
        if parsed is None:
            # the strict-codec fallback needs Span objects: punt back to
            # the dispatcher, which still holds the payload bytes
            state["completed"] = True
            result_q.put((_KIND_FALLBACK, widx, pid, prod.next_wseq()))
            return
        nvocab.sync()
        n = parsed.n
        dropped = 0
        if boundary is not None and n:
            keep = native.sampler_keep(parsed, n, boundary)
            dropped = int(n - keep.sum())
            if dropped:
                idx = np.nonzero(keep)[0]
                for field in PARSED_FIELDS:
                    col = getattr(parsed, field, None)
                    if col is not None:
                        setattr(parsed, field, col[:n][idx])
                parsed.n = n = len(idx)
        parse_s = time.perf_counter() - t0
        if traced:
            cview.stamp(
                cslot, SEG_PARSE, int(t0 * 1e9),
                int((t0 + parse_s) * 1e9),
            )
        if n == 0:
            state["completed"] = True
            result_q.put(
                (_KIND_BATCH, widx, pid, prod.next_wseq(), None, 0, 0, 0,
                 dropped, [], [], [], [], (0, 0), None, parse_s, 0.0, 0.0)
            )
            return
        for lo in range(0, n, max_batch):
            hi = min(lo + max_batch, n)
            if lo == 0 and hi == n:
                sub = parsed
            else:
                sub = native.ParsedColumns()
                sub.data = parsed.data
                for f in PARSED_FIELDS:
                    col = getattr(parsed, f, None)
                    setattr(sub, f, None if col is None else col[lo:hi])
                sub.n = hi - lo
            t1 = time.perf_counter()
            cols = pack_parsed(sub, vocab, pad)
            t2 = time.perf_counter()
            fused = route_fused(cols, n_shards)
            route_s = time.perf_counter() - t2
            pack_s = t2 - t1
            if traced:
                cview.stamp(cslot, SEG_PACK, int(t1 * 1e9), int(t2 * 1e9))
                cview.stamp(
                    cslot, SEG_ROUTE, int(t2 * 1e9),
                    int((t2 + route_s) * 1e9),
                )
            arch = _extract_archive_slices(sub, every)
            rec = parsed_record(sub) if disk else None
            # vocab journal since the last report (id order)
            svc_new = vocab.services._names[sent_svc:]
            name_new = vocab.span_names._names[sent_name:]
            pairs_new = vocab._key_list[sent_pair:]
            sent_svc += len(svc_new)
            sent_name += len(name_new)
            sent_pair += len(pairs_new)
            n_spans = int(cols.valid.sum())
            n_dur = int((cols.valid & cols.has_dur).sum())
            n_err = int((cols.valid & cols.err).sum())
            live_ts = cols.ts_min[cols.valid]
            ts_range = (
                (int(live_ts.min()), int(live_ts.max()))
                if live_ts.size
                else (0, 0)
            )
            # -1 marks a continuation chunk: the dispatcher completes a
            # payload (applies its buffered chunks, decrements inflight)
            # on the LAST chunk only, so drain() can never return while
            # later chunks are still queued or being packed (ADVICE r3).
            # The sampled-drop count rides the completion chunk.
            is_last = hi == n
            if is_last:
                state["completed"] = True
            aux = pack_aux(svc_new, name_new, pairs_new, arch, rec)
            if fused.size <= prod.img_cap_u32 and len(aux) <= prod.aux_cap:
                ta = time.perf_counter()
                prod.claim()
                tb = time.perf_counter()
                if traced:
                    cview.stamp(
                        cslot, SEG_RING_WAIT, int(ta * 1e9), int(tb * 1e9)
                    )
                prod.image(fused.size)[:] = fused.reshape(-1)
                # the wseq is allocated at the last infallible instant
                # before emission on BOTH channels, so a worker that
                # survives an exception can never leave a sequence gap
                # that would stall the dispatcher's in-order pump
                prod.publish(
                    pidx=pid, wseq=prod.next_wseq(),
                    per=int(fused.shape[-1]),
                    n_spans=n_spans, n_dur=n_dur, n_err=n_err,
                    dropped=dropped if is_last else -1,
                    cslot=cslot if traced else -1,
                    ts_min=ts_range[0], ts_max=ts_range[1],
                    parse_ns=int(parse_s * 1e9),
                    pack_ns=int(pack_s * 1e9),
                    route_ns=int(route_s * 1e9),
                    tenant=tidx,
                    aux=aux,
                )
                # a ring publish carries no wakeup of its own: nudge
                # the dispatcher so a backed-off idle poll (up to
                # 50 ms) doesn't sit out its full interval while a
                # ready slot waits
                result_q.put((_KIND_NUDGE,))
            else:
                # sidecar outgrew the bounded slot (huge disk-archive
                # record): ship the whole chunk through the queue — the
                # wseq keeps it ordered against the ring chunks
                result_q.put(
                    (_KIND_BATCH, widx, pid, prod.next_wseq(), fused,
                     n_spans, n_dur, n_err,
                     dropped if is_last else -1,
                     svc_new, name_new, pairs_new, arch, ts_range, rec,
                     parse_s, pack_s, route_s)
                )
            parse_s = 0.0  # only bill the parse once per payload

    try:
        while True:
            item = work_q.get()
            if item is None:
                break
            pid, payload, cslot, tidx = item
            state: dict = {"completed": False}
            try:
                handle(pid, payload, state, cslot, tidx)
            except Exception:  # pragma: no cover - keep the pool alive
                logging.getLogger(__name__).exception(
                    "mp-ingest worker %d failed on a payload", widx
                )
                if not state["completed"]:
                    # the dispatcher buffers chunk application until the
                    # completion marker, so any chunks this payload DID
                    # ship were never applied: a whole-payload fallback
                    # retry cannot double-ingest, and nothing is lost
                    result_q.put(
                        (_KIND_FALLBACK, widx, pid, prod.next_wseq())
                    )
    finally:
        result_q.put((_KIND_EOF, widx))
        if cview is not None:
            cview.close()
        prod.close()


class _IdMaps:
    """Worker-local -> global id tables, grown as journals arrive."""

    def __init__(self) -> None:
        self.svc = np.zeros(1, np.uint32)  # local id 0 -> global 0
        self.name = np.zeros(1, np.uint32)
        self.key = np.zeros(1, np.uint32)

    @staticmethod
    def _append(arr: np.ndarray, values: List[int]) -> np.ndarray:
        return np.concatenate([arr, np.asarray(values, np.uint32)]) if values else arr


class MultiProcessIngester:
    """Owns the worker pool + the span ring + the dispatcher thread.

    ``submit(payload)`` enqueues raw JSON v2 / proto3 bytes onto one
    live worker and returns once the payload is accepted.
    ``submit(payload, block=False)`` — the server boundary's mode —
    raises :class:`IngestBackpressure` instead of blocking when every
    live worker is saturated (ring stripe or delivery queue full).
    ``drain()`` blocks until everything submitted has reached the
    device. ``coalesce_max`` bounds how many ready chunks one flush may
    merge into a single device step + WAL record; the default of 1
    keeps per-chunk dispatch — and the WAL byte stream — identical to
    the pre-ring path. Parity with ``TpuStorage.ingest_json_fast`` —
    same sketches, same sampling verdicts, same WAL contents — is
    asserted in tests/test_mp_ingest.py and tests/test_fanout_parity.py.
    """

    def __init__(
        self,
        store,
        workers: int = 2,
        slots_per_worker: int = 2,
        sampler=None,
        queue_depth: Optional[int] = None,
        metrics=None,
        critpath_slots: int = 0,
        critpath_reclaim_s: float = 60.0,
        ring_slots: int = 0,
        coalesce_max: int = 1,
        ring_aux_bytes: int = 1 << 20,
    ) -> None:
        from zipkin_tpu import native
        from zipkin_tpu.tpu.columnar import WIRE_ROWS

        if not native.available():
            raise RuntimeError("native codec unavailable; MP tier needs it")
        self.store = store
        self.workers = workers
        self.queue_depth = queue_depth or 2  # PER-WORKER payload bound
        self.coalesce_max = max(1, int(coalesce_max))
        self._sampler = sampler
        agg = store.agg
        self._n_shards = agg.n_shards
        self._wire_rows = WIRE_ROWS
        # worst case: every span of a max_batch chunk routes to one
        # shard, and route_fused rounds the per-shard lane count up to
        # its 256 pad multiple — ring slots must cover the ROUNDED bound
        # or a near-full chunk would spill past its image region
        per_cap = ((store.max_batch + 255) // 256) * 256
        img_cap_u32 = agg.n_shards * WIRE_ROWS * per_cap
        stripe = int(ring_slots) if ring_slots else max(
            4, 2 * slots_per_worker
        )
        self._ring = ring_mod.SpanRing(
            workers, stripe, img_cap_u32, aux_cap=int(ring_aux_bytes)
        )
        ctx = mp.get_context("spawn")
        # one bounded delivery queue per worker: payload handoff + the
        # second backpressure surface (a frozen worker's stripe stays
        # empty, so ring occupancy alone would never push back on it)
        self._work_qs = [
            ctx.Queue(maxsize=self.queue_depth) for _ in range(workers)
        ]
        self._result_q = ctx.Queue()
        has_disk = getattr(store, "_disk", None) is not None
        params = dict(
            max_services=store.vocab.services.capacity,
            max_keys=store.vocab.max_keys,
            n_shards=agg.n_shards,
            max_batch=store.max_batch,
            pad=store._pad,
            # workers build per-chunk raw-archive records (payload +
            # index columns, worker-local ids) that the dispatcher
            # remaps and appends — the MP tier and the complete trace
            # store are no longer mutually exclusive (VERDICT r4 order
            # 2). The RAM 1/N sample then only matters for
            # autocompleteTags, exactly like the sync fast path.
            archive_disk=has_disk,
            archive_every=(
                store._fast_archive_every
                if (not has_disk or store.autocomplete_keys)
                else 0
            ),
            sample_boundary=(
                sampler._boundary
                if sampler is not None and sampler.rate < 1.0
                else None
            ),
        )
        # critical-path interval ledger (obs/critpath.py): created before
        # the pool spawns so workers attach by name. The stitcher is
        # exposed as .critpath; the server registers it on the windows
        # ticker and the statusz/bench report reads its waterfall.
        self._cp_ledger = None
        self.critpath = None
        self._cslots: Dict[int, int] = {}
        if critpath_slots > 0:
            self._cp_ledger = _critpath.CritPathLedger(
                workers, critpath_slots
            )
            self.critpath = _critpath.CritPathStitcher(
                self._cp_ledger,
                queue_capacity=workers * self.queue_depth,
                recorder=obs.RECORDER,
                reclaim_age_s=critpath_reclaim_s,
            )
            params["critpath"] = self._cp_ledger.params()
        self._procs = [
            ctx.Process(
                target=_worker_main,
                args=(
                    w, self._work_qs[w], self._result_q,
                    self._ring.params(), params,
                ),
                daemon=True,
            )
            for w in range(workers)
        ]
        for p in self._procs:
            p.start()
        self.metrics = metrics  # CollectorMetrics-shaped, optional
        # accuracy-observatory tap (obs/shadow.py): when attached, every
        # applied chunk's fused image is offered (ring-slot views are
        # copied first — the tap may retain its argument past the slot's
        # reuse)
        self.shadow = None
        # tenant attribution (ISSUE 18): a bounded intern table maps the
        # boundary's tenant string to a small idx that rides the queue
        # item, the ring slot header, and the critpath ledger. Overflow
        # collapses onto idx 0 (the default tenant) — a hostile stream
        # of unique tenant ids cannot grow this table unboundedly.
        # tenant_sink (optional; called on the DISPATCHER thread at ack
        # time, must be thread-safe) receives (tenant, n_spans) so the
        # admission table can account retained-spans/sec budgets.
        self._tenant_names: List[str] = ["default"]
        self._tenant_ids: Dict[str, int] = {"default": 0}
        self._tenant_max = 256
        self._tenant_of: Dict[int, int] = {}
        self._tenant_acked: Dict[str, Dict[str, int]] = {}
        self.tenant_sink = None
        self.counters = {
            "accepted": 0, "sampleDropped": 0, "fallbacks": 0, "rejected": 0,
            "coalescedBatches": 0, "coalescedChunks": 0,
            "ringDiscarded": 0, "ringTorn": 0,
        }
        # per-worker attribution (chunks carry widx): a slow worker is
        # distinguishable from a slow pool. Mutated only on the
        # dispatcher thread; read lock-free by stats().
        self._wstats = [
            {"chunks": 0, "spans": 0, "payloads": 0, "parseUs": 0,
             "packUs": 0, "routeUs": 0, "fallbacks": 0}
            for _ in range(workers)
        ]
        # live per-worker occupancy (submitted minus finished) and its
        # high-water mark — the between-ticks saturation signal the
        # cumulative tallies above cannot show. Mutated under _cv.
        self._qdepth = [0] * workers
        self._qhigh = [0] * workers
        self._ring_high = 0
        self._inflight = 0
        self._cv = threading.Condition()
        self._closed = False
        self._dispatch_error: Optional[BaseException] = None
        # payload retention until APPLIED (zero-loss worker death):
        # _pending maps payload id -> raw bytes, _assigned -> the worker
        # that owns it, _buffered -> its not-yet-applied chunk results.
        # _pending/_assigned are mutated by submit() (under _cv) and by
        # the dispatcher thread; _buffered only by the dispatcher.
        self._next_pid = 0
        self._rr = 0
        self._pending: Dict[int, bytes] = {}
        self._assigned: Dict[int, int] = {}
        self._buffered: Dict[int, list] = {}
        self._dead: Set[int] = set()
        self._maps: List[Optional[_IdMaps]] = [
            _IdMaps() for _ in range(workers)
        ]
        # cross-channel in-order pump state (dispatcher thread only):
        # the next wseq to apply per worker, plus queue messages that
        # arrived ahead of their turn
        self._expected = [0] * workers
        self._holdback: List[Dict[int, tuple]] = [
            {} for _ in range(workers)
        ]
        self._pending_eof: Set[int] = set()
        self._reap_later: List[int] = []
        # reap reentrancy guard: _reap_dead_workers drains result_q and
        # pumps, which can discover ANOTHER premature EOF — a recursive
        # reap would abort the outer one before its salvage ran
        # (ADVICE r4). Extra dead workers found mid-reap are collected
        # here and folded into the current reap instead.
        self._reaping = False
        self._reap_extra: List[int] = []
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="mp-ingest-dispatch", daemon=True
        )
        self._dispatcher.start()

    # -- producer side ---------------------------------------------------

    def _tenant_idx(self, tenant: Optional[str]) -> int:
        """Intern a boundary tenant id into the bounded idx table;
        unknown tenants past the cap collapse onto the default idx 0."""
        if not tenant or tenant == "default":
            return 0
        idx = self._tenant_ids.get(tenant)
        if idx is not None:
            return idx
        with self._cv:
            idx = self._tenant_ids.get(tenant)
            if idx is not None:
                return idx
            if len(self._tenant_names) >= self._tenant_max:
                return 0
            idx = len(self._tenant_names)
            self._tenant_names.append(tenant)
            self._tenant_ids[tenant] = idx
            return idx

    def submit(self, payload: bytes, *, block: bool = True,
               tenant: Optional[str] = None) -> None:
        """Enqueue a payload onto one live unsaturated worker.

        Registration happens BEFORE the queue put (under _cv, the same
        lock the reaper takes to mark workers dead), so a worker-death
        reap is linearized against submission: either the reap sees the
        registration and refeeds the payload, or submit() sees the
        worker marked dead and picks another. A worker whose ring
        stripe is full is skipped exactly like one whose queue is full
        — ring occupancy is the tier's backpressure basis. ``tenant``
        (the boundary-extracted id) rides the queue item, the ring slot
        header, and the critpath ledger so ack-time accounting stays
        tenant-attributed end to end.
        """
        tidx = self._tenant_idx(tenant)
        while True:
            if self._closed:
                raise RuntimeError("ingester closed")
            if self._dispatch_error is not None:
                raise RuntimeError(
                    "dispatcher died"
                ) from self._dispatch_error
            with self._cv:
                live = [
                    w for w in range(self.workers) if w not in self._dead
                ]
                if not live:
                    raise RuntimeError(
                        "mp-ingest worker pool exhausted (every worker "
                        "died); restart the ingester"
                    )
                start = self._rr % len(live)
                self._rr += 1
                pid = self._next_pid
                self._next_pid += 1
                self._pending[pid] = payload
                if tidx:
                    self._tenant_of[pid] = tidx
                self._inflight += 1
            wire_ns = (
                _critpath.WIRE_T0_NS.get()
                if self._cp_ledger is not None
                else 0
            )
            for relax in (False, True):
                for w in live[start:] + live[:start]:
                    with self._cv:
                        if w in self._dead:
                            continue
                        self._assigned[pid] = w
                    if not relax and self._ring.stripe_full(w):
                        # the dispatcher is behind on this stripe:
                        # first round prefers a worker with drain
                        # headroom. Ring congestion alone must NOT
                        # reject — the worker's blocking claim()
                        # propagates the ring bound back through its
                        # delivery queue — so a second round relaxes
                        # the check and only full queues remain
                        with self._cv:
                            if pid not in self._pending:
                                return  # a racing reap already refed it
                            if self._assigned.get(pid) == w:
                                self._assigned.pop(pid)
                        continue
                    cslot = -1
                    if wire_ns:
                        t_en0 = time.perf_counter_ns()
                        cslot = self._cp_ledger.alloc(
                            pid, w, wire_ns, tenant=tidx
                        )
                        if cslot >= 0:
                            # stamp + register BEFORE the queue put: the
                            # dispatcher only writes this slot after the
                            # worker's chunk arrives, so main-side
                            # region writers stay causally serialized
                            self._cp_ledger.stamp(
                                cslot, _critpath.SEG_ENQUEUE, t_en0,
                                time.perf_counter_ns(), pid,
                            )
                            with self._cv:
                                self._cslots[pid] = cslot
                    try:
                        self._work_qs[w].put_nowait(
                            (pid, payload, cslot, tidx)
                        )
                        with self._cv:
                            self._qdepth[w] += 1
                            if self._qdepth[w] > self._qhigh[w]:
                                self._qhigh[w] = self._qdepth[w]
                        return
                    except queue.Full:
                        if cslot >= 0:
                            with self._cv:
                                self._cslots.pop(pid, None)
                            self._cp_ledger.abandon(cslot)
                        with self._cv:
                            if pid not in self._pending:
                                return  # a racing reap already refed it
                            if self._assigned.get(pid) == w:
                                self._assigned.pop(pid)
            # every live worker is saturated: roll the registration back
            with self._cv:
                if pid not in self._pending:
                    return  # a racing reap consumed it
                self._pending.pop(pid)
                self._assigned.pop(pid, None)
                self._tenant_of.pop(pid, None)
                self._inflight -= 1
                if self._inflight == 0:
                    self._cv.notify_all()
            if not block:
                self.counters["rejected"] += 1
                raise IngestBackpressure(
                    f"ingest fan-out saturated: every live worker's "
                    f"delivery queue is full behind its ring stripe "
                    f"({len(live)} workers x queue depth "
                    f"{self.queue_depth}, {self._ring.stripe_slots} "
                    f"ring slots each); retry after backoff"
                )
            time.sleep(0.002)

    def drain(self) -> None:
        """Block until every submitted payload has reached the device."""
        with self._cv:
            self._cv.wait_for(
                lambda: self._inflight == 0 or self._dispatch_error is not None
            )
        if self._dispatch_error is not None:
            raise RuntimeError("dispatcher died") from self._dispatch_error
        # zt-lint: disable=ZT06 — drain's contract IS the blocking sync:
        # "until every payload has reached the device" means retire the
        # device queue, not just the dispatch threads
        self.store.agg.block_until_ready()

    def stats(self) -> dict:
        """Fan-out tier gauges, merged into TpuStorage.ingest_counters()
        so /metrics and /statusz show the tier."""
        with self._cv:
            inflight = self._inflight
            dead = len(self._dead)
            qdepth = list(self._qdepth)
            qhigh = list(self._qhigh)
        out = {
            "mpWorkers": self.workers,
            "mpWorkersAlive": self.workers - dead,
            "mpQueueDepth": self.queue_depth,
            "mpInflight": inflight,
            "mpAccepted": self.counters["accepted"],
            "mpSampleDropped": self.counters["sampleDropped"],
            "mpFallbacks": self.counters["fallbacks"],
            "mpRejected": self.counters["rejected"],
            "mpRingSlots": self._ring.capacity,
            "mpRingOccupancy": self._ring.occupancy(),
            "mpRingHighWater": self._ring_high,
            "mpCoalesceMax": self.coalesce_max,
            "mpCoalescedBatches": self.counters["coalescedBatches"],
            "mpCoalescedChunks": self.counters["coalescedChunks"],
            "mpRingDiscarded": self.counters["ringDiscarded"],
            "mpRingTorn": self.counters["ringTorn"],
            # nested per-worker table — scalar-only consumers
            # (/prometheus gauge emission) skip non-scalar values
            "mpWorkerTable": [
                {"widx": w, "alive": w not in self._dead,
                 "queueDepth": qdepth[w], "queueHighWater": qhigh[w],
                 "ringDepth": self._ring.stripe_depth(w),
                 **dict(ws)}
                for w, ws in enumerate(self._wstats)
            ],
            # per-tenant acked attribution (ISSUE 18) — nested like the
            # worker table; bounded by the tenant intern cap
            "mpTenantTable": {
                name: dict(row)
                for name, row in self._tenant_acked.items()
            },
        }
        if self.critpath is not None:
            out.update(self.critpath.counters())
        return out

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        for w, p in enumerate(self._procs):
            if w in self._dead:
                continue  # no consumer; nothing to shut down
            # per-worker bounded queue: a live worker keeps consuming,
            # so a timed put retried until it lands cannot hang; a
            # worker that died mid-shutdown just stops needing one
            while True:
                try:
                    self._work_qs[w].put(None, timeout=0.5)
                    break
                except queue.Full:
                    if not p.is_alive():
                        break
        for p in self._procs:
            p.join(timeout=30)
            if p.is_alive():  # pragma: no cover - hang safety
                p.terminate()
        self._dispatcher.join(timeout=30)
        for q in self._work_qs:
            # a dead worker's queue may still hold (already-salvaged)
            # payloads; don't let its feeder thread block interpreter
            # exit flushing them to a pipe nobody reads
            q.close()
            q.cancel_join_thread()
        if self._dispatch_error is not None:
            # the stored exception's traceback pins frames whose locals
            # can include ndarray VIEWS into ring slots — shm close()
            # would refuse ("exported pointers exist"). The dispatcher
            # thread is joined, so the frames are safe to clear;
            # drain()'s re-raise keeps the message.
            import traceback

            tb = self._dispatch_error.__traceback__
            if tb is not None:
                traceback.clear_frames(tb)
        self._buffered.clear()
        self._ring.close()
        if self._cp_ledger is not None:
            self._cp_ledger.close()

    # -- dispatcher ------------------------------------------------------

    def _dispatch_loop(self) -> None:
        try:
            self._run_dispatch()
        except BaseException as e:
            logger.exception("mp-ingest dispatcher failed")
            self._dispatch_error = e
            with self._cv:
                self._cv.notify_all()
            self._sink_until_closed()

    def _sink_until_closed(self) -> None:
        """After a dispatcher failure, keep draining result_q and
        freeing ring slots so SURVIVING workers never wedge in
        ``claim()`` with the only consumer (the normal dispatch loop)
        gone — otherwise close() would burn its full join timeout per
        live worker and terminate() it mid-payload. Results are
        discarded: the error is already surfaced to submit()/drain(),
        so callers know batches after the failure point are lost."""
        while True:
            for w in range(self.workers):
                while self._ring.stripe_depth(w) > 0:
                    self._ring.free_next(w)
            try:
                self._result_q.get(timeout=0.25)
            except queue.Empty:
                if self._closed and not any(p.is_alive() for p in self._procs):
                    return

    def _run_dispatch(self) -> None:
        eof_set: set = set()
        last_liveness = time.monotonic()
        idle_wait = 0.0005
        while len(eof_set) < self.workers:
            if self._pass(eof_set):
                idle_wait = 0.0005
            else:
                # nothing ready anywhere: block on the control queue —
                # ring publishes wake it via a nudge message, and the
                # timeout doubles as a poll backstop, backing off while
                # idle (a nudge can race the pass that already consumed
                # its slot, so the poll still matters)
                try:
                    msg = self._result_q.get(timeout=idle_wait)
                except queue.Empty:
                    if self._closed and not any(
                        p.is_alive() for p in self._procs
                    ):
                        self._pass(eof_set)  # final sweep
                        break
                    idle_wait = min(idle_wait * 2, 0.05)
                else:
                    self._route_msg(msg, eof_set)
                    idle_wait = 0.0005
            # liveness must ALSO run under sustained traffic: a busy
            # surviving worker keeps the ring non-empty, so the idle
            # branch alone could leave a dead worker's acked payloads
            # pinning _inflight for as long as load lasts
            if (
                not self._closed
                and time.monotonic() - last_liveness > 2.0
            ):
                self._check_liveness(eof_set)
                last_liveness = time.monotonic()

    def _pass(self, eof_set: set) -> bool:  # zt-dispatch-critical: one drain pass — consume ready slots, flush completed payloads coalesced, free slots
        """One dispatcher pass: drain the control queue, pump every live
        stripe's contiguous run of ready slots in wseq order, flush the
        payloads that completed (coalesced), materialize any view still
        buffered for an incomplete payload, then free the consumed slots
        — so no slot is ever held across passes and a multi-chunk
        payload cannot starve its own worker of ring capacity."""
        activity = False
        # zt-lint: disable=ZT09 — per queued control MESSAGE (chunk- or
        # payload-granular), never per span
        while True:
            try:
                msg = self._result_q.get_nowait()
            except queue.Empty:
                break
            self._route_msg(msg, eof_set)
            activity = True
        ready: List[tuple] = []
        consumed: Dict[int, int] = {}
        self._pump(ready, consumed)
        occ = self._ring.occupancy()  # zt-lint: disable=ZT09 — O(n_workers) stripe-depth word reads
        if occ > self._ring_high:
            self._ring_high = occ
        if ready:
            self._flush_ready(ready)
        if consumed:
            self._materialize_views()  # zt-lint: disable=ZT09 — per straddling-payload CHUNK copy, bounded by stripe depth × workers, not span count
            # zt-lint: disable=ZT09 — per worker STRIPE with consumed slots
            for w, cnt in consumed.items():
                for _ in range(cnt):  # zt-lint: disable=ZT09 — per consumed SLOT (chunk-sized), a word store + counter bump each
                    self._ring.free_next(w)
            activity = True
        if self._reap_later and not self._reaping:
            # zt-lint: disable=ZT09 — per deferred-reap WORKER
            dead = [w for w in self._reap_later if w not in eof_set]
            self._reap_later = []
            if dead:
                self._reap_dead_workers(dead, eof_set)  # zt-lint: disable=ZT09 — rare worker-death recovery path, trips per dead worker / inflight payload, not steady-state dispatch
                activity = True
        # zt-lint: disable=ZT09 — per EOF-pending WORKER, two integer reads
        for w in list(self._pending_eof):
            if (
                self._ring.stripe_depth(w) == 0
                and not self._holdback[w]
            ):
                self._pending_eof.discard(w)
                eof_set.add(w)
                activity = True
        return activity or bool(ready)

    def _route_msg(self, msg, eof_set: set) -> None:
        """Sort one control-queue message: EOFs resolve now (clean) or
        mark the worker for reaping (premature); chunk/fallback messages
        park in the per-worker holdback until their wseq turn."""
        kind = msg[0]
        if kind == _KIND_NUDGE:
            return  # wakeup only — the pump reads the ring directly
        if kind == _KIND_EOF:
            widx = msg[1]
            if self._closed or widx in self._dead:
                # clean shutdown: finalized once the stripe drains
                self._pending_eof.add(widx)
                if widx in self._dead:
                    self._pending_eof.discard(widx)
                    eof_set.add(widx)
            elif self._reaping:
                self._reap_extra.append(widx)
            else:
                # workers only EOF after close()'s None sentinel; an EOF
                # before close() means the worker loop was torn down by
                # a BaseException with its inflight payloads unaccounted
                # — treat it exactly like an unclean death and refeed
                # (deferred to the pass tail so payloads already
                # completed in this pass flush before the reap scan)
                self._reap_later.append(widx)
            return
        widx, wseq = msg[1], msg[3]
        if widx in self._dead:
            return
        self._holdback[widx][wseq] = msg

    def _pump(self, ready: List[tuple], consumed: Dict[int, int]) -> None:  # zt-dispatch-critical: in-order merge of ring slots + queue stragglers per worker
        """Apply every worker's available chunks strictly in wseq order,
        merging the ring stripe with held-back queue messages. Stops per
        worker at the first missing sequence (still in flight on the
        other channel)."""
        # zt-lint: disable=ZT09 — per WORKER stripe
        for w in range(self.workers):
            if w in self._dead:
                continue
            budget = self._ring.stripe_slots + len(self._holdback[w]) + 1
            while budget > 0:  # zt-lint: disable=ZT09 — bounded by stripe depth + holdback, each iteration applies one chunk
                budget -= 1
                exp = self._expected[w]
                hb = self._holdback[w].pop(exp, None)
                if hb is not None:
                    self._apply_queue_msg(hb, ready)
                    self._expected[w] = exp + 1
                    continue
                peeked = self._ring.peek(w, consumed.get(w, 0))
                if peeked is None:
                    break
                hdr, seq = peeked
                if int(hdr[ring_mod._S_WSEQ]) != exp:
                    break  # the missing wseq is in flight on the queue
                self._consume_ring_chunk(w, hdr, seq, ready)
                consumed[w] = consumed.get(w, 0) + 1
                self._expected[w] = exp + 1

    def _consume_ring_chunk(
        self, w: int, hdr: np.ndarray, seq: int, ready: List[tuple]
    ) -> None:  # zt-dispatch-critical: zero-copy slot consume — header decode + vocab replay, no image copy
        t0 = time.perf_counter()
        pid = int(hdr[ring_mod._S_PIDX])
        if pid not in self._pending:
            # late chunk of a payload a reap already refed: discard (the
            # slot is still counted consumed and freed by the pass)
            self.counters["ringDiscarded"] += 1
            return
        # tenant idx rides the slot header cross-process; the submit
        # side already recorded it, but the ring word is authoritative
        # for chunks (it survives even when attribution maps are cold)
        tidx = int(hdr[ring_mod._S_TENANT])
        if tidx and pid not in self._tenant_of:
            # zt-lint: disable=ZT04 — single-writer-per-pid: submit()
            # records the mapping under _cv BEFORE the worker can publish
            # a chunk; this dispatcher-thread write only fills pids whose
            # submit-side record was skipped (tidx==0 fast path), and no
            # other thread touches that pid's key
            self._tenant_of[pid] = tidx
        per = int(hdr[ring_mod._S_PER])
        fused = self._ring.image(
            w, seq, self._n_shards * self._wire_rows * per
        ).reshape(self._n_shards, self._wire_rows, per)
        aux_len = int(hdr[ring_mod._S_AUX_LEN])
        svc_new, name_new, pairs_new, arch, rec = ring_mod.unpack_aux(
            self._ring.aux(w, seq, aux_len)
        )
        self._apply_chunk(
            w, pid, fused,
            int(hdr[ring_mod._S_NSPANS]), int(hdr[ring_mod._S_NDUR]),
            int(hdr[ring_mod._S_NERR]), int(hdr[ring_mod._S_DROPPED]),
            svc_new, name_new, pairs_new, arch,
            (int(hdr[ring_mod._S_TS_MIN]), int(hdr[ring_mod._S_TS_MAX])),
            rec,
            int(hdr[ring_mod._S_PARSE_NS]) / 1e9,
            int(hdr[ring_mod._S_PACK_NS]) / 1e9,
            int(hdr[ring_mod._S_ROUTE_NS]) / 1e9,
            True, time.perf_counter() - t0, ready,
        )

    def _apply_queue_msg(self, msg, ready: List[tuple]) -> None:
        kind = msg[0]
        if kind == _KIND_FALLBACK:
            _, widx, pid, _wseq = msg
            payload = self._pending.get(pid)
            if payload is None:
                return  # a reap already refed it
            self._buffered.pop(pid, None)
            self._drop_cslot(pid)  # slow-path retry: timeline abandoned
            self._fallback(payload)
            self.counters["fallbacks"] += 1
            if 0 <= widx < len(self._wstats):
                self._wstats[widx]["fallbacks"] += 1
            self._finish(pid)
            return
        (
            _, widx, pid, _wseq, fused, n_spans, n_dur, n_err, dropped,
            svc_new, name_new, pairs_new, arch, ts_range, rec,
            parse_s, pack_s, route_s,
        ) = msg
        t0 = time.perf_counter()
        if pid not in self._pending:
            return
        self._apply_chunk(
            widx, pid, fused, n_spans, n_dur, n_err, dropped,
            svc_new, name_new, pairs_new, arch, ts_range, rec,
            parse_s, pack_s, route_s,
            False, time.perf_counter() - t0, ready,
        )

    def _apply_chunk(
        self, widx, pid, fused, n_spans, n_dur, n_err, dropped,
        svc_new, name_new, pairs_new, arch, ts_range, rec,
        parse_s, pack_s, route_s, is_view, consume_s, ready,
    ) -> None:  # zt-dispatch-critical: per-chunk apply — vocab journal replay + buffer append on the single dispatch thread
        store = self.store
        vocab = store.vocab
        m = self._maps[widx]
        cs = self._cslots.get(pid, -1) if self._cp_ledger is not None else -1
        if svc_new or name_new or pairs_new:
            tv0 = time.perf_counter()
            with store._intern_lock:
                # zt-lint: disable=ZT09 — journal replay is per NEWLY
                # INTERNED STRING (bounded by vocab capacity, amortized
                # zero per span), not per span
                m.svc = _IdMaps._append(
                    m.svc, [vocab.services.intern(s) for s in svc_new]
                )
                # zt-lint: disable=ZT09 — per new string, as above
                m.name = _IdMaps._append(
                    m.name, [vocab.span_names.intern(s) for s in name_new]
                )
                # zt-lint: disable=ZT09 — per new (svc, name) pair
                m.key = _IdMaps._append(
                    m.key,
                    [
                        vocab.key_id(int(m.svc[sl]), int(m.name[nl]))
                        for sl, nl in pairs_new
                    ],
                )
            tv1 = time.perf_counter()
            obs.record("mp_vocab_replay", tv1 - tv0)
            if cs >= 0:
                self._cp_ledger.stamp(
                    cs, _critpath.SEG_VOCAB_REPLAY,
                    int(tv0 * 1e9), int(tv1 * 1e9), pid,
                )
        # worker-measured stage wall time: the workers can't touch the
        # in-process flight recorder, so their parse/pack/route timings
        # ride the chunk and are recorded here. record_relayed
        # (histogram-only): the time was spent in a worker process, so a
        # budget crossing must not emit a self-span B3-linked to
        # whatever request context this dispatcher thread holds.
        if parse_s > 0.0:
            obs.record_relayed("parse", parse_s)
        if pack_s > 0.0:
            obs.record_relayed("pack", pack_s)
        if route_s > 0.0:
            obs.record_relayed("route", route_s)
        ws = self._wstats[widx]
        ws["chunks"] += 1
        ws["spans"] += n_spans
        ws["parseUs"] += int(parse_s * 1e6 + 0.5)
        ws["packUs"] += int(pack_s * 1e6 + 0.5)
        ws["routeUs"] += int(route_s * 1e6 + 0.5)
        if dropped >= 0:
            ws["payloads"] += 1
        if fused is not None:
            if rec is not None:
                # remap the record's svc/rsvc/name/key lanes local ->
                # global NOW (the journal above covers every id this
                # chunk references; the maps may have grown by apply
                # time); append is deferred to the completion flush
                rec = list(rec)
                rec[7] = m.svc[rec[7]]
                rec[8] = m.svc[rec[8]]
                rec[9] = m.name[rec[9]]
                rec[10] = m.key[rec[10]]
                rec = tuple(rec)
            self._buffered.setdefault(pid, []).append(
                [fused, n_spans, n_dur, n_err, ts_range, arch, rec,
                 consume_s, is_view, widx]
            )
        # dropped == -1 marks a continuation chunk; the payload is
        # applied atomically once its LAST chunk has been consumed
        if dropped >= 0:
            ready.append((pid, dropped))

    def _materialize_views(self) -> None:
        """Chunks still buffered for an INCOMPLETE payload at pass end
        get copied out of their ring slots (the pre-ring per-chunk copy,
        now paid only by payloads that straddle a pass) so every
        consumed slot can be freed — a payload can never pin its
        worker's stripe while waiting for its own later chunks."""
        for pid, entries in self._buffered.items():
            for e in entries:
                if not e[8]:
                    continue
                t0 = time.perf_counter()
                e[0] = np.array(e[0])
                e[8] = False
                tc1 = time.perf_counter()
                obs.record("mp_shm_copy", tc1 - t0)
                cs = (
                    self._cslots.get(pid, -1)
                    if self._cp_ledger is not None else -1
                )
                if cs >= 0:
                    self._cp_ledger.stamp(
                        cs, _critpath.SEG_SHM_COPY,
                        int(t0 * 1e9), int(tc1 * 1e9), pid,
                    )

    # -- coalesced flush --------------------------------------------------

    def _flush_ready(self, ready: List[tuple]) -> None:  # zt-dispatch-critical: applies completed payloads to the device + durability path, coalesced
        """Flush the payloads completed this pass: their buffered chunks
        are packed into groups of up to ``coalesce_max`` chunks (bounded
        by the aggregator's lane cap) and each group takes ONE
        ``ingest_fused_multi`` — whose dispatch side carries the WAL
        append and sampling verdicts, preserving ack-after-durability
        exactly like the serial path. Until this runs, a payload has
        mutated nothing, which is what makes worker death recoverable.
        A payload's chunks may split across groups (the same
        at-least-once boundary the per-chunk path always had); its ack
        fires only after the group holding its last chunk — and, when
        several groups share one vectored WAL commit, after that commit.
        """
        store = self.store
        plans: Dict[int, dict] = {}
        flat: List[tuple] = []
        # zt-lint: disable=ZT09 — per completed PAYLOAD
        for pid, dropped in ready:
            entries = self._buffered.pop(pid, [])
            # zt-lint: disable=ZT09 — per buffered CHUNK of one payload
            plans[pid] = {
                "dropped": dropped,
                "left": len(entries),
                "spans": sum(e[1] for e in entries),
                "consume_s": sum(e[7] for e in entries),
            }
            # zt-lint: disable=ZT09 — per buffered CHUNK, a list append
            for e in entries:
                flat.append((e, pid))
        cap = store.agg.lane_cap
        groups: List[List[tuple]] = []
        cur: List[tuple] = []
        lanes = 0
        for e, pid in flat:  # zt-lint: disable=ZT09 — per chunk: greedy group packing, integer bookkeeping only
            per = int(e[0].shape[-1])
            if cur and (
                len(cur) >= self.coalesce_max or lanes + per > cap
            ):
                groups.append(cur)
                cur, lanes = [], 0
            cur.append((e, pid))
            lanes += per
        if cur:
            groups.append(cur)
        wal = getattr(store, "wal", None)
        if wal is not None and len(groups) > 1:
            # one vectored WAL commit for the whole pass: per-record
            # flush/fsync deferred, every group's ack deferred past the
            # commit so ack-after-durability still holds
            done: List[int] = []
            with wal.batched():
                for g in groups:  # zt-lint: disable=ZT09 — per coalesced GROUP (one device step each)
                    done.extend(self._flush_group(g, plans))
            self._ack_done(done, plans)
        else:
            for g in groups:  # zt-lint: disable=ZT09 — per coalesced GROUP (one device step each)
                self._ack_done(self._flush_group(g, plans), plans)
        # payloads with no device chunks at all (every span boundary-
        # sampled away, or an empty payload): nothing to group, ack now
        # zt-lint: disable=ZT09 — per completed PAYLOAD, dict reads only
        empty = [
            pid for pid, p in plans.items()
            if p["left"] == 0 and not p.get("acked")
        ]
        if empty:
            self._ack_done(empty, plans)

    def _flush_group(self, group: List[tuple], plans: Dict[int, dict]) -> List[int]:  # zt-dispatch-critical: one coalesced group -> one remap+step+WAL record
        store = self.store
        led = self._cp_ledger
        t_g0 = time.perf_counter()
        pairs = []
        if led is not None:
            seen: Set[int] = set()
            for _, pid in group:  # zt-lint: disable=ZT09 — per group member, set lookups only
                if pid not in seen:
                    seen.add(pid)
                    pairs.append((self._cslots.get(pid, -1), pid))
            # zt-lint: disable=ZT09 — per traced group MEMBER
            traced = [(s, p) for s, p in pairs if s >= 0]
            if len(traced) == 1:
                # arm the thread-local so wal.py's append/fsync stamps
                # land in this payload's timeline (WAL rides the step)
                _critpath.set_active(led, traced[0][0], traced[0][1])
            elif traced:
                _critpath.set_active_group(led, traced)
        n_spans = n_dur = n_err = 0
        lo = hi = None
        parts = []
        for e, pid in group:  # zt-lint: disable=ZT09 — per CHUNK (max_batch-sized); all per-span work inside is vectorized
            fused, c_spans, c_dur, c_err, ts_range, arch, rec, _c, is_view, widx = e
            if arch:
                self._archive(arch)  # zt-lint: disable=ZT09 — per archive SLICE = the 1-in-N sampled raw spans; decode/gate IS the retention surface, bounded by the sampling rate
            if rec is not None and getattr(store, "_disk", None) is not None:
                # sampling gate: the fused sketch feed below always sees
                # 100% of spans; only raw-archive retention is gated.
                # Gating happens here (not in disk_append_record) so the
                # sync fast path is not double-gated, and at flush time
                # so verdicts see the same publish state as the serial
                # path's dispatch-ordered gate.
                sampler = store.agg.sampler
                if sampler is not None:
                    rec = sampler.gate_record(rec)  # zt-lint: disable=ZT09 — vectorized verdict; the per-kept-span byte compaction runs only when spans are gated away, on ONE record
                if rec is not None:
                    store.disk_append_record(rec)
            if self.shadow is not None:
                # the tap may retain its argument: never hand it a live
                # ring-slot view
                self.shadow.offer_fused(
                    np.array(fused) if is_view else fused
                )
            m = self._maps[widx]
            parts.append((fused, m.svc, m.key))
            n_spans += c_spans
            n_dur += c_dur
            n_err += c_err
            if c_spans > 0:
                lo = ts_range[0] if lo is None else min(lo, ts_range[0])
                hi = ts_range[1] if hi is None else max(hi, ts_range[1])
        if len(group) == 1:
            ts = group[0][0][4]  # the chunk's own range, bit-for-bit
        else:
            ts = (lo, hi) if lo is not None else (0, 0)
        tf0 = time.perf_counter()
        # resource-fault injection (faults.py, ISSUE 13/18): an armed
        # feed.latency site sleeps here — the exact seam where a slow
        # device feed stalls the dispatcher — so overload tests can
        # manufacture queue saturation deterministically. The group's
        # tenant is passed explicitly (the dispatcher thread has no
        # request context) so a tenant-scoped fault stalls only that
        # tenant's dispatches.
        g_tidx = self._tenant_of.get(group[0][1], 0) if group else 0
        faults.resource_point(
            "feed.latency",
            tenant=self._tenant_names[g_tidx]
            if 0 <= g_tidx < len(self._tenant_names) else "default",
        )
        store.agg.ingest_fused_multi(
            parts, n_spans=n_spans, n_dur=n_dur, n_err=n_err,
            ts_range=ts, pad_to_multiple=store._pad,
        )
        tf1 = time.perf_counter()
        obs.record("mp_device_feed", tf1 - tf0)
        if led is not None:
            for s, p in pairs:  # zt-lint: disable=ZT09 — per traced group member, 3 word stores each
                if s >= 0:
                    led.stamp(
                        s, _critpath.SEG_DEVICE_FEED,
                        int(tf0 * 1e9), int(tf1 * 1e9), p,
                    )
            _critpath.clear_active()
        if len(group) > 1:
            self.counters["coalescedBatches"] += 1
            self.counters["coalescedChunks"] += len(group)
        # apportion this group's flush wall across its chunks by span
        # weight, so mp_record stays a PER-CHUNK handling time (consume
        # + attributable flush share) like the pre-ring tier's stage —
        # not the whole pass wall billed to every payload in it
        g_wall = time.perf_counter() - t_g0
        # zt-lint: disable=ZT09 — per group MEMBER (bounded by
        # coalesce_max), integer header reads only
        g_spans = sum(e[1] for e, _ in group) or len(group)
        done = []
        for e, pid in group:  # zt-lint: disable=ZT09 — per group member, dict bookkeeping only
            p = plans[pid]
            p["flush_s"] = p.get("flush_s", 0.0) + g_wall * (
                (e[1] or 1) / g_spans
            )
            p["left"] -= 1
            if p["left"] == 0:
                done.append(pid)
        return done

    def _ack_done(self, pids: List[int], plans: Dict[int, dict]) -> None:  # zt-dispatch-critical: post-durability ack fan-in on the dispatch core — O(payloads per pass)
        """Ack payloads whose last chunk is durable: counters, metrics,
        ledger ack, inflight release. Runs after the group flush — and
        after the vectored WAL commit when one covered the pass."""
        for pid in pids:  # zt-lint: disable=ZT09 — per completed PAYLOAD, counter updates only
            p = plans[pid]
            if p.get("acked"):
                continue
            p["acked"] = True
            total = p["spans"]
            dropped = p["dropped"]
            obs.record(
                "mp_record", p["consume_s"] + p.get("flush_s", 0.0)
            )
            self.counters["accepted"] += total
            self.counters["sampleDropped"] += max(dropped, 0)
            if self.metrics is not None:
                self.metrics.increment_spans(total + max(dropped, 0))
                if dropped > 0:
                    self.metrics.increment_spans_dropped(dropped)
            cs = (
                self._cslots.get(pid, -1)
                if self._cp_ledger is not None else -1
            )
            if cs >= 0:
                # durable ack: the WAL append + device feed completed
                self._cp_ledger.ack(cs, pid)
            # per-tenant acked accounting + the retained-spans budget
            # feed (ISSUE 18): span counts are only known post-parse,
            # so retention budgets charge here, at ack time
            tidx = self._tenant_of.get(pid, 0)
            tname = (
                self._tenant_names[tidx]
                if 0 <= tidx < len(self._tenant_names) else "default"
            )
            ta = self._tenant_acked.setdefault(
                tname, {"payloads": 0, "spans": 0}
            )
            ta["payloads"] += 1
            ta["spans"] += total
            sink = self.tenant_sink
            if sink is not None and total:
                try:
                    sink(tname, total)
                except Exception:  # accounting must never kill an ack
                    logger.exception("tenant_sink failed")
            self._finish(pid)

    # -- worker death -----------------------------------------------------

    def _check_liveness(self, eof_set: set) -> None:
        """A worker that died uncleanly (segfault in the native parser,
        OOM kill) never sends EOF: without this check its inflight
        payloads would pin _inflight > 0 and drain()/stop() would wedge
        forever (ADVICE r3)."""
        dead = [
            w
            for w, p in enumerate(self._procs)
            if not p.is_alive() and w not in eof_set
        ]
        if dead:
            self._reap_dead_workers(dead, eof_set)

    def _reap_dead_workers(self, dead: List[int], eof_set: set) -> None:
        """A worker died without EOF. Recover EVERYTHING and keep the
        pool serving on the survivors: because chunk application is
        buffered until a payload's completion marker, a half-processed
        payload has mutated no store state — its buffered chunks are
        discarded, its ring stripe reclaimed (the pid-guarded torn-slot
        reset handles a SIGKILL mid-write), and the whole payload (plus
        everything queued behind it) re-ingests on the slow path. Zero
        acked-span loss, no double-ingest, and the dead worker's
        _IdMaps / inflight accounting are released. Re-entrancy:
        draining below can discover ANOTHER premature EOF — those fold
        into THIS reap via _reap_extra rather than recursing (ADVICE
        r4)."""
        self._reaping = True
        try:
            # mark dead under _cv FIRST: submit() registers under the
            # same lock, so after this no new payload can target these
            # workers, and every already-registered one is visible to
            # the refeed scan below
            with self._cv:
                self._dead.update(dead)
            # timeout-based drains, not get_nowait(): mp.Queue puts go
            # through a feeder thread, so a just-shipped result can be
            # in the pipe but not yet visible — get_nowait() would miss
            # chunks a surviving worker already produced
            while True:
                try:
                    msg = self._result_q.get(timeout=0.25)
                except queue.Empty:
                    break
                self._route_msg(msg, eof_set)
            # apply + FLUSH everything already produced (survivors, and
            # any payload the dead workers fully published before
            # dying): completed payloads leave _pending before the
            # refeed scan, so they cannot double-ingest
            ready: List[tuple] = []
            consumed: Dict[int, int] = {}
            self._pump(ready, consumed)
            if ready:
                self._flush_ready(ready)
            self._materialize_views()
            for w, cnt in consumed.items():
                for _ in range(cnt):
                    self._ring.free_next(w)
            if self._reap_extra:
                with self._cv:
                    self._dead.update(self._reap_extra)
                dead = dead + [w for w in self._reap_extra if w not in dead]
                self._reap_extra = []
            refed = 0
            for w in dead:
                eof_set.add(w)
                self._pending_eof.discard(w)
                self._maps[w] = None  # free the dead worker's id tables
                self._holdback[w].clear()
                rec = self._ring.reclaim_stripe(
                    w, self._procs[w].pid or -1
                )
                self.counters["ringDiscarded"] += rec["discarded"]
                self.counters["ringTorn"] += rec["torn"]
                # empty its queue so the feeder thread can't block
                # shutdown; the payloads themselves re-ingest via the
                # _assigned scan (they are all still in _pending)
                while True:
                    try:
                        item = self._work_qs[w].get(timeout=0.25)
                    except queue.Empty:
                        break
                    del item
                with self._cv:
                    owned = [
                        p for p, a in self._assigned.items() if a == w
                    ]
                for pid in owned:
                    self._buffered.pop(pid, None)
                    payload = self._pending.get(pid)
                    if payload is None:
                        continue
                    # the dead worker's ledger slots would stay OPEN
                    # forever: recycle them now (no stuck timelines)
                    self._drop_cslot(pid)
                    self._fallback(payload)
                    self.counters["fallbacks"] += 1
                    self._finish(pid)
                    refed += 1
        finally:
            self._reaping = False
        logger.warning(
            "mp-ingest worker(s) %s died uncleanly; %d acked payload(s) "
            "re-ingested via the slow path, pool continues on %d "
            "survivor(s)",
            dead, refed, self.workers - len(self._dead),
        )

    # -- shared helpers ----------------------------------------------------

    def _drop_cslot(self, pid: int) -> None:
        """Abandon a payload's timeline (fallback/reap path): partial
        stamps would decompose misleadingly, so the slot recycles now."""
        if self._cp_ledger is None:
            return
        with self._cv:
            cs = self._cslots.pop(pid, -1)
        if cs >= 0:
            self._cp_ledger.abandon(cs)

    def _finish(self, pid: int) -> None:
        with self._cv:
            self._pending.pop(pid, None)
            w = self._assigned.pop(pid, None)
            self._cslots.pop(pid, None)
            self._tenant_of.pop(pid, None)
            if w is not None and self._qdepth[w] > 0:
                self._qdepth[w] -= 1
            self._inflight -= 1
            if self._inflight == 0:
                self._cv.notify_all()

    def _archive(self, slices: List[bytes]) -> None:
        from zipkin_tpu.tpu.store import _decode_raw_span

        spans = []
        for raw in slices:
            try:
                spans.append(_decode_raw_span(raw))
            except Exception:  # slice the strict codec rejects: skip
                continue
        if not spans:
            return
        sampler = self.store.agg.sampler
        if sampler is not None:
            # the RAM-archive sample is a retention surface like the disk
            # archive: gate it with the same verdicts (re-packing the few
            # 1-in-N sampled spans is cheap; interning is idempotent)
            from zipkin_tpu.tpu.columnar import pack_spans

            with self.store._intern_lock:
                cols = pack_spans(spans, self.store.vocab, 1)
            keep = sampler.verdict_cols(cols)[: len(spans)]
            spans = [s for s, k in zip(spans, keep) if k]
        if spans:
            self.store._archive.accept(spans).execute()

    def _fallback(self, payload: bytes) -> None:
        """Payloads the native parser rejects — or that a dead worker
        owned — take the object path, including the boundary sampler, so
        a parser punt cannot smuggle unsampled spans into the store.
        Malformed payloads are counted and dropped (the asynchronous-ack
        trade: like the reference's Kafka collector, a poison message
        can't be HTTP-400'd after the 202 — SURVEY.md §3.3). The codec
        sniffs the wire format, so proto3 payloads fall back too."""
        from zipkin_tpu.model import codec

        try:
            spans = codec.decode_spans(payload)
        except Exception:
            logger.warning("mp-ingest: undecodable payload dropped")
            if self.metrics is not None:
                self.metrics.increment_messages_dropped()
            return
        n_all = len(spans)
        if self._sampler is not None:
            spans = [s for s in spans if self._sampler.test(s)]
        self.store.accept(spans).execute()
        if self.metrics is not None:
            self.metrics.increment_spans(n_all)
            if n_all - len(spans):
                self.metrics.increment_spans_dropped(n_all - len(spans))
