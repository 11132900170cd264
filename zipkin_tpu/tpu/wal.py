"""Host write-ahead log of fused wire batches (VERDICT r2 order 6).

SURVEY.md §5's failure-detection row calls for a "host WAL of raw
batches so a device restart replays the window": snapshots
(tpu/snapshot.py) capture sketch state periodically, but HTTP/gRPC
ingest BETWEEN snapshots lives only in volatile HBM — the reference
never loses acked spans (durability is delegated to its storage
backends; Kafka resumes from offsets). This module closes that gap for
the device aggregates:

- every batch that reaches ``ShardedAggregator.ingest_fused`` is
  appended as one record: the packed ``[shards, 11, per]`` u32 wire
  image (already contiguous — the append is a straight write, no
  serialization) plus the GLOBAL vocab entries interned since the last
  record, so replay reconstructs the identical id space;
- records carry a monotone sequence number; snapshots store the last
  sequence folded into the captured state, and restore replays only
  ``seq > snapshot.wal_seq`` — exactly the batches the snapshot missed;
- a crc over the payload detects the torn tail record of a mid-write
  crash: replay stops cleanly at the last complete record;
- segments rotate by size and are deleted once a newer snapshot covers
  them.

The sampled raw-span archive is NOT logged: it is a bounded, lossy
cache by design (1-in-N traces, evicted by capacity), so replaying it
would fake a durability the tier never promised. Counter/link/sketch
parity after crash+replay is asserted in tests/test_wal.py.
"""

from __future__ import annotations

import contextlib
import errno
import json
import logging
import os
import struct
import time
import zlib
from typing import Callable, Iterator, Optional, Tuple

import numpy as np

from zipkin_tpu import faults, obs
from zipkin_tpu.obs import critpath

logger = logging.getLogger(__name__)

_MAGIC = 0x5A57414C  # "ZWAL"
_HEADER = struct.Struct("<IQII I")  # magic, seq, meta_len, payload_len, crc


class WriteAheadLog:
    def __init__(
        self,
        directory: str,
        max_segment_bytes: int = 256 * 1024 * 1024,
        fsync: bool = False,
    ) -> None:
        self.directory = directory
        self.max_segment_bytes = max_segment_bytes
        self.fsync = fsync
        os.makedirs(directory, exist_ok=True)
        self._fh = None
        self._path: Optional[str] = None
        self._fh_bytes = 0
        self._seg_idx = 0
        self._seq = 0
        self._closed = False
        self._batch_depth = 0  # >0 inside batched(): defer flush/fsync
        # disk-exhaustion degraded mode (ISSUE 13): an ENOSPC append
        # does NOT crash the ingest path — the record is missed, the log
        # flags itself at-risk (acked spans between here and the next
        # durable snapshot would not survive a crash), and the flag
        # stays sticky until a snapshot re-covers the full state
        # (storage/tpu.py calls clear_at_risk() after a committed save)
        self.at_risk = False
        self.enospc_count = 0
        self.missed_records = 0
        # resume numbering after the existing records — via a HEADER
        # walk, not records(): records() stops at the first bad payload
        # crc, so mid-segment rot would hide the seq high-water mark and
        # a revived writer would re-issue seqs a snapshot already covers
        # (replay silently skips covered seqs: acked-span loss)
        self._seq = self._scan_high_seq()
        segs = self._segments()
        if segs:
            self._seg_idx = segs[-1][0] + 1

    # -- write side ------------------------------------------------------

    def append(self, fused: np.ndarray, meta: dict) -> int:
        """Append one batch; returns its sequence number. ``meta`` must
        be JSON-serializable; shape/dtype are recorded automatically."""
        if self._closed:
            # without this, a hook captured by a racing ingest thread
            # before close() detached it would silently REOPEN the
            # segment via _file_for and log a batch after the final
            # snapshot — double-replay on next boot (r3 review finding)
            raise RuntimeError("WAL is closed")
        # record write incl. buffer flush and fsync; an append that ran
        # into a full disk is counted by enospc_count, not here
        with obs.span("wal_append") as whole:
            return self._append_record(fused, meta, whole)

    def _append_record(self, fused: np.ndarray, meta: dict, whole) -> int:
        t0 = whole.t0
        self._seq += 1
        # memoryview, not tobytes(): the image is already contiguous u32
        # (or made so here) and BufferedWriter/crc32 both consume the
        # buffer protocol, so the record costs zero payload copies
        # (cast() refuses views with a zero in the shape, so empty
        # images — flush markers — take the literal-bytes branch)
        arr = np.ascontiguousarray(fused, np.uint32)
        payload = arr.data.cast("B") if arr.size else memoryview(b"")
        meta = dict(meta, shape=list(fused.shape))
        meta_b = json.dumps(meta, separators=(",", ":")).encode()
        head = _HEADER.pack(
            _MAGIC, self._seq, len(meta_b), len(payload),
            zlib.crc32(payload),
        )
        rec_len = len(head) + len(meta_b) + len(payload)
        deferred = self._batch_depth > 0
        try:
            faults.resource_point("wal.append")
            fh = self._file_for(rec_len)
            # the record is written in two pieces so the mid-append
            # crashpoint sits at the worst tear: header+meta on disk,
            # payload missing — replay must detect the torn record and
            # stop at it
            fh.write(head + meta_b)
            if faults.is_armed("wal.append.mid"):
                fh.flush()  # the partial record must be kernel-visible
                # for the in-process (raise) crash action to leave the
                # same on-disk state a SIGKILL after a real flush would
            faults.crashpoint("wal.append.mid")
            fh.write(payload)
            if not deferred:
                fh.flush()
            faults.crashpoint("wal.append.pre_fsync")
            t1 = time.perf_counter()
            # the critical-path ledger wants append and fsync as
            # DISJOINT intervals (the recorder's wal_append stage keeps
            # including the fsync): a no-op unless a traced MP payload
            # is being flushed on this thread
            critpath.stamp_active(
                critpath.SEG_WAL_APPEND, int(t0 * 1e9), int(t1 * 1e9)
            )
            if self.fsync and not deferred:
                with obs.span("wal_fsync") as sync:
                    os.fsync(fh.fileno())
                critpath.stamp_active(
                    critpath.SEG_WAL_FSYNC,
                    int(sync.t0 * 1e9), int(sync.t1 * 1e9),
                )
        except OSError as e:
            if e.errno != errno.ENOSPC:
                raise
            self._note_enospc()
            whole.drop()
            return self._seq
        # bit-rot injection site (ISSUE 7): the record's payload bytes
        # are durable — damage them at rest; the process keeps running
        # (a deferred append must land on disk first for rot to have
        # bytes to chew on)
        if deferred and faults.is_corrupt_armed("wal.record"):
            fh.flush()
        faults.corrupt_point(
            "wal.record", self._path,
            self._fh_bytes + _HEADER.size + len(meta_b), len(payload),
        )
        self._fh_bytes += rec_len
        return self._seq

    @contextlib.contextmanager
    def batched(self):
        """Vectored append: records appended inside this context defer
        the per-record flush/fsync, and exiting commits the whole run
        with ONE flush (+ one fsync when enabled) — the span-ring
        dispatcher's multi-group flush pass amortizes its durability
        syscalls this way. Record FORMAT is untouched (each append still
        writes its own header/meta/payload/crc), so ``records()``/
        ``replay()`` cannot tell a batched run from serial appends; only
        the ack must wait for the commit, which the dispatcher does.
        ``wal.append.mid`` keeps its armed-flush semantics per record."""
        if self._closed:
            raise RuntimeError("WAL is closed")
        self._batch_depth += 1
        try:
            yield self
        finally:
            self._batch_depth -= 1
            if self._batch_depth == 0:
                self._commit_batch()

    def _commit_batch(self) -> None:
        fh = self._fh
        if fh is None:
            return
        t1 = time.perf_counter()
        try:
            fh.flush()
            if self.fsync:
                os.fsync(fh.fileno())
                t2 = time.perf_counter()
                obs.record("wal_fsync", t2 - t1)
                critpath.stamp_active(
                    critpath.SEG_WAL_FSYNC, int(t1 * 1e9), int(t2 * 1e9)
                )
        except OSError as e:
            if e.errno != errno.ENOSPC:
                raise
            self._note_enospc()

    def _note_enospc(self) -> None:
        """Disk full mid-append: the record is lost (it gets a seq but
        no durable bytes) and the segment may carry a torn tail. Rotate
        so post-recovery appends land in a FRESH segment — replay skips
        a torn segment's tail, so stacking good records behind the tear
        would silently lose them. The log keeps accepting appends (each
        retries the disk) and flags itself at-risk until a snapshot
        re-covers the missed window."""
        self.enospc_count += 1
        self.missed_records += 1
        if not self.at_risk:
            logger.error(
                "WAL append hit ENOSPC at seq %d: durability AT RISK "
                "(acked spans not crash-safe until the next snapshot "
                "commit)", self._seq,
            )
        self.at_risk = True
        if self._fh is not None:
            try:
                self._fh.close()
            except OSError:
                pass
            self._fh = None

    def clear_at_risk(self) -> None:
        """Called after a committed snapshot: full state is durable
        again, the ENOSPC-missed WAL window no longer matters."""
        if self.at_risk:
            logger.info(
                "WAL at-risk cleared: snapshot re-covered the missed "
                "window (%d records lost to ENOSPC)", self.missed_records,
            )
        self.at_risk = False

    def _file_for(self, rec_len: int):
        if self._fh is not None and (
            self._fh_bytes + rec_len > self.max_segment_bytes
        ):
            self._fh.close()
            self._fh = None
        if self._fh is None:
            path = os.path.join(
                self.directory, f"wal-{self._seg_idx:08d}.log"
            )
            self._seg_idx += 1
            self._fh = open(path, "ab")
            self._path = path
            self._fh_bytes = os.path.getsize(path)
        return self._fh

    def _scan_high_seq(self) -> int:
        """Max seq over every structurally valid record HEADER across
        all segments. Payload damage (flipped/zeroed bytes) leaves the
        headers after it reachable, so rot cannot roll numbering back;
        a rotted header still ends the walk early — attach() closes that
        residual gap by flooring the counter at the snapshot's seq."""
        top = 0
        for _, path in self._segments():
            try:
                with open(path, "rb") as fh:
                    while True:
                        head = fh.read(_HEADER.size)
                        if len(head) < _HEADER.size:
                            break
                        magic, seq, meta_len, payload_len, _ = _HEADER.unpack(
                            head
                        )
                        if magic != _MAGIC:
                            break
                        top = max(top, seq)
                        fh.seek(meta_len + payload_len, os.SEEK_CUR)
            except OSError:
                continue
        return top

    # -- read side -------------------------------------------------------

    def _segments(self):
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("wal-") and name.endswith(".log"):
                try:
                    out.append(
                        (int(name[4:-4]), os.path.join(self.directory, name))
                    )
                except ValueError:
                    continue
        out.sort()
        return out

    def records(
        self, from_seq: int = 0
    ) -> Iterator[Tuple[int, dict, np.ndarray]]:
        """Yield (seq, meta, fused) for every complete record with
        ``seq > from_seq``. A torn/corrupt record skips the REST OF ITS
        SEGMENT only — in the designed crash scenario the torn record is
        a segment's write tail, and LATER segments (appended by a
        post-crash process) hold independently-acked batches whose vocab
        deltas build on exactly the replay state at the tear, so they
        must still replay (a whole-log stop here silently dropped them)."""
        for _, path in self._segments():
            with open(path, "rb") as fh:
                while True:
                    rec_off = fh.tell()
                    head = fh.read(_HEADER.size)
                    if not head:
                        break
                    if len(head) < _HEADER.size:
                        logger.warning(
                            "WAL %s: torn header at offset %d; skipping "
                            "segment tail", path, rec_off,
                        )
                        break
                    magic, seq, meta_len, payload_len, crc = _HEADER.unpack(
                        head
                    )
                    if magic != _MAGIC:
                        logger.warning(
                            "WAL %s: bad magic at offset %d; skipping "
                            "segment tail", path, rec_off,
                        )
                        break
                    if seq <= from_seq:
                        # covered by the snapshot: seek past the body
                        # instead of reading + CRC-checking bytes the
                        # caller is about to discard — resume from a
                        # late snapshot used to decode the entire log
                        # it then skipped. A seek past EOF (covered torn
                        # tail) is benign: the next header read comes
                        # back empty and ends the segment.
                        fh.seek(meta_len + payload_len, os.SEEK_CUR)
                        continue
                    meta_b = fh.read(meta_len)
                    payload = fh.read(payload_len)
                    if len(meta_b) < meta_len or len(payload) < payload_len:
                        logger.warning(
                            "WAL %s: torn record seq %d at offset %d; "
                            "skipping segment tail", path, seq, rec_off,
                        )
                        break
                    if zlib.crc32(payload) != crc:
                        # seq + offset so a postmortem can tell exactly
                        # where the abandonment started and how much of
                        # the segment it cost (ISSUE 7 satellite)
                        logger.warning(
                            "WAL %s: bad crc on record seq %d at offset %d; "
                            "skipping segment tail", path, seq, rec_off,
                        )
                        break
                    meta = json.loads(meta_b)
                    fused = np.frombuffer(payload, np.uint32).reshape(
                        meta["shape"]
                    )
                    yield seq, meta, fused

    # -- maintenance -----------------------------------------------------

    def truncate_covered(self, covered_seq: int) -> None:
        """Delete segments whose every record is <= covered_seq (already
        folded into a durable snapshot)."""
        segs = self._segments()
        newest_idx = segs[-1][0] if segs else -1
        for idx, path in segs:
            if idx == newest_idx:
                # Never unlink the newest segment, even when fully
                # covered. It is the live segment when one is open, and
                # after a reopen-without-writes it is the only carrier
                # of the seq high-water mark: deleting it would make the
                # next boot's records() scan find nothing, restart
                # numbering at 1, and hand post-truncate appends seqs
                # <= the snapshot's wal_seq — which replay would then
                # silently skip (acked-span loss). The old guard
                # (`self._fh is not None and self._fh_bytes`) only
                # protected the segment while a writer had it open.
                continue
            max_seq = 0
            try:
                with open(path, "rb") as fh:
                    while True:
                        head = fh.read(_HEADER.size)
                        if len(head) < _HEADER.size:
                            break
                        magic, seq, meta_len, payload_len, _ = _HEADER.unpack(
                            head
                        )
                        if magic != _MAGIC:
                            break
                        max_seq = max(max_seq, seq)
                        fh.seek(meta_len + payload_len, os.SEEK_CUR)
            except OSError:
                continue
            if max_seq and max_seq <= covered_seq:
                os.unlink(path)
                logger.info("WAL segment %s truncated (<= %d)", path, covered_seq)

    def sealed_segment_paths(self):
        """Segment paths EXCLUDING the newest — the scrub set. The
        newest segment is the live writer target and the seq high-water
        carrier; it is never scrubbed-quarantined (runtime/scrub.py)."""
        return [path for _, path in self._segments()[:-1]]

    def close(self) -> None:
        self._closed = True
        if self._fh is not None:
            self._fh.close()
            self._fh = None


def verify_segment(path: str) -> dict:
    """At-rest integrity scan of one segment (the scrubber's WAL leg):
    re-read every record, checking structure, payload crc, AND meta
    JSON validity (the header crc covers only the payload — rotted meta
    would otherwise surface as a json error mid-replay). Returns
    ``{"ok", "records", "max_seq", "bytes", "bad_seq", "bad_offset"}``;
    on damage, ``bad_seq``/``bad_offset`` locate the first bad record
    and ``max_seq`` covers only the records BEFORE it."""
    out = dict(
        ok=True, records=0, max_seq=0, bytes=0, bad_seq=None,
        bad_offset=None,
    )
    with open(path, "rb") as fh:
        while True:
            rec_off = fh.tell()
            head = fh.read(_HEADER.size)
            if not head:
                break
            bad = len(head) < _HEADER.size
            seq = None
            if not bad:
                magic, seq, meta_len, payload_len, crc = _HEADER.unpack(head)
                bad = magic != _MAGIC
            if not bad:
                meta_b = fh.read(meta_len)
                payload = fh.read(payload_len)
                bad = (
                    len(meta_b) < meta_len
                    or len(payload) < payload_len
                    or zlib.crc32(payload) != crc
                )
                if not bad:
                    try:
                        json.loads(meta_b)
                    except ValueError:
                        bad = True
            if bad:
                out["ok"] = False
                out["bad_seq"] = seq
                out["bad_offset"] = rec_off
                break
            out["records"] += 1
            out["max_seq"] = max(out["max_seq"], seq)
            out["bytes"] = fh.tell()
    return out


def attach(store, wal: WriteAheadLog) -> WriteAheadLog:
    """Wire a WAL into a TpuStorage: every ingest_fused batch is logged
    with the vocab delta since the previous record, and the aggregator
    records the applied sequence for snapshot coordination. Call AFTER
    any replay so the vocab delta cursors start at the current state."""
    vocab = store.vocab
    # numbering floor: never hand a new append a seq the restored
    # snapshot already covers (rotted headers can hide the true
    # high-water mark from the boot scan; covered seqs are skipped at
    # replay, so a re-issued one would lose an acked batch)
    wal._seq = max(wal._seq, int(getattr(store.agg, "wal_seq", 0)))
    sent = {"svc": 1, "name": 1, "pair": 1}
    # fast-forward the delta cursors past what a restored snapshot (or
    # prior replay) already covers — those entries are in snapshot meta
    sent["svc"] = len(vocab.services._names)
    sent["name"] = len(vocab.span_names._names)
    sent["pair"] = len(vocab._key_list)

    def hook(fused, n_spans, n_dur, n_err, ts_range, extra=None) -> int:
        with store._intern_lock:
            svc_new = vocab.services._names[sent["svc"]:]
            name_new = vocab.span_names._names[sent["name"]:]
            pairs_new = vocab._key_list[sent["pair"]:]
            sent["svc"] += len(svc_new)
            sent["name"] += len(name_new)
            sent["pair"] += len(pairs_new)
        meta = dict(
            n_spans=n_spans, n_dur=n_dur, n_err=n_err,
            ts_range=list(ts_range) if ts_range else None,
            svc=svc_new, names=name_new,
            pairs=[list(p) for p in pairs_new],
        )
        if extra:
            # sampling-tier sidecar meta: per-batch pre-compaction
            # seen/kept tallies, or a zero-lane "sctl" table-delta record
            # (controller publish) replay applies at this exact point of
            # the batch stream
            meta.update(extra)
        return wal.append(fused, meta)

    store.agg.wal_hook = hook
    store.wal = wal
    return wal


def replay(store, wal: WriteAheadLog, from_seq: int = 0) -> int:
    """Re-apply every WAL record after ``from_seq`` (the snapshot's
    cutoff) to the store: vocab deltas first (reconstructing the id
    space in the original intern order), then the fused batch. The WAL
    hook is suspended during replay. Returns batches applied."""
    agg = store.agg
    vocab = store.vocab
    hook, agg.wal_hook = getattr(agg, "wal_hook", None), None
    applied = 0
    try:
        for seq, meta, fused in wal.records(from_seq):
            with store._intern_lock:
                for s in meta.get("svc", []):
                    vocab.services.intern(s)
                for s in meta.get("names", []):
                    vocab.span_names.intern(s)
                for a, b in meta.get("pairs", []):
                    # position-faithful: the journal records the exact
                    # historical pair-id sequence (including any catch-
                    # all rows the writing build reserved) — re-deriving
                    # via key_id would shift every id when interning
                    # rules differ between builds (r4 review finding)
                    vocab.append_pair(a, b)
            sctl = meta.get("sctl")
            if sctl and hasattr(store, "apply_sctl"):
                # sampling-controller publish: apply the sparse table
                # delta to the host mirror HERE, between the same two
                # batches the live run published between — later replayed
                # verdicts must read the post-publish tables
                store.apply_sctl(sctl)
            if meta.get("ttflush"):
                # explicit digest flush marker (percentile reads, the
                # time-tier sealer): t-digest folding is order-sensitive,
                # so replay re-applies the flush at the exact stream
                # position — the time-bucket digests (tb_digest) come
                # back bit-identical only if pending points fold in the
                # same groups as the live run. wal_hook is None here, so
                # the replayed flush never re-logs its own marker.
                agg.flush_now()
            if meta.get("ttroll"):
                # explicit rollup marker (the sealer's pre-seal rollup):
                # same exact-position rule for the rolled edge planes
                agg.rollup_now()
            if fused.shape[-1]:
                agg.ingest_fused(
                    np.array(fused),  # frombuffer view is read-only
                    n_spans=meta["n_spans"], n_dur=meta["n_dur"],
                    n_err=meta["n_err"],
                    ts_range=tuple(ts) if (ts := meta.get("ts_range")) else None,
                )
            if "seen" in meta:
                # pre-compaction tallies of a sampled batch: the record
                # holds only kept lanes, so the ingest above under-counted
                # — restore the exact host counters from the meta
                hc = agg.host_counters
                hc["sampledKept"] += meta.get("kept", 0)
                hc["sampledDropped"] += meta["seen"] - meta.get("kept", 0)
                hc["spans"] += meta["seen"] - meta["n_spans"]
                hc["spansWithDuration"] += (
                    meta.get("seen_dur", meta["n_dur"]) - meta["n_dur"]
                )
                hc["spansWithError"] += (
                    meta.get("seen_err", meta["n_err"]) - meta["n_err"]
                )
            agg.wal_seq = seq
            applied += 1
    finally:
        agg.wal_hook = hook
    if applied:
        logger.info("WAL: replayed %d batches (> seq %d)", applied, from_seq)
    return applied
