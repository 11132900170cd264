"""Host-side columnar packing: Span objects -> fixed-shape device batches.

The reference's row-oriented object-per-span design
(``zipkin2/Span.java``) is wrong for TPU; the idiomatic core is a struct
of fixed-shape arrays with host-side string interning (SURVEY.md §7
"Design stance"). This module is the boundary: everything above it speaks
:class:`zipkin_tpu.model.span.Span`, everything below speaks arrays.

Ids: trace/span ids are 64/128-bit hex strings in the model; on device
they travel as ``uint32`` lane pairs (TPUs have no useful 64-bit integer
path). ``trace_h`` is a host-computed 32-bit avalanche hash of the full
128-bit id, used for HLL cardinality and as the cheap first lane of
join keys.

Strings: service names / span names are interned into bounded
vocabularies. Id 0 is reserved for "unknown/absent"; overflow beyond
capacity lands in id 0 and is counted (the bounded-cardinality stance the
reference delegates to backends, SURVEY.md §5 long-context row).
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from zipkin_tpu.internal.hex import lower_64, normalize_trace_id
from zipkin_tpu.model.span import Kind, Span

KIND_TO_ID = {
    None: 0,
    Kind.CLIENT: 1,
    Kind.SERVER: 2,
    Kind.PRODUCER: 3,
    Kind.CONSUMER: 4,
}
ID_TO_KIND = {v: k for k, v in KIND_TO_ID.items()}

_U32 = np.uint32
_MASK32 = 0xFFFFFFFF


def _mix32(x: np.ndarray) -> np.ndarray:
    """numpy mirror of zipkin_tpu.ops.hashing.fmix32 (must stay in sync)."""
    x = x.astype(np.uint32)
    x ^= x >> _U32(16)
    x = (x.astype(np.uint64) * np.uint64(0x85EBCA6B)).astype(np.uint32)
    x ^= x >> _U32(13)
    x = (x.astype(np.uint64) * np.uint64(0xC2B2AE35)).astype(np.uint32)
    x ^= x >> _U32(16)
    return x


def _hash2_np(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return _mix32(a.astype(np.uint32) ^ _mix32((b.astype(np.uint64) + np.uint64(0x9E3779B9)).astype(np.uint32)))


class Interner:
    """Bounded, thread-safe string -> dense id map. Id 0 is reserved."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self._ids: Dict[str, int] = {}
        self._names: List[str] = ["" ]  # id 0
        self._overflow = 0
        self._lock = threading.Lock()

    def intern(self, name: Optional[str]) -> int:
        if not name:
            return 0
        with self._lock:
            got = self._ids.get(name)
            if got is not None:
                return got
            if len(self._names) >= self.capacity:
                self._overflow += 1
                return 0
            nid = len(self._names)
            self._ids[name] = nid
            self._names.append(name)
            return nid

    def lookup(self, nid: int) -> str:
        return self._names[nid] if 0 <= nid < len(self._names) else ""

    def get(self, name: str) -> Optional[int]:
        return self._ids.get(name)

    @property
    def names(self) -> List[str]:
        return self._names[1:]

    @property
    def overflow(self) -> int:
        return self._overflow

    def __len__(self) -> int:
        return len(self._names)


class Vocab:
    """The interners one TPU store shares across batches.

    ``keys`` interns (service, spanName) pairs — the sketch row space for
    latency digests, mirroring the per-(service, span) indexing of the
    reference's index tables (``trace_by_service_span`` in the cassandra
    schema, SURVEY.md §2.3).
    """

    def __init__(self, max_services: int = 1024, max_keys: int = 8192) -> None:
        self.services = Interner(max_services)
        self.span_names = Interner(max_keys)
        self._keys: Dict[Tuple[int, int], int] = {}
        self._key_list: List[Tuple[int, int]] = [(0, 0)]
        self.max_keys = max_keys
        self._overflow = 0
        self._lock = threading.Lock()

    def key_id(self, service_id: int, span_name_id: int) -> int:
        pair = (service_id, span_name_id)
        with self._lock:
            got = self._keys.get(pair)
            if got is not None:
                return got
            if span_name_id != 0 and service_id != 0:
                # pre-reserve the per-service catch-all (svc, 0) BEFORE
                # the named pair — same order as the C interner, so the
                # two id streams stay identical. Past capacity, span-name
                # churn then aggregates under its SERVICE's catch-all row
                # (semantically the "unnamed span mass for this service"
                # row, which id 0 names already share) instead of the
                # global unknown row — the r3 adversarial bench lumped
                # 2.2M spans into one unattributable global row
                # (VERDICT r3 order 5). Service 0 is the global unknown
                # itself: no catch-all (a shadow (0, 0) row would hijack
                # unknown-service mass from row 0).
                ca = (service_id, 0)
                if ca not in self._keys and len(self._key_list) < self.max_keys:
                    cid = len(self._key_list)
                    self._keys[ca] = cid
                    self._key_list.append(ca)
            if len(self._key_list) >= self.max_keys:
                self._overflow += 1
                if span_name_id != 0 and service_id != 0:
                    return self._keys.get((service_id, 0), 0)
                return 0
            kid = len(self._key_list)
            self._keys[pair] = kid
            self._key_list.append(pair)
            return kid

    def append_pair(self, service_id: int, span_name_id: int) -> int:
        """Position-faithful append for REPLAY paths (WAL, snapshots):
        records the pair at the next id with NO derived insertions (no
        catch-all pre-reserve), reproducing a historical id assignment
        verbatim whatever interning rules the writing build used. Live
        ingest must use :meth:`key_id`."""
        pair = (service_id, span_name_id)
        with self._lock:
            got = self._keys.get(pair)
            if got is not None:
                return got
            if len(self._key_list) >= self.max_keys:
                self._overflow += 1
                return 0
            kid = len(self._key_list)
            self._keys[pair] = kid
            self._key_list.append(pair)
            return kid

    def key_pair(self, key_id: int) -> Tuple[int, int]:
        return self._key_list[key_id] if 0 <= key_id < len(self._key_list) else (0, 0)

    def key_ids_for_service(self, service_id: int) -> List[int]:
        return [k for k, (s, _) in enumerate(self._key_list) if s == service_id and k]

    @property
    def num_keys(self) -> int:
        return len(self._key_list)


class SpanColumns(NamedTuple):
    """One fixed-shape batch; every field is a numpy array of length n."""

    trace_h: np.ndarray  # u32 avalanche hash of the full trace id
    tl0: np.ndarray  # u32 trace id low-64 lanes (lo, hi of the low word)
    tl1: np.ndarray
    s0: np.ndarray  # u32 span id lanes
    s1: np.ndarray
    p0: np.ndarray  # u32 parent id lanes (0,0 = absent)
    p1: np.ndarray
    shared: np.ndarray  # bool
    kind: np.ndarray  # i32 KIND_TO_ID
    svc: np.ndarray  # i32 local service id
    rsvc: np.ndarray  # i32 remote service id
    key: np.ndarray  # i32 (service, spanName) sketch row
    err: np.ndarray  # bool
    dur: np.ndarray  # u32 duration µs (clamped), 0 if absent
    has_dur: np.ndarray  # bool
    ts_min: np.ndarray  # u32 epoch minutes (retention ring key)
    valid: np.ndarray  # bool

    @property
    def size(self) -> int:
        return int(self.valid.shape[0])

    @property
    def live(self) -> int:
        return int(self.valid.sum())

    def concat(self, other: "SpanColumns") -> "SpanColumns":
        return SpanColumns(*(np.concatenate([a, b]) for a, b in zip(self, other)))


# Packed wire image: 11 u32 rows = 44 B/span (was 17 rows / 68 B in r2;
# a transfer costs by its size, so narrow lanes ride shared rows).
#   rows 0-8: trace_h, tl0, tl1, s0, s1, p0, p1, dur, ts_min (plain u32)
#   row 9:    svc << 16 | rsvc          (service ids, u16 each)
#   row 10:   key << 8 | kind << 4 | has_dur << 3 | err << 2
#             | shared << 1 | valid     (key u24 + 8 flag bits)
WIRE_ROWS = 11
_PLAIN = ("trace_h", "tl0", "tl1", "s0", "s1", "p0", "p1", "dur", "ts_min")
# hard ceilings implied by the packing (AggConfig defaults: 1024 / 8192)
MAX_WIRE_SERVICES = 1 << 16
MAX_WIRE_KEYS = 1 << 24


def fuse_columns(cols: SpanColumns) -> np.ndarray:
    """One contiguous PACKED u32 image of a batch: ``[..., 11, n]``.

    A host->device transfer costs a fixed overhead per array plus its
    raw bytes, so the whole batch ships
    as ONE uint32 array — with the narrow fields (service ids, sketch
    key, kind, flag bits) packed into shared rows — and is unpacked on
    device by :func:`zipkin_tpu.parallel.sharded.unfuse_columns` (free
    shifts/masks that XLA fuses into the consuming ops). Accepts
    per-shard stacked fields (leading axes are preserved).
    """
    d = cols._asdict()
    lead = cols.valid.shape[:-1]
    n = cols.valid.shape[-1]
    out = np.empty(lead + (WIRE_ROWS, n), np.uint32)
    for i, name in enumerate(_PLAIN):
        out[..., i, :] = d[name]
    out[..., 9, :] = (
        (d["svc"].astype(np.uint32) << _U32(16)) | d["rsvc"].astype(np.uint32)
    )
    out[..., 10, :] = (
        (d["key"].astype(np.uint32) << _U32(8))
        | (d["kind"].astype(np.uint32) << _U32(4))
        | (d["has_dur"].astype(np.uint32) << _U32(3))
        | (d["err"].astype(np.uint32) << _U32(2))
        | (d["shared"].astype(np.uint32) << _U32(1))
        | d["valid"].astype(np.uint32)
    )
    return out


def empty_columns(n: int) -> SpanColumns:
    z32 = np.zeros(n, _U32)
    return SpanColumns(
        trace_h=z32.copy(), tl0=z32.copy(), tl1=z32.copy(),
        s0=z32.copy(), s1=z32.copy(), p0=z32.copy(), p1=z32.copy(),
        shared=np.zeros(n, bool), kind=np.zeros(n, np.int32),
        svc=np.zeros(n, np.int32), rsvc=np.zeros(n, np.int32),
        key=np.zeros(n, np.int32), err=np.zeros(n, bool),
        dur=z32.copy(), has_dur=np.zeros(n, bool),
        ts_min=z32.copy(), valid=np.zeros(n, bool),
    )


def _pad(n: int, multiple: int) -> int:
    if n == 0:
        return multiple
    return ((n + multiple - 1) // multiple) * multiple


def pack_parsed(
    parsed, vocab: Vocab, pad_to_multiple: int = 1024
) -> SpanColumns:
    """Columns from a native parse (zipkin_tpu.native.parse_spans) —
    the fast ingest path: no Span objects, strings interned straight from
    the wire-buffer slices.

    Interning cost is the host bottleneck at line rate, so slices are
    cached per-call by their raw bytes (names repeat heavily within a
    batch) and the service/name/key lookups share one pass.
    """
    n = parsed.n
    cap = _pad(n, pad_to_multiple)
    data = parsed.data
    mv = memoryview(data)

    svc = np.zeros(cap, np.int32)
    rsvc = np.zeros(cap, np.int32)
    key = np.zeros(cap, np.int32)

    if getattr(parsed, "svc_id", None) is not None:
        # interning already happened inside the native parse
        svc[:n] = parsed.svc_id[:n]
        rsvc[:n] = parsed.rsvc_id[:n]
        key[:n] = parsed.key_id[:n]
        return _assemble(parsed, n, cap, svc, rsvc, key)

    intern_svc = vocab.services.intern
    intern_name = vocab.span_names.intern
    key_id = vocab.key_id
    scache: Dict[bytes, int] = {}
    ncache: Dict[bytes, int] = {}
    kcache: Dict[Tuple[int, int], int] = {}

    soff, slen = parsed.svc_off, parsed.svc_len
    roff, rlen = parsed.rsvc_off, parsed.rsvc_len
    noff, nlen = parsed.name_off, parsed.name_len

    def sid_of(off: int, ln: int) -> int:
        if ln == 0:
            return 0
        raw = bytes(mv[off : off + ln])
        got = scache.get(raw)
        if got is None:
            got = intern_svc(raw.decode("utf-8", "replace").lower())
            scache[raw] = got
        return got

    for i in range(n):
        s = sid_of(soff[i], slen[i])
        svc[i] = s
        rsvc[i] = sid_of(roff[i], rlen[i])
        ln = nlen[i]
        if ln:
            raw = bytes(mv[noff[i] : noff[i] + ln])
            nid = ncache.get(raw)
            if nid is None:
                nid = intern_name(raw.decode("utf-8", "replace").lower())
                ncache[raw] = nid
        else:
            nid = 0
        pair = (s, nid)
        kid = kcache.get(pair)
        if kid is None:
            kid = key_id(s, nid)
            kcache[pair] = kid
        key[i] = kid

    return _assemble(parsed, n, cap, svc, rsvc, key)


def _assemble(parsed, n, cap, svc, rsvc, key) -> SpanColumns:
    def padded(a: np.ndarray, dtype) -> np.ndarray:
        out = np.zeros(cap, dtype)
        out[:n] = a[:n]
        return out

    hi32 = _hash2_np(parsed.th0[:n], parsed.th1[:n])
    trace_h = np.zeros(cap, _U32)
    trace_h[:n] = _hash2_np(_hash2_np(parsed.tl0[:n], parsed.tl1[:n]), hi32)

    valid = np.zeros(cap, bool)
    valid[:n] = True
    return SpanColumns(
        trace_h=trace_h,
        tl0=padded(parsed.tl0, _U32), tl1=padded(parsed.tl1, _U32),
        s0=padded(parsed.s0, _U32), s1=padded(parsed.s1, _U32),
        p0=padded(parsed.p0, _U32), p1=padded(parsed.p1, _U32),
        shared=padded(parsed.shared, bool),
        kind=padded(parsed.kind, np.int32),
        svc=svc, rsvc=rsvc, key=key,
        err=padded(parsed.err, bool),
        dur=padded(parsed.dur_us, _U32),
        has_dur=padded(parsed.has_dur, bool),
        ts_min=padded((parsed.ts_us // 60_000_000).astype(_U32), _U32),
        valid=valid,
    )


def pack_spans(
    spans: Sequence[Span], vocab: Vocab, pad_to_multiple: int = 1024
) -> SpanColumns:
    """Pack spans into a padded columnar batch, interning strings.

    Padding to a small set of bucket sizes keeps jit cache hits high
    (static shapes, SURVEY.md §7 P2 "pad/bucket to static shapes").
    """
    n = len(spans)
    cap = _pad(n, pad_to_multiple)
    cols = empty_columns(cap)

    hi = np.zeros(n, np.uint64)
    lo = np.zeros(n, np.uint64)
    for i, span in enumerate(spans):
        tid = normalize_trace_id(span.trace_id)
        full = int(tid, 16)
        lo[i] = full & 0xFFFFFFFFFFFFFFFF
        hi[i] = full >> 64
        sid = int(span.id, 16)
        cols.s0[i] = sid & _MASK32
        cols.s1[i] = (sid >> 32) & _MASK32
        if span.parent_id:
            pid = int(span.parent_id, 16)
            cols.p0[i] = pid & _MASK32
            cols.p1[i] = (pid >> 32) & _MASK32
        cols.shared[i] = bool(span.shared)
        cols.kind[i] = KIND_TO_ID[span.kind]
        svc = vocab.services.intern(span.local_service_name)
        cols.svc[i] = svc
        cols.rsvc[i] = vocab.services.intern(span.remote_service_name)
        name_id = vocab.span_names.intern(span.name)
        cols.key[i] = vocab.key_id(svc, name_id)
        cols.err[i] = span.is_error
        if span.duration is not None:
            cols.dur[i] = min(int(span.duration), _MASK32)
            cols.has_dur[i] = True
        if span.timestamp is not None:
            cols.ts_min[i] = min(int(span.timestamp) // 60_000_000, _MASK32)
        cols.valid[i] = True

    cols.tl0[:n] = (lo & _MASK32).astype(_U32)
    cols.tl1[:n] = (lo >> np.uint64(32)).astype(_U32)
    hi32 = _hash2_np((hi & _MASK32).astype(_U32), (hi >> np.uint64(32)).astype(_U32))
    cols.trace_h[:n] = _hash2_np(
        _hash2_np(cols.tl0[:n], cols.tl1[:n]), hi32
    )
    return cols


def _route_order(shard_of: np.ndarray, n_shards: int, pad_to_multiple: int):
    """(order, counts, starts, per): lanes stably sorted by shard id, so
    shard ``s`` owns the contiguous slice ``order[starts[s] :
    starts[s] + counts[s]]`` and within-shard insertion order is
    preserved (the linker's first-wins tie-breaks depend on it).

    One radix argsort over a u8 key replaces the per-shard nonzero scans
    (the r2 Python loop cost 8 shards x 17 fields of masked gathers on
    the ingest hot path, VERDICT r2 weak #5); the u8 cast alone makes
    numpy pick its radix path — 15x faster than the i32 stable sort.
    """
    key_dtype = np.uint8 if n_shards < 255 else np.uint16
    order = np.argsort(shard_of.astype(key_dtype), kind="stable")
    counts = np.bincount(shard_of, minlength=n_shards + 1)[:n_shards]
    per = max(int(counts.max()), 1)
    per = ((per + pad_to_multiple - 1) // pad_to_multiple) * pad_to_multiple
    starts = np.zeros(n_shards, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    return order, counts, starts, per


def _shard_of(cols: SpanColumns, n_shards: int) -> np.ndarray:
    """Trace-affine shard id per lane (invalid lanes -> sink n_shards).

    Trace affinity (all spans of a trace land on one shard) is what makes
    the dependency-link parent joins shard-local — the same invariant the
    reference gets from trace-id–keyed storage partitioning.
    """
    return np.where(
        cols.valid, cols.trace_h % np.uint32(n_shards), n_shards
    ).astype(np.int32)


def route_fused(
    cols: SpanColumns, n_shards: int, pad_to_multiple: int = 256
) -> np.ndarray:
    """Fuse + route in one pass: ``[shards, F, per]`` u32 wire image.

    The whole routed batch is ONE fancy-index gather over the fused
    image (plus an appended zero lane serving as the pad sentinel), so
    multi-chip routing costs the same order as single-chip fusing.
    """
    fz = fuse_columns(cols)  # [F, n]
    if n_shards == 1:
        return fz[None]
    order, counts, starts, per = _route_order(
        _shard_of(cols, n_shards), n_shards, pad_to_multiple
    )
    out = np.zeros((n_shards, fz.shape[0], per), np.uint32)
    for s in range(n_shards):
        c = int(counts[s])
        if c:
            # each destination block is contiguous, so np.take(out=)
            # writes it in one pass — the whole route is one radix sort
            # + n_shards block gathers, ~0.05µs/span at 8 shards
            np.take(fz, order[starts[s] : starts[s] + c], axis=1,
                    out=out[s, :, :c])
    return out


def remap_fused(
    fused: np.ndarray, svc_map: np.ndarray, key_map: np.ndarray
) -> None:  # zt-dispatch-critical: per-span id remap on the dispatch core
    """Remap a packed wire image's service/key id lanes in place through
    ``svc_map``/``key_map`` lookup tables (u32, indexed by old id).

    This is the dispatch-core half of the MP fan-out's worker-local
    interning: workers intern against private vocabs, and the dispatcher
    rewrites row 9 (``svc << 16 | rsvc``) and row 10's key field
    (``key << 8 | flags``) local -> global with three vectorized table
    lookups. Lives here so the packed-row layout is defined in exactly
    one module (see :func:`fuse_columns`). Accepts ``[F, n]`` and
    ``[shards, F, n]`` images alike.
    """
    sr = fused[..., 9, :]
    fused[..., 9, :] = (svc_map[sr >> _U32(16)] << _U32(16)) | svc_map[
        sr & _U32(0xFFFF)
    ]
    kf = fused[..., 10, :]
    fused[..., 10, :] = (key_map[kf >> _U32(8)] << _U32(8)) | (
        kf & _U32(0xFF)
    )


def concat_remap(
    parts, out: np.ndarray
) -> int:  # zt-dispatch-critical: the coalesce gather — one pass per chunk over the whole coalesced image
    """Gather N routed chunk images into one bucket-padded image while
    remapping worker-local ids to global (the span-ring dispatcher's
    coalesce step: the only copy a ready slot ever takes).

    ``parts`` is a sequence of ``(fused, svc_map, key_map)`` where each
    ``fused`` is ``[shards, F, per_i]`` (typically a zero-copy view into
    a ring slot) and the maps are that chunk's local->global LUTs.
    ``out`` is a zeroed ``[shards, F, bucket]`` destination with
    ``bucket >= sum(per_i)``. Chunks land lane-contiguous in order;
    trailing pad lanes stay zero (valid=0 — the same safe-pad invariant
    as :func:`route_fused`). Remapping happens on the copied lanes, so
    the shared-memory source is never written. Returns the number of
    populated lanes per shard.
    """
    off = 0
    for fused, svc_map, key_map in parts:  # zt-lint: disable=ZT09 — bounded by coalesce_max chunks; each iteration is whole-image vectorized
        per = fused.shape[-1]
        dst = out[..., off:off + per]
        dst[:] = fused
        remap_fused(dst, svc_map, key_map)
        off += per
    return off


def route_columns(
    cols: SpanColumns, n_shards: int, pad_to_multiple: int = 256
) -> SpanColumns:
    """Host-side trace-affine routing: split one batch into ``n_shards``
    stacked sub-batches ``[shards, per]`` keyed by trace hash (see
    :func:`_shard_of`). Column-typed variant of :func:`route_fused` for
    callers that want SpanColumns; the ingest path routes the fused
    image directly.
    """
    n = cols.valid.shape[0]
    order, counts, starts, per = _route_order(
        _shard_of(cols, n_shards), n_shards, pad_to_multiple
    )
    j = np.arange(per)
    in_range = j[None, :] < counts[:, None]
    # gather indices with sentinel n -> appended zero/invalid lane
    # (max(n-1, 0): a zero-length batch still routes to all-pad shards)
    take = np.where(
        in_range,
        order[np.minimum(starts[:, None] + j[None, :], max(n - 1, 0))]
        if n else n,
        n,
    ).reshape(-1)

    def route(field: np.ndarray) -> np.ndarray:
        padded = np.concatenate([field, np.zeros(1, field.dtype)])
        return padded[take].reshape(n_shards, per)

    return SpanColumns(*(route(f) for f in cols))
