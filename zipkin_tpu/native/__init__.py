"""Native host tier: the C fast-path codec.

Where the reference's performance-critical inner loops live in
hand-rolled Java (``zipkin2/internal/{ReadBuffer,WriteBuffer}.java``),
this package holds the C equivalents, compiled on demand with the
system toolchain and loaded via ctypes — no pip dependencies.

Graceful degradation is part of the contract: if no compiler is
available, or the payload uses features the fast path doesn't cover
(escaped strings, unknown kinds, the JSON v1 dialect), callers fall
back to the pure-Python codec, which is the semantic reference.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(__file__)
_SRC = os.path.join(_DIR, "span_json.c")
_BUILD_DIR = os.path.join(_DIR, "build")

#: span_json.c's ERR_V1: the JSON tokenizer met a span in v1's shape
_ERR_V1 = -4

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _compile() -> Optional[str]:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(_BUILD_DIR, f"span_json-{digest}.so")
    if os.path.exists(so_path):
        return so_path
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # A temporary name of this process's own: processes that build at once
    # (test workers, two servers on one checkout) would otherwise rename
    # each other's half-written file into place.
    fd, tmp = tempfile.mkstemp(dir=_BUILD_DIR, suffix=".so.tmp")
    os.close(fd)
    try:
        for cc in ("cc", "gcc", "clang"):
            try:
                subprocess.run(
                    [cc, "-O2", "-fPIC", "-shared", "-o", tmp, _SRC],
                    check=True, capture_output=True, timeout=120,
                )
            except FileNotFoundError:
                continue
            except subprocess.CalledProcessError as e:
                if os.path.exists(so_path):  # another process won
                    return so_path
                logger.warning(
                    "native codec build failed with %s: %s", cc, e.stderr
                )
                return None
            os.replace(tmp, so_path)
            return so_path
        logger.warning("no C compiler found; native codec disabled")
        return None
    finally:
        with contextlib.suppress(OSError):  # gone already if renamed
            os.unlink(tmp)


def _load():
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        so_path = _compile()
        if so_path is None:
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(so_path)
        except OSError:
            # A stale cached .so (e.g. built on a different arch/libc)
            # would otherwise disable the native codec forever, since the
            # source digest still matches. Evict it and rebuild once.
            try:
                os.unlink(so_path)
            except OSError:
                pass
            so_path = _compile()
            if so_path is None:
                _build_failed = True
                return None
            try:
                lib = ctypes.CDLL(so_path)
            except OSError as e:
                logger.warning("native codec load failed (%s); disabled", e)
                _build_failed = True
                return None
        u32p = ctypes.POINTER(ctypes.c_uint32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        base = (
            [ctypes.c_char_p, ctypes.c_size_t, ctypes.c_long]
            + [u32p] * 8  # id lanes
            + [u8p] * 4   # shared, kind, err, has_dur
            + [u64p, u32p, u8p]  # ts, dur, debug
            + [u32p] * 6  # string slices
            + [u32p] * 2  # span byte extents
        )
        lib.zt_parse_spans.restype = ctypes.c_long
        lib.zt_parse_spans.argtypes = base
        lib.zt_parse_spans_interned.restype = ctypes.c_long
        lib.zt_parse_spans_interned.argtypes = (
            base[:3] + [ctypes.c_void_p] + base[3:] + [i32p] * 4
        )
        lib.zt_parse_proto3.restype = ctypes.c_long
        lib.zt_parse_proto3.argtypes = base
        lib.zt_parse_proto3_interned.restype = ctypes.c_long
        lib.zt_parse_proto3_interned.argtypes = (
            base[:3] + [ctypes.c_void_p] + base[3:] + [i32p] * 4
        )
        lib.zt_vocab_new.restype = ctypes.c_void_p
        lib.zt_vocab_new.argtypes = [ctypes.c_uint32] * 3
        lib.zt_vocab_free.argtypes = [ctypes.c_void_p]
        lib.zt_vocab_drain_strings.restype = ctypes.c_long
        lib.zt_vocab_drain_strings.argtypes = [
            ctypes.c_void_p, ctypes.c_int, u8p, ctypes.c_size_t,
        ]
        lib.zt_vocab_drain_pairs.restype = ctypes.c_long
        lib.zt_vocab_drain_pairs.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_long,
        ]
        lib.zt_vocab_overflow.restype = ctypes.c_long
        lib.zt_vocab_overflow.argtypes = [ctypes.c_void_p]
        lib.zt_vocab_counts.argtypes = [ctypes.c_void_p] + [u32p] * 3
        for fn in (lib.zt_intern_service, lib.zt_intern_name):
            fn.restype = ctypes.c_long
            fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint32]
        for fn in (lib.zt_intern_pair, lib.zt_intern_pair_raw):
            fn.restype = ctypes.c_long
            fn.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
            ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


#: every per-span column of a parse result (the slice set chunking and
#: sampler filtering iterate over; ``data``/``n`` are handled separately)
PARSED_FIELDS = (
    "tl0", "tl1", "th0", "th1", "s0", "s1", "p0", "p1",
    "shared", "kind", "err", "has_dur", "ts_us", "dur_us",
    "debug", "svc_off", "svc_len", "rsvc_off", "rsvc_len",
    "name_off", "name_len", "span_off", "span_len",
    "svc_id", "rsvc_id", "name_id", "key_id",
)


class ParsedColumns:
    """Raw columnar parse result; string fields are (offset, len) slices
    into ``data`` (kept alive here). When parsed against a NativeVocab,
    the ``*_id`` columns are filled and interning is already done."""

    __slots__ = ("data", "n") + PARSED_FIELDS


def sampler_keep(parsed, n: int, boundary: int) -> np.ndarray:
    """[n] bool: which parsed spans a boundary sampler keeps — the exact
    numpy mirror of CollectorSampler.is_sampled on the trace id's low 64
    bits (Java parity: abs(MIN_VALUE) maps to MAX_VALUE so it drops at
    every rate < 1.0); debug spans always pass. Shared by the sync fast
    path and the multi-process workers so the two tiers drop identically.
    """
    lo = (
        parsed.tl1[:n].astype(np.uint64) << np.uint64(32)
    ) | parsed.tl0[:n].astype(np.uint64)
    signed = lo.view(np.int64)
    t = np.abs(signed)
    t = np.where(t == np.iinfo(np.int64).min, np.iinfo(np.int64).max, t)
    return (t <= boundary) | (parsed.debug[:n] != 0)


class NativeVocab:
    """C-side interning tables mirroring a Python Vocab.

    Ids are assigned by C in first-seen order; :meth:`sync` drains the
    insertion journal into the Python Vocab and asserts the ids line up,
    so everything downstream (lookup tables, snapshots) keeps working.
    Not thread-safe: callers serialize parse+sync (the store does).

    ``dialect_fallbacks`` counts the payloads that :func:`parse_spans`
    handed back as JSON v1 while parsing against these tables.
    """

    def __init__(self, vocab) -> None:
        lib = _load()
        if lib is None:
            raise RuntimeError("native codec unavailable")
        self._lib = lib
        self.vocab = vocab
        self.handle = lib.zt_vocab_new(
            vocab.services.capacity - 1,
            vocab.span_names.capacity - 1,
            vocab.max_keys - 1,
        )
        if not self.handle:
            raise MemoryError("zt_vocab_new failed")
        self._drain_buf = np.zeros(1 << 20, np.uint8)
        self._pair_buf = np.zeros(1 << 16, np.uint64)
        self.dialect_fallbacks = 0

    @property
    def overflow(self) -> int:
        """Total intern attempts the C tables rejected at capacity (the
        fast path's analog of Interner.overflow — overflowing entries
        never reach the Python journal, so they must be read from C)."""
        return int(self._lib.zt_vocab_overflow(self.handle))

    def counts(self):
        a = ctypes.c_uint32()
        b = ctypes.c_uint32()
        c = ctypes.c_uint32()
        self._lib.zt_vocab_counts(
            self.handle, ctypes.byref(a), ctypes.byref(b), ctypes.byref(c)
        )
        return a.value, b.value, c.value

    def ensure_synced(self) -> None:
        """Bring the C tables up to date with the Python vocab.

        The two id spaces must be identical (both assign sequentially in
        first-seen order). If the object path interned entries the C side
        hasn't seen, replay the missing tail in id order; if the C side
        somehow diverged (should not happen), rebuild it from Python.
        """
        c_svc, c_name, c_pair = self.counts()
        v = self.vocab
        py_svc = len(v.services) - 1
        py_name = len(v.span_names) - 1
        py_pair = v.num_keys - 1
        if (c_svc, c_name, c_pair) == (py_svc, py_name, py_pair):
            return
        if c_svc > py_svc or c_name > py_name or c_pair > py_pair:
            # C ahead of Python: a sync() was missed; drain it now.
            self.sync()
            c_svc, c_name, c_pair = self.counts()
        lib = self._lib
        for nid in range(c_svc + 1, len(v.services._names)):
            raw = v.services._names[nid].encode()
            got = lib.zt_intern_service(self.handle, raw, len(raw))
            assert got == nid, (got, nid, raw)
        for nid in range(c_name + 1, len(v.span_names._names)):
            raw = v.span_names._names[nid].encode()
            got = lib.zt_intern_name(self.handle, raw, len(raw))
            assert got == nid, (got, nid, raw)
        for kid in range(c_pair + 1, len(v._key_list)):
            s, n = v._key_list[kid]
            # _raw: position-faithful replay — the Python list records
            # the exact id order (including or excluding catch-all rows,
            # per the build that wrote it); the live interning rules
            # must not re-derive insertions here or ids shift
            got = lib.zt_intern_pair_raw(self.handle, s, n)
            assert got == kid, (got, kid, (s, n))
        # drain journals so the replay isn't re-reported as new
        self.sync()

    def sync(self) -> None:
        """Mirror newly interned strings/pairs into the Python vocab."""
        lib = self._lib
        for table, interner in ((0, self.vocab.services), (1, self.vocab.span_names)):
            while True:
                n = lib.zt_vocab_drain_strings(
                    self.handle, table,
                    self._drain_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    self._drain_buf.nbytes,
                )
                if n <= 0:
                    break
                pos = 0
                raw = self._drain_buf
                for _ in range(n):
                    ln = int.from_bytes(raw[pos : pos + 4], "little")
                    s = bytes(raw[pos + 4 : pos + 4 + ln]).decode("utf-8", "replace")
                    got = interner.intern(s)
                    pos += 4 + ln
                if n < 16384:
                    break
        while True:
            n = lib.zt_vocab_drain_pairs(
                self.handle,
                self._pair_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                len(self._pair_buf),
            )
            if n <= 0:
                break
            for i in range(n):
                v = int(self._pair_buf[i])
                self.vocab.key_id(v >> 32, v & 0xFFFFFFFF)
            if n < len(self._pair_buf):
                break

    def __del__(self):  # pragma: no cover - interpreter teardown
        try:
            if self.handle:
                self._lib.zt_vocab_free(self.handle)
                self.handle = None
        except Exception:
            pass


def parse_spans(
    data: bytes, cap: Optional[int] = None, nvocab: Optional[NativeVocab] = None
) -> Optional[ParsedColumns]:
    """Parse a JSON v2 span array OR a proto3 ``ListOfSpans`` into
    columns; None => use the Python codec (a JSON v1 body, parse error,
    unsupported feature, or no native lib).

    Routing is by framing alone (``codec.detect_framing``): a JSON opener
    (``[``/``{``, whitespace-led included) goes to the JSON tokenizer; an
    0x0A first byte (the ListOfSpans field-1 tag, and also a newline)
    goes to proto3 if the proto3 frame headers consume the payload
    exactly, else to the tokenizer; anything else returns None. The body
    is never scanned for v1 markers: the tokenizer meets v1's shape
    (``binaryAnnotations``, an annotation with an ``endpoint``) in its own
    pass and refuses the body, counted in ``nvocab.dialect_fallbacks``.

    With ``nvocab``, interning happens inside the parse (the ``*_id``
    columns are filled); the caller must hold the store's intern lock and
    call ``nvocab.sync()`` afterwards. A refused body interns nothing.
    """
    lib = _load()
    if lib is None:
        return None
    from zipkin_tpu.model import codec as _codec

    try:
        enc = _codec.detect_framing(data)
    except ValueError:
        return None
    if enc is _codec.Encoding.JSON_V2:
        fn_plain, fn_interned = lib.zt_parse_spans, lib.zt_parse_spans_interned
    elif enc is _codec.Encoding.PROTO3:
        fn_plain, fn_interned = (
            lib.zt_parse_proto3, lib.zt_parse_proto3_interned
        )
    else:
        return None
    if cap is None:
        # every span object contributes >= ~20 bytes; this bound never
        # truncates and keeps allocation linear in payload size
        cap = max(len(data) // 20, 16)

    u32 = lambda: np.zeros(cap, np.uint32)
    u8 = lambda: np.zeros(cap, np.uint8)
    out = ParsedColumns()
    out.data = data
    out.tl0, out.tl1, out.th0, out.th1 = u32(), u32(), u32(), u32()
    out.s0, out.s1, out.p0, out.p1 = u32(), u32(), u32(), u32()
    out.shared, out.kind, out.err, out.has_dur = u8(), u8(), u8(), u8()
    out.ts_us = np.zeros(cap, np.uint64)
    out.dur_us = u32()
    out.debug = u8()
    out.svc_off, out.svc_len = u32(), u32()
    out.rsvc_off, out.rsvc_len = u32(), u32()
    out.name_off, out.name_len = u32(), u32()
    out.span_off, out.span_len = u32(), u32()

    p32 = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32))
    p8 = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    p64 = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
    pi32 = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))

    common = (
        p32(out.tl0), p32(out.tl1), p32(out.th0), p32(out.th1),
        p32(out.s0), p32(out.s1), p32(out.p0), p32(out.p1),
        p8(out.shared), p8(out.kind), p8(out.err), p8(out.has_dur),
        p64(out.ts_us), p32(out.dur_us), p8(out.debug),
        p32(out.svc_off), p32(out.svc_len),
        p32(out.rsvc_off), p32(out.rsvc_len),
        p32(out.name_off), p32(out.name_len),
        p32(out.span_off), p32(out.span_len),
    )
    if nvocab is not None:
        out.svc_id = np.zeros(cap, np.int32)
        out.rsvc_id = np.zeros(cap, np.int32)
        out.name_id = np.zeros(cap, np.int32)
        out.key_id = np.zeros(cap, np.int32)
        n = fn_interned(
            data, len(data), cap, nvocab.handle, *common,
            pi32(out.svc_id), pi32(out.rsvc_id),
            pi32(out.name_id), pi32(out.key_id),
        )
    else:
        out.svc_id = None
        n = fn_plain(data, len(data), cap, *common)
    if n < 0:
        if n == _ERR_V1 and nvocab is not None:
            nvocab.dialect_fallbacks += 1
        return None
    out.n = int(n)
    return out
