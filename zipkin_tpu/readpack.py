"""One-transfer query reads: pack program outputs into a single buffer.

Every query program used to end in several separate ``np.asarray(...)``
device→host pulls (three for dependency edges, three for the merged
sketches, two for percentiles...). Each pull pays a transfer's fixed
cost, whatever its size, so N pulls amplify a query's wall N times over
its device program. This module makes **exactly one device→host transfer per
query** a structural invariant:

- **Device side** (:func:`pack`): the last stage of every read program
  flattens its output arrays into a single 1-D ``uint32`` buffer with a
  small fixed header, so the whole answer is one wire object — the
  "serve merged sketch reads as one compact wire object" shape of
  "Sketch Disaggregation Across Time and Space" (PAPERS.md).
- **Host side** (:func:`unpack`): one :func:`device_get` pulls the
  buffer; sections come back as zero-copy NumPy **views** into it.
- **Chokepoint** (:func:`device_get`): the only sanctioned device→host
  pull on the query path, with a process-wide transfer counter — so
  amplification is observable (``read_stats``/``/prometheus``) and
  regression-pinnable (tests/test_readpack.py asserts ==1 per query).

Wire format (all little-endian ``uint32`` words)::

    word 0                MAGIC 0x5A504B31 ("ZPK1": format + version)
    word 1                n_sections
    words 2 .. 2+8n-1     per-section header, 8 words each:
                            [0] dtype code (see DTYPE_CODES)
                            [1] byte offset of the section payload,
                                from the start of the buffer
                            [2] payload byte length (unpadded)
                            [3] ndim (0..4)
                            [4..7] dims (unused slots 0)
    then the payloads, each padded to a 4-byte (word) boundary

Shapes and dtypes are static at trace time, so the header is a compiled
constant — packing adds only the concatenation copy on device (KBs for
every query program; the dense state never crosses). Sections are
word-aligned by construction, which is what lets :func:`unpack` return
``.view(dtype)`` slices without copies. Booleans are stored as ``u8``
(NumPy bools are 1 byte, so the view back is also copy-free).
"""

from __future__ import annotations

import threading
import time
from typing import List, Sequence, Tuple

import numpy as np

from zipkin_tpu import obs
from zipkin_tpu.obs import querytrace

MAGIC = 0x5A504B31  # "ZPK1"
_SECTION_WORDS = 8
_MAX_NDIM = 4

# dtype code <-> NumPy dtype. Codes are part of the wire format: append
# only, never renumber (snapshots/benchmark artifacts may hold buffers).
DTYPE_CODES = {
    np.dtype(np.uint8): 0,
    np.dtype(np.uint32): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.float32): 3,
    np.dtype(np.bool_): 4,
    np.dtype(np.uint64): 5,
    np.dtype(np.int64): 6,
    np.dtype(np.float64): 7,
}
CODE_DTYPES = {v: k for k, v in DTYPE_CODES.items()}

# -- transfer accounting (the single chokepoint) -------------------------

_counter_lock = threading.Lock()
_transfers = 0
_transfer_bytes = 0


def device_get(x) -> np.ndarray:
    """THE device→host pull for the query read path. Counts every call
    (and its byte volume) so transfers-per-query is observable;
    everything that serves a query must come through here (pinned by
    ZT-lint rule ZT01 via tests/test_lint_clean.py)."""
    global _transfers, _transfer_bytes
    with _counter_lock:
        _transfers += 1
    import jax

    with obs.span("readpack_transfer") as pull:
        out = np.asarray(jax.device_get(x))
    querytrace.stamp_active(
        querytrace.QSEG_READPACK_TRANSFER,
        int(pull.t0 * 1e9), int(pull.t1 * 1e9),
    )
    with _counter_lock:
        _transfer_bytes += out.nbytes
    return out


def transfer_count() -> int:
    """Process-wide device→host transfer count (monotonic)."""
    with _counter_lock:
        return _transfers


def transfer_bytes() -> int:
    """Process-wide device→host transfer volume in bytes (monotonic)."""
    with _counter_lock:
        return _transfer_bytes


# -- device-side pack ----------------------------------------------------


def _section_words(a):
    """Flatten one array into uint32 words (device-side, trace-safe)."""
    import jax
    import jax.numpy as jnp

    a = jnp.asarray(a)
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint8)
    flat = a.reshape(-1)
    itemsize = np.dtype(a.dtype).itemsize
    if itemsize == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if itemsize == 1:
        pad = (-flat.shape[0]) % 4
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return jax.lax.bitcast_convert_type(
            flat.reshape(-1, 4), jnp.uint32
        )
    if itemsize == 8:
        # widens to [n, 2] words, low word first — matches the host's
        # little-endian view on every platform this runs on
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    raise NotImplementedError(
        f"readpack: unsupported dtype {a.dtype} (itemsize {itemsize})"
    )


def pack(arrays: Sequence) -> "jax.Array":  # noqa: F821 - doc type
    """Pack arrays into one 1-D uint32 wire buffer (device-side).

    Runs as the LAST stage inside a jitted read program: shapes/dtypes
    are static, so the header is a baked constant and XLA fuses the
    bitcasts; only the final concatenated buffer leaves the device.
    """
    import jax.numpy as jnp

    arrays = [jnp.asarray(a) for a in arrays]
    n = len(arrays)
    if n == 0:
        raise ValueError("readpack.pack: need at least one section")
    header_words = 2 + _SECTION_WORDS * n
    header = np.zeros(header_words, np.uint32)
    header[0] = MAGIC
    header[1] = n
    sections = []
    off = header_words * 4
    for i, a in enumerate(arrays):
        if a.ndim > _MAX_NDIM:
            raise ValueError(
                f"readpack.pack: ndim {a.ndim} > {_MAX_NDIM} (section {i})"
            )
        stored = np.dtype(np.uint8) if a.dtype == jnp.bool_ else np.dtype(a.dtype)
        code = DTYPE_CODES.get(
            np.dtype(np.bool_) if a.dtype == jnp.bool_ else np.dtype(a.dtype)
        )
        if code is None:
            raise NotImplementedError(f"readpack: unsupported dtype {a.dtype}")
        nbytes = int(np.prod(a.shape, dtype=np.int64)) * stored.itemsize
        h = 2 + _SECTION_WORDS * i
        header[h + 0] = code
        header[h + 1] = off
        header[h + 2] = nbytes
        header[h + 3] = a.ndim
        for d, dim in enumerate(a.shape):
            header[h + 4 + d] = dim
        words = _section_words(a)
        sections.append(words)
        off += int(words.shape[0]) * 4
    return jnp.concatenate([jnp.asarray(header)] + sections)


# -- host-side unpack ----------------------------------------------------


def unpack(buf: np.ndarray) -> List[np.ndarray]:
    """Split one pulled wire buffer back into its arrays, as zero-copy
    views (every returned array shares ``buf``'s memory)."""
    buf = np.asarray(buf)
    if buf.ndim != 1 or buf.dtype != np.uint32:
        raise ValueError(
            f"readpack.unpack: expected 1-D uint32, got {buf.dtype}{buf.shape}"
        )
    if buf.shape[0] < 2 or int(buf[0]) != MAGIC:
        raise ValueError("readpack.unpack: bad magic (not a ZPK1 buffer)")
    n = int(buf[1])
    raw = buf.view(np.uint8)
    out: List[np.ndarray] = []
    for i in range(n):
        h = buf[2 + _SECTION_WORDS * i : 2 + _SECTION_WORDS * (i + 1)]
        dt = CODE_DTYPES[int(h[0])]
        off, nbytes, ndim = int(h[1]), int(h[2]), int(h[3])
        dims = tuple(int(d) for d in h[4 : 4 + ndim])
        out.append(raw[off : off + nbytes].view(dt).reshape(dims))
    return out


def pull(packed) -> List[np.ndarray]:
    """One transfer + unpack: the host half of a packed query read."""
    buf = device_get(packed)
    if querytrace.active() is None:
        return unpack(buf)
    t0 = time.perf_counter_ns()
    out = unpack(buf)
    querytrace.stamp_active(
        querytrace.QSEG_UNPACK, t0, time.perf_counter_ns()
    )
    return out


def describe(buf: np.ndarray) -> List[Tuple[str, tuple, int]]:
    """Header introspection: [(dtype_name, shape, byte_len), ...]."""
    return [
        (a.dtype.name, a.shape, a.nbytes) for a in unpack(np.asarray(buf))
    ]
