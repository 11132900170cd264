"""Bytes the device step must move per applied span, whatever implements it.

The system runs no model: its step is memory traffic (scatter into sketches,
a ring write), not arithmetic, so the bound is HBM bandwidth and there is no
``mfu`` metric. The count is from what a span IS and what the configuration
keeps of it, not from the program's layout:

- the span as it reaches the device, read once: 128-bit trace id, 64-bit
  span and parent ids, service, remote service and key ids, minute
  timestamp, duration, flags: 11 words of 4 bytes;
- the same record written to the retention ring, and read once more when its
  half of the ring is rolled up into link counts;
- one edge cell read and written per span at the roll-up (calls and errors,
  4 bytes each way each);
- HyperLogLog: one register read and written in the service's row and in the
  global row, in the all-time sketch and, where the configuration keeps
  time buckets, in the current bucket's;
- histogram: one 4-byte cell read and written, all-time and in the current
  hour slice;
- t-digest: the (key, value) pair appended to the pending buffer, all-time
  and, with time buckets, in the current bucket's.

Compaction of the digest buffer and the sort inside the roll-up are work the
chosen algorithms add; they are not in this count, so the share reads low by
design and a better algorithm can raise it.
"""

WORD = 4
SPAN_WORDS = 11


def step_bytes_per_span(agg: dict) -> float:
    tiers = 2 if agg.get("time_buckets", 0) > 0 else 1
    record = SPAN_WORDS * WORD
    ring = record + record  # written at ingest, read at the roll-up
    edges = 2 * 2 * WORD  # calls and errors, read and write
    hll = tiers * 2 * 2 * 1  # two rows, one byte each way
    hist = 2 * 2 * WORD  # all-time and hour slice, read and write
    digest = tiers * 2 * WORD  # key and value appended
    return float(record + ring + edges + hll + hist + digest)


def min_seconds(spans: float, agg: dict, peaks: dict) -> float:
    """The least time the chip could take for ``spans`` spans: HBM-bound."""
    return spans * step_bytes_per_span(agg) / peaks["hbm_bytes_per_s"]
