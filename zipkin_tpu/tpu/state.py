"""Device-resident aggregate state: the TPU replacement for storage rows.

Where the reference materializes every span as rows + index tables
(cassandra ``span`` / ``trace_by_service_span``, ES daily indices —
SURVEY.md §2.3), the TPU tier keeps **fixed-shape aggregate state in HBM**
(SURVEY.md §7 design stance):

- ``hll``      — [services+1, m] u8: distinct-trace registers, row per
                 service, last row global.
- ``hist``     — [keys, BUCKETS] u32: per-(service, spanName) latency
                 histograms (psum-mergeable), all-time.
- ``hist_t``   — [T, keys, BUCKETS] u32: time-sliced histograms (slice =
                 epoch-hour % T) so percentile queries can be WINDOWED —
                 the sketch analog of the reference's daily ES indices.
- ``digest``   — [keys, C, 2] f32: per-key t-digests for tight tails.
- ring columns — a circular columnar span window (capacity R) feeding the
                 windowed dependency-link job.
- rollup       — [D, S, S] per-time-bucket dependency-link matrices: when
                 ring spans are about to be overwritten, a rollup program
                 links them and folds the edges into the bucket of the
                 child span's timestamp. This is the exact analog of the
                 reference's PRE-AGGREGATED daily ``dependency`` rows
                 (cassandra schema / zipkin-dependencies job, SURVEY.md
                 §2.3, §3.5) — links survive ring eviction, and
                 ``get_dependencies(endTs, lookback)`` merges live-ring
                 links with the buckets in the window.
- ``counters`` — ingest telemetry (CollectorMetrics catalogue, §2.2).

The whole state is one NamedTuple pytree of arrays → trivially donatable,
shard-able on a leading axis, and snapshot-able (tpu/snapshot.py).
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp

from zipkin_tpu.ops import histogram

# counter slots (keep CollectorMetrics names in docs/metrics export)
CTR_SPANS, CTR_SPANS_DROPPED, CTR_WITH_DURATION, CTR_ERRORS, CTR_BATCHES = range(5)
# tail-sampling verdict tallies (zipkin_tpu/sampling): spans the device
# sampler kept / dropped for RETENTION — sketches still saw all of them
CTR_SAMPLED_KEPT = 5
CTR_SAMPLED_DROPPED = 6
NUM_COUNTERS = 8


@dataclasses.dataclass(frozen=True)
class AggConfig:
    """Static shapes of the device state; hashable so jit can close over it."""

    max_services: int = 1024
    max_keys: int = 8192
    hll_precision: int = 11
    digest_centroids: int = 64
    # t-digest pending buffer: batches append here (cheap) and the big
    # sort-based compaction runs only when it fills — the classic digest
    # buffering trade, amortizing the K*C-point sort across many batches.
    # Must be >= the largest packed batch size. 128k lanes halve the
    # per-span compaction cost vs 64k (the sort is dominated by the
    # K*C existing-centroid lanes, so a bigger buffer is nearly free).
    digest_buffer: int = 1 << 17
    ring_capacity: int = 1 << 18  # spans retained per shard for linking
    # time-bucketed retention (the daily-index / daily-dependency-table
    # analog): D rollup slots of bucket_minutes each for link matrices,
    # T slices of slice_minutes each for windowed histograms. A slot/slice
    # is recycled when a newer epoch maps onto it, so coverage is the most
    # recent D*bucket_minutes / T*slice_minutes of traffic.
    link_buckets: int = 16
    bucket_minutes: int = 60
    hist_slices: int = 8
    hist_slice_minutes: int = 60
    # tail-sampling tier (zipkin_tpu/sampling): when on, the ingest step
    # scores every span against the published sampler tables (s_rate /
    # s_tail / s_link leaves) and records the keep verdict in the r_keep
    # ring column + counter slots 5/6. Static so sampling=False compiles
    # the exact pre-sampling step. rare_min: a (svc, rsvc) edge whose
    # published link count is below this is "rare" and always kept.
    sampling: bool = False
    sample_rare_min: int = 4
    # time-disaggregated sketch tier (tpu/timetier.py): the ingest step
    # ALSO updates a current-bucket set of sketch leaves — tb_hll /
    # tb_digest / tb_calls+tb_errs over W = time_buckets ring slots of
    # time_bucket_minutes each (slot = epoch % W, recycled exactly like
    # hist_t slices). A host-side sealer reads completed buckets out as
    # compact mergeable segments; queries over [lookback, endTs] merge
    # covering segments plus the unsealed device slots. The persisted
    # query digest is deliberately SMALLER than the cumulative update
    # digest (the SF-sketch two-stage split): time_digest_centroids
    # clusters per key per bucket. time_buckets=0 disables the tier
    # (no leaves allocated, no tt programs compiled).
    time_buckets: int = 4
    time_bucket_minutes: int = 5
    time_digest_centroids: int = 32

    def __post_init__(self) -> None:
        # the packed wire image gives service ids 16 bits and sketch keys
        # 24 (zipkin_tpu.tpu.columnar.fuse_columns); a config beyond that
        # would silently alias ids on device
        from zipkin_tpu.tpu.columnar import MAX_WIRE_KEYS, MAX_WIRE_SERVICES

        if self.max_services > MAX_WIRE_SERVICES:
            raise ValueError(
                f"max_services ({self.max_services}) exceeds the packed "
                f"wire limit ({MAX_WIRE_SERVICES})"
            )
        if self.max_keys > MAX_WIRE_KEYS:
            raise ValueError(
                f"max_keys ({self.max_keys}) exceeds the packed wire "
                f"limit ({MAX_WIRE_KEYS})"
            )

    @property
    def hll_rows(self) -> int:
        return self.max_services + 1

    @property
    def global_hll_row(self) -> int:
        return self.max_services

    @property
    def timetier_enabled(self) -> bool:
        return self.time_buckets > 0

    @property
    def rollup_segment(self) -> int:
        """Ring slots linked+invalidated per rollup: half the ring. The
        host triggers a rollup before writes since the last one exceed
        this, so no valid span is ever overwritten unrolled."""
        return self.ring_capacity // 2


class AggState(NamedTuple):
    hll: jnp.ndarray  # u8 [services+1, m]
    hist: jnp.ndarray  # u32 [keys, BUCKETS] (all-time)
    hist_t: jnp.ndarray  # u32 [T, keys, BUCKETS] (time slices)
    hist_t_epoch: jnp.ndarray  # i32 [T] — absolute slice epoch held, -1 empty
    digest: jnp.ndarray  # f32 [keys, C, 2]
    pend_key: jnp.ndarray  # i32 [P] — -1 = empty lane
    pend_val: jnp.ndarray  # f32 [P]
    pend_pos: jnp.ndarray  # i32 scalar
    # ring columns, all [R]
    r_trace_h: jnp.ndarray  # u32
    r_tl0: jnp.ndarray  # u32
    r_tl1: jnp.ndarray  # u32
    r_s0: jnp.ndarray  # u32
    r_s1: jnp.ndarray  # u32
    r_p0: jnp.ndarray  # u32
    r_p1: jnp.ndarray  # u32
    r_shared: jnp.ndarray  # bool
    r_kind: jnp.ndarray  # i32
    r_svc: jnp.ndarray  # i32
    r_rsvc: jnp.ndarray  # i32
    r_err: jnp.ndarray  # bool
    r_ts_min: jnp.ndarray  # u32
    r_valid: jnp.ndarray  # bool
    # tail-sampling verdict per ring lane (meaningful iff config.sampling;
    # all-False otherwise). The ring itself retains 100% of spans — link
    # joins need whole-trace context — r_keep only RECORDS the device
    # verdict so the parity oracle can read it back.
    r_keep: jnp.ndarray  # bool
    # rolled lanes already contributed their links to the rollup matrices:
    # they no longer EMIT edges but stay JOIN-VISIBLE (a live child can
    # still resolve a rolled parent until the lane is overwritten)
    r_rolled: jnp.ndarray  # bool
    ring_pos: jnp.ndarray  # i32 scalar
    # time-bucketed link rollups (daily dependency-table analog)
    rollup_calls: jnp.ndarray  # u32 [D, S, S]
    rollup_errs: jnp.ndarray  # u32 [D, S, S]
    rollup_epoch: jnp.ndarray  # i32 [D] — absolute bucket held, -1 empty
    # time-disaggregated sketch tier (current-bucket leaves): W ring
    # slots of time_bucket_minutes each; slot = bucket_epoch % W,
    # recycled on a newer epoch exactly like hist_t slices. tb_epoch is
    # the ONE shared epoch array — a recycle wipes every tt plane for
    # the slot. tb_digest holds the compact per-key query digest
    # (time_digest_centroids clusters); pend_ep tags each pending digest
    # point with its bucket epoch so the flush can fold points into
    # their bucket slots segmented by (slot, key).
    tb_epoch: jnp.ndarray  # i32 [W] — absolute bucket epoch held, -1 empty
    tb_hll: jnp.ndarray  # u8 [W, services+1, m]
    tb_digest: jnp.ndarray  # f32 [W, keys, Cw, 2]
    tb_calls: jnp.ndarray  # u32 [W, S, S]
    tb_errs: jnp.ndarray  # u32 [W, S, S]
    pend_ep: jnp.ndarray  # i32 [P] — bucket epoch per pending point, -1 empty
    # published tail-sampling tables (zipkin_tpu/sampling). These are
    # HOST-AUTHORITATIVE: the controller computes them on host and
    # publishes by swapping the leaves under the aggregator lock; the
    # device only READS them, so every shard holds identical content and
    # verdicts are a pure function of (span, published tables) — the
    # foundation of host/device verdict parity and crash-resume replay.
    s_rate: jnp.ndarray  # u32 [S] — per-service keep rate, 65536 = keep all
    s_tail: jnp.ndarray  # u32 [K] — per-key tail-latency threshold (µs)
    s_link: jnp.ndarray  # u32 [S, S] — published (svc, rsvc) edge counts
    # persistent incremental link context (ops/delta_linker.py): the
    # sorted join-union order over the ring, its run decomposition, the
    # per-run first-wins candidates restricted to lanes that cannot be
    # overwritten before the next advance, and the resolved tree at the
    # last advance. Advanced at rollup cadence. No read uses them: a
    # fresh dependency read resolves from scratch (ingest.fresh_link_context).
    ctx_order: jnp.ndarray  # i32 [2R] union index per sorted position
    ctx_keys: jnp.ndarray  # u32 [4, 2R] sort-key snapshot per position
    ctx_rid_c: jnp.ndarray  # i32 [2R] coarse run id (1-based)
    ctx_rid_f: jnp.ndarray  # i32 [2R] fine run id (1-based)
    ctx_inv: jnp.ndarray  # i32 [2R] sorted position of union entry u
    ctx_safe_sh: jnp.ndarray  # i32 [2R] first safe shared lane per run
    ctx_safe_ns: jnp.ndarray  # i32 [2R] first safe non-shared lane per run
    ctx_safe_fsh: jnp.ndarray  # i32 [2R] first safe shared lane, fine run
    ctx_parent: jnp.ndarray  # i32 [R] resolved parent lane at the advance
    ctx_anc: jnp.ndarray  # i32 [R] nearest-RPC-ancestor lane at the advance
    ctx_root: jnp.ndarray  # bool [R] parent chain reaches a root
    ctx_pos: jnp.ndarray  # i32 scalar — covered-watermark lane cursor
    ctx_delta: jnp.ndarray  # i32 scalar — lanes written since the advance
    counters: jnp.ndarray  # u32 [NUM_COUNTERS]


def init_state(config: AggConfig) -> AggState:
    r = config.ring_capacity
    z32 = jnp.zeros((r,), jnp.uint32)
    return AggState(
        hll=jnp.zeros((config.hll_rows, 1 << config.hll_precision), jnp.uint8),
        hist=jnp.zeros((config.max_keys, histogram.BUCKETS), jnp.uint32),
        hist_t=jnp.zeros(
            (config.hist_slices, config.max_keys, histogram.BUCKETS), jnp.uint32
        ),
        hist_t_epoch=jnp.full((config.hist_slices,), -1, jnp.int32),
        digest=jnp.zeros((config.max_keys, config.digest_centroids, 2), jnp.float32),
        pend_key=jnp.full((config.digest_buffer,), -1, jnp.int32),
        pend_val=jnp.zeros((config.digest_buffer,), jnp.float32),
        pend_pos=jnp.zeros((), jnp.int32),
        r_trace_h=z32, r_tl0=z32, r_tl1=z32, r_s0=z32, r_s1=z32,
        r_p0=z32, r_p1=z32,
        r_shared=jnp.zeros((r,), bool),
        r_kind=jnp.zeros((r,), jnp.int32),
        r_svc=jnp.zeros((r,), jnp.int32),
        r_rsvc=jnp.zeros((r,), jnp.int32),
        r_err=jnp.zeros((r,), bool),
        r_ts_min=z32,
        r_valid=jnp.zeros((r,), bool),
        r_keep=jnp.zeros((r,), bool),
        r_rolled=jnp.zeros((r,), bool),
        ring_pos=jnp.zeros((), jnp.int32),
        rollup_calls=jnp.zeros(
            (config.link_buckets, config.max_services, config.max_services),
            jnp.uint32,
        ),
        rollup_errs=jnp.zeros(
            (config.link_buckets, config.max_services, config.max_services),
            jnp.uint32,
        ),
        rollup_epoch=jnp.full((config.link_buckets,), -1, jnp.int32),
        tb_epoch=jnp.full((config.time_buckets,), -1, jnp.int32),
        tb_hll=jnp.zeros(
            (config.time_buckets, config.hll_rows, 1 << config.hll_precision),
            jnp.uint8,
        ),
        tb_digest=jnp.zeros(
            (
                config.time_buckets,
                config.max_keys,
                config.time_digest_centroids,
                2,
            ),
            jnp.float32,
        ),
        tb_calls=jnp.zeros(
            (config.time_buckets, config.max_services, config.max_services),
            jnp.uint32,
        ),
        tb_errs=jnp.zeros(
            (config.time_buckets, config.max_services, config.max_services),
            jnp.uint32,
        ),
        pend_ep=jnp.full(
            (config.digest_buffer if config.time_buckets else 0,),
            -1,
            jnp.int32,
        ),
        # sampler tables boot in "keep everything" posture: max rate, an
        # unreachable tail threshold, and zero published link counts
        # (every edge rare). The controller publishes real tables later.
        s_rate=jnp.full((config.max_services,), 65536, jnp.uint32),
        s_tail=jnp.full((config.max_keys,), 0xFFFFFFFF, jnp.uint32),
        s_link=jnp.zeros(
            (config.max_services, config.max_services), jnp.uint32
        ),
        # incremental link ctx of the all-invalid ring (every union key
        # 0xFFFFFFFF -> identity order is validly sorted, one run, no
        # candidates) — exactly what an advance over the empty ring
        # yields, so the first real advance is indistinguishable from
        # one that followed an earlier empty advance
        ctx_order=jnp.arange(2 * r, dtype=jnp.int32),
        ctx_keys=jnp.full((4, 2 * r), 0xFFFFFFFF, jnp.uint32),
        ctx_rid_c=jnp.ones((2 * r,), jnp.int32),
        ctx_rid_f=jnp.ones((2 * r,), jnp.int32),
        ctx_inv=jnp.arange(2 * r, dtype=jnp.int32),
        ctx_safe_sh=jnp.full((2 * r,), -1, jnp.int32),
        ctx_safe_ns=jnp.full((2 * r,), -1, jnp.int32),
        ctx_safe_fsh=jnp.full((2 * r,), -1, jnp.int32),
        ctx_parent=jnp.full((r,), -1, jnp.int32),
        ctx_anc=jnp.full((r,), -1, jnp.int32),
        ctx_root=jnp.ones((r,), bool),
        ctx_pos=jnp.zeros((), jnp.int32),
        ctx_delta=jnp.zeros((), jnp.int32),
        counters=jnp.zeros((NUM_COUNTERS,), jnp.uint32),
    )


def state_bytes(config: AggConfig) -> int:
    """HBM footprint of one shard's state (for capacity planning)."""
    import numpy as np

    s = init_state(config)
    return int(sum(np.prod(a.shape) * a.dtype.itemsize for a in s))
