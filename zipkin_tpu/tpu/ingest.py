"""The jit'd ingest step and read kernels over :class:`AggState`.

This is the device half of the reference's hot path (SURVEY.md §3.2):
where ``Collector.acceptSpans`` fans bytes out to storage writers, the TPU
tier applies one pure function ``state, batch -> state`` per shard —
sketch scatter updates + a circular-buffer append — compiled once by XLA
and re-used for every batch (static shapes via the packer's bucketed
padding). Reads are pure functions over the same state.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from zipkin_tpu.ops import delta_linker, hashing, histogram, hll, linker, tdigest
from zipkin_tpu.tpu.columnar import SpanColumns
from zipkin_tpu.tpu.state import (
    CTR_BATCHES,
    CTR_ERRORS,
    CTR_SAMPLED_DROPPED,
    CTR_SAMPLED_KEPT,
    CTR_SPANS,
    CTR_WITH_DURATION,
    AggConfig,
    AggState,
)


def lane_bucket(lanes: int, pad_to_multiple: int, cap: int) -> int:  # zt-dispatch-critical: shape-bucket pick on the coalesced dispatch path
    """Static-shape bucket for a coalesced multi-chunk lane count.

    The coalesced dispatch path (span ring, mp_ingest) concatenates N
    routed chunk images into one device batch; feeding the raw sum of
    lane counts to the jitted step would compile a fresh program per
    distinct sum (the ZT03 failure mode). Instead the sum is rounded up
    a doubling ladder anchored at the packer's pad multiple —
    ``pad * 2^k`` capped at the aggregator's lane ceiling — so at most
    ``log2(cap/pad)+1`` programs ever exist. Pad lanes are zero
    (valid=0), the same safe-pad invariant the router relies on.
    """
    b = max(1, int(pad_to_multiple))
    while b < lanes:  # zt-lint: disable=ZT09 — doubling ladder: ≤ log2(cap/pad)+1 trips, independent of span count
        b *= 2
    return min(b, cap) if cap >= lanes else b


def ingest_step(config: AggConfig, state: AggState, batch: SpanColumns) -> AggState:
    """Fold one columnar batch into the aggregate state (pure, jit-safe).

    Donate ``state`` at the jit boundary: updates are in-place in HBM.
    """
    valid = batch.valid
    n = valid.shape[0]

    # --- HLL: distinct traces per service + globally --------------------
    h = hashing.fmix32(batch.trace_h)
    svc_rows = jnp.clip(batch.svc, 0, config.max_services - 1)
    new_hll = hll.update(state.hll, svc_rows, h, valid & (batch.svc > 0))
    new_hll = hll.update(
        new_hll, jnp.full((n,), config.global_hll_row, jnp.int32), h, valid
    )

    # --- latency sketches per (service, spanName) key -------------------
    has_dur = valid & batch.has_dur
    new_hist = histogram.update(state.hist, batch.key, batch.dur, has_dur)
    new_hist_t, new_hist_t_epoch = _hist_slice_update(config, state, batch, has_dur)
    # t-digest: append to the pending buffer; compaction is a SEPARATE
    # program the host dispatches when the buffer would overflow (it
    # tracks pend_pos exactly — every shard advances by the same padded
    # lane count). Round 1 embedded the decision as a lax.cond here; the
    # cond forced full copies of both pending buffers through the
    # conditional every step (~45% of step device time in the r2 profile
    # capture, PROFILE_r02.md) even when no flush ran.
    pend_key, pend_val, pend_pos, pend_ep = _digest_append(
        config, state, batch.key, batch.dur.astype(jnp.float32), has_dur,
        batch.ts_min,
    )

    # --- time-disaggregated current-bucket leaves (tpu/timetier.py) -----
    # Same epoch-ring recycle as the histogram slices, over bucket epochs
    # of time_bucket_minutes: the HLL registers update here per step; the
    # bucketed digest points ride the SAME pending buffer (pend_ep tags
    # each point's bucket) and fold at flush; the edge counts fold at
    # rollup cadence. config.time_buckets is trace-static, so the
    # disabled tier compiles the exact pre-tier step.
    tt = {}
    if config.timetier_enabled:
        w_tt = config.time_buckets
        g = jnp.uint32(config.time_bucket_minutes)
        ep_tt = (batch.ts_min // g).astype(jnp.int32)
        sl_tt = ep_tt % w_tt
        tb_epoch, tb_wipe, tb_keep = _recycle_slots(
            w_tt, state.tb_epoch, sl_tt, ep_tt, valid
        )
        tb_hll = jnp.where(tb_wipe[:, None, None], jnp.uint8(0), state.tb_hll)
        rows_flat = sl_tt * config.hll_rows + svc_rows
        flat = tb_hll.reshape(w_tt * config.hll_rows, -1)
        flat = hll.update(flat, rows_flat, h, tb_keep & (batch.svc > 0))
        flat = hll.update(
            flat, sl_tt * config.hll_rows + config.global_hll_row, h, tb_keep
        )
        tt = dict(
            tb_epoch=tb_epoch,
            tb_hll=flat.reshape(tb_hll.shape),
            tb_digest=jnp.where(
                tb_wipe[:, None, None, None], 0.0, state.tb_digest
            ),
            tb_calls=jnp.where(
                tb_wipe[:, None, None], jnp.uint32(0), state.tb_calls
            ),
            tb_errs=jnp.where(
                tb_wipe[:, None, None], jnp.uint32(0), state.tb_errs
            ),
            pend_ep=pend_ep,
        )

    # --- ring append (valid lanes first, advance by live count) ---------
    order = jnp.argsort(~valid)  # stable: valid lanes keep order, pad sinks
    live = jnp.sum(valid.astype(jnp.int32))
    lane = jnp.arange(n, dtype=jnp.int32)
    # pad lanes scatter out of range and are DROPPED — they must not
    # clobber retained ring slots ahead of the cursor.
    pos = jnp.where(
        lane < live,
        (state.ring_pos + lane) % config.ring_capacity,
        config.ring_capacity,
    )

    def put(col, new):
        return col.at[pos].set(new[order], mode="drop")

    # --- tail-sampling verdicts (static off by default) -----------------
    # config.sampling is trace-static, so the off path compiles the exact
    # pre-sampling step: r_keep untouched, counters 5/6 never written.
    counters = (
        state.counters.at[CTR_SPANS].add(live.astype(jnp.uint32))
        .at[CTR_WITH_DURATION].add(jnp.sum(has_dur).astype(jnp.uint32))
        .at[CTR_ERRORS].add(jnp.sum(valid & batch.err).astype(jnp.uint32))
        .at[CTR_BATCHES].add(1)
    )
    r_keep = state.r_keep
    if config.sampling:
        from zipkin_tpu.sampling.device import device_verdict

        keep = device_verdict(
            batch.trace_h, batch.svc, batch.rsvc, batch.key,
            batch.dur, batch.has_dur, batch.err, valid,
            state.s_rate, state.s_tail, state.s_link,
            config.sample_rare_min,
        )
        n_keep = jnp.sum(keep).astype(jnp.uint32)
        counters = (
            counters.at[CTR_SAMPLED_KEPT].add(n_keep)
            .at[CTR_SAMPLED_DROPPED].add(live.astype(jnp.uint32) - n_keep)
        )
        r_keep = put(state.r_keep, keep)

    new_state = state._replace(
        hll=new_hll,
        hist=new_hist,
        hist_t=new_hist_t,
        hist_t_epoch=new_hist_t_epoch,
        pend_key=pend_key,
        pend_val=pend_val,
        pend_pos=pend_pos,
        r_trace_h=put(state.r_trace_h, batch.trace_h),
        r_tl0=put(state.r_tl0, batch.tl0),
        r_tl1=put(state.r_tl1, batch.tl1),
        r_s0=put(state.r_s0, batch.s0),
        r_s1=put(state.r_s1, batch.s1),
        r_p0=put(state.r_p0, batch.p0),
        r_p1=put(state.r_p1, batch.p1),
        r_shared=put(state.r_shared, batch.shared),
        r_kind=put(state.r_kind, batch.kind),
        r_svc=put(state.r_svc, batch.svc),
        r_rsvc=put(state.r_rsvc, batch.rsvc),
        r_err=put(state.r_err, batch.err),
        r_ts_min=put(state.r_ts_min, batch.ts_min),
        r_valid=put(state.r_valid, valid),
        r_keep=r_keep,
        r_rolled=put(state.r_rolled, jnp.zeros((n,), bool)),
        ring_pos=(state.ring_pos + live) % config.ring_capacity,
        # incremental-ctx watermark: the rollup cadence guarantees this
        # never exceeds rollup_segment before the next ctx advance
        ctx_delta=state.ctx_delta + live,
        counters=counters,
        **tt,
    )
    return new_state


def _recycle_slots(num_slots, stored_epoch, slot, ep, active):
    """Epoch-ring slot management shared by the histogram slices and link
    rollups: a slot is zeroed ("wiped") when a batch brings it a NEWER
    absolute epoch; items older than what the slot then holds are dropped
    from the windowed view — the late-arrival semantics of the
    reference's daily indices, where a late span lands in an old daily
    index that queries no longer scan (SURVEY.md §2.3).

    Returns (new_epoch [D], wipe [D] bool, keep [n] bool).
    """
    slot_ep = jnp.full((num_slots,), -1, jnp.int32).at[slot].max(
        jnp.where(active, ep, -1)
    )
    new_epoch = jnp.maximum(stored_epoch, slot_ep)
    wipe = slot_ep > stored_epoch
    keep = active & (ep == new_epoch[slot])
    return new_epoch, wipe, keep


def _slots_in_window(epoch, lo_unit, hi_unit):
    """[D] bool: which epoch-ring slots hold a bucket intersecting the
    window (whole-bucket granularity, as when the reference merges the
    daily rollup rows of a lookback — SURVEY.md §3.5)."""
    return (epoch >= 0) & (epoch >= lo_unit) & (epoch <= hi_unit)


def _masked_slot_sum(sel, arr):
    """Sum [D, ...] over the slots selected by ``sel`` (dtype-preserving)."""
    return jnp.sum(jnp.where(sel[:, None, None], arr, 0), axis=0).astype(arr.dtype)


def _hist_slice_update(config: AggConfig, state: AggState, batch, has_dur):
    """Fold durations into the time-sliced histograms (slice = epoch % T,
    recycled per :func:`_recycle_slots`; the all-time ``hist`` keeps every
    count regardless)."""
    t = config.hist_slices
    ep = (batch.ts_min // jnp.uint32(config.hist_slice_minutes)).astype(jnp.int32)
    sl = ep % t
    new_epoch, wipe, ok = _recycle_slots(t, state.hist_t_epoch, sl, ep, has_dur)
    hist_t = jnp.where(wipe[:, None, None], jnp.uint32(0), state.hist_t)
    b = histogram.bucket_of(batch.dur)
    k = jnp.clip(batch.key.astype(jnp.int32), 0, config.max_keys - 1)
    hist_t = hist_t.at[sl, k, b].add(ok.astype(jnp.uint32))
    return hist_t, new_epoch


def _flush_pending_digest(
    config: AggConfig, digest: jnp.ndarray, pend_key: jnp.ndarray, pend_val: jnp.ndarray
):
    """Compact the whole pending buffer into the digests (empty lanes have
    key -1 -> weight 0).

    Split formulation: sort ONLY the pending points into per-key partial
    digests, then fold them in with a row-parallel merge. The round-1
    joint formulation re-sorted all K*C existing centroid lanes every
    flush and dominated the ingest step (66% of device time in the
    profiler capture — see PROFILE_r02.md)."""
    w = (pend_key >= 0).astype(jnp.float32)
    keys = jnp.clip(pend_key, 0, config.max_keys - 1)
    partial = tdigest.compact_points(
        keys, pend_val, w, config.max_keys, config.digest_centroids
    )
    return tdigest.row_merge(digest, partial)


def _digest_append(config: AggConfig, state: AggState, key, val, has_dur,
                   ts_min=None):
    """Append the batch's (key, value) points to the pending ring.

    PRECONDITION (host-enforced, see ShardedAggregator.ingest): pend_pos +
    n <= digest_buffer — dynamic_update_slice CLAMPS out-of-range starts,
    which would silently overwrite the buffer tail."""
    batch_key = jnp.where(has_dur, jnp.clip(key, 0, config.max_keys - 1), -1)
    pos = state.pend_pos
    pk = jax.lax.dynamic_update_slice(state.pend_key, batch_key, (pos,))
    pv = jax.lax.dynamic_update_slice(state.pend_val, val, (pos,))
    pe = state.pend_ep
    if config.timetier_enabled and ts_min is not None:
        # bucket-epoch tag per point; validity is re-checked against
        # tb_epoch at FLUSH time, so a slot recycled between append and
        # flush drops its stale points (late-arrival semantics)
        ep = (ts_min // jnp.uint32(config.time_bucket_minutes)).astype(
            jnp.int32
        )
        pe = jax.lax.dynamic_update_slice(
            pe, jnp.where(has_dur, ep, -1), (pos,)
        )
    return pk, pv, pos + key.shape[0], pe


def _flush_pending_tt(config: AggConfig, tb_epoch, tb_digest, pend_key,
                      pend_val, pend_ep):
    """Fold the pending points into their bucket slots' compact digests:
    one compact_points segmented by (bucket slot, key) over W*K rows,
    then a row-parallel merge — the same split formulation as the
    cumulative flush. Points whose bucket epoch no longer matches the
    slot (recycled since append, or older than the ring) fold nowhere.
    Per-slot segmentation keeps bucket contents independent of the other
    epochs sharing the buffer — the property the windowed bit-identity
    oracle (tests/test_timetier.py) rests on."""
    w_tt = config.time_buckets
    k = config.max_keys
    cw = config.time_digest_centroids
    sl = jnp.where(pend_ep >= 0, pend_ep % w_tt, 0)
    live = (pend_ep >= 0) & (pend_key >= 0) & (tb_epoch[sl] == pend_ep)
    w = live.astype(jnp.float32)
    keys = jnp.clip(pend_key, 0, k - 1)
    partial = tdigest.compact_points(
        sl * k + keys, pend_val, w, w_tt * k, cw
    )
    merged = tdigest.row_merge(tb_digest.reshape(w_tt * k, cw, 2), partial)
    return merged.reshape(w_tt, k, cw, 2)


def flush_digest(config: AggConfig, state: AggState) -> AggState:
    """Reader-side flush: fold any pending values so digest reads are
    complete. Pure; call via jit before quantile queries."""
    d = _flush_pending_digest(config, state.digest, state.pend_key, state.pend_val)
    tt = {}
    if config.timetier_enabled:
        tt = dict(
            tb_digest=_flush_pending_tt(
                config, state.tb_epoch, state.tb_digest,
                state.pend_key, state.pend_val, state.pend_ep,
            ),
            pend_ep=jnp.full_like(state.pend_ep, -1),
        )
    return state._replace(
        digest=d,
        pend_key=jnp.full_like(state.pend_key, -1),
        pend_val=jnp.zeros_like(state.pend_val),
        pend_pos=jnp.zeros_like(state.pend_pos),
        **tt,
    )


def ring_link_input(state: AggState) -> linker.LinkInput:
    """View the retention ring as a link window (all valid lanes; use the
    ``emit`` mask of link_window/link_edges for time filtering so parent
    joins keep full-ring context)."""
    r = state.r_valid.shape[0]
    lane = jnp.arange(r, dtype=jnp.int32)
    return linker.LinkInput(
        trace_h=state.r_trace_h, tl0=state.r_tl0, tl1=state.r_tl1,
        s0=state.r_s0, s1=state.r_s1, p0=state.r_p0, p1=state.r_p1,
        shared=state.r_shared, kind=state.r_kind,
        svc=state.r_svc, rsvc=state.r_rsvc, err=state.r_err,
        valid=state.r_valid,
        # age since the cursor: the cursor's own lane is the OLDEST live
        # span (next to be overwritten), so tie-breaks stay first-wins in
        # true insertion order across ring wraps (ADVICE r2)
        seq=(lane - state.ring_pos) % r,
    )


def ctx_struct(state: AggState) -> delta_linker.CtxStruct:
    """View the persistent incremental-ctx leaves as a CtxStruct."""
    return delta_linker.CtxStruct(
        order=state.ctx_order, keys=state.ctx_keys,
        rid_c=state.ctx_rid_c, rid_f=state.ctx_rid_f, inv=state.ctx_inv,
        safe_sh=state.ctx_safe_sh, safe_ns=state.ctx_safe_ns,
        safe_fsh=state.ctx_safe_fsh,
        pos=state.ctx_pos, delta=state.ctx_delta,
    )


def fresh_link_context(state: AggState) -> linker.LinkContext:
    """The fresh-read link context, resolved from scratch over the ring:
    one stable sort of the 2R-lane join union, the three-channel run-min
    ladder, the ancestor chase and the rules (``linker.link_context``).
    The persistent ``ctx_*`` leaves are the roll-up's and are not read
    here."""
    return linker.link_context(ring_link_input(state))


def rollup_step(config: AggConfig, state: AggState) -> AggState:
    """Link the half-ring the cursor will overwrite next and fold the
    edges into per-time-bucket rollup matrices, then mark those lanes
    rolled (they stop emitting edges but stay JOIN-VISIBLE until
    physically overwritten, so live children still resolve them).

    This is the reference's zipkin-dependencies batch job run on-device
    ahead of eviction (SURVEY.md §3.5): links are attributed to the
    bucket of the child span's timestamp (like the daily ``dependency``
    rows), parents resolve against the FULL ring (whole-trace context),
    and a bucket slot is recycled — zeroed — when a newer epoch folds in.
    The host dispatches this before writes since the last rollup exceed
    ``config.rollup_segment`` (see ShardedAggregator.ingest), so no valid
    span is ever overwritten without its links being preserved.

    This is also where the persistent link ctx ADVANCES — one sort of
    the whole join union (delta_linker.advance) gives the rollup's emit
    context and the rebuilt ctx leaves alike.
    """
    x = ring_link_input(state)
    # x.seq is age-since-cursor: the lanes the cursor will overwrite next
    # are exactly the oldest rollup_segment ranks
    to_roll = state.r_valid & ~state.r_rolled & (x.seq < config.rollup_segment)

    bm = jnp.uint32(config.bucket_minutes)
    bucket_abs = (state.r_ts_min // bm).astype(jnp.int32)
    d = config.link_buckets
    slot = bucket_abs % d
    new_epoch, wipe, emit = _recycle_slots(
        d, state.rollup_epoch, slot, bucket_abs, to_roll
    )

    cs, parent, anc, root_ok, ctx = delta_linker.advance(
        x, ctx_struct(state), config.rollup_segment
    )
    calls_d, errs_d = linker.emit_links_bucketed(
        ctx, slot, d, emit, config.max_services
    )
    rollup_calls = jnp.where(wipe[:, None, None], jnp.uint32(0), state.rollup_calls)
    rollup_errs = jnp.where(wipe[:, None, None], jnp.uint32(0), state.rollup_errs)
    # time-tier edge fold: the SAME resolve emits a second bucketed pass
    # at time_bucket_minutes granularity into the current-bucket edge
    # planes. Slot recycle for these lives in the ingest step (shared
    # tb_epoch); a lane whose bucket epoch is no longer current in its
    # slot emits nowhere (late-arrival semantics).
    tt = {}
    if config.timetier_enabled:
        w_tt = config.time_buckets
        g = jnp.uint32(config.time_bucket_minutes)
        ep_tt = (state.r_ts_min // g).astype(jnp.int32)
        sl_tt = ep_tt % w_tt
        emit_tt = to_roll & (state.tb_epoch[sl_tt] == ep_tt)
        calls_tt, errs_tt = linker.emit_links_bucketed(
            ctx, sl_tt, w_tt, emit_tt, config.max_services
        )
        tt = dict(
            tb_calls=state.tb_calls + calls_tt,
            tb_errs=state.tb_errs + errs_tt,
        )
    return state._replace(
        rollup_calls=rollup_calls + calls_d,
        rollup_errs=rollup_errs + errs_d,
        rollup_epoch=new_epoch,
        **tt,
        # rolled lanes stop emitting but stay join-visible (r_valid keeps
        # them in the parent table until the cursor overwrites them) — so
        # a live child written shortly after its parent rolled still
        # resolves full tree context at query or rollup time
        r_rolled=state.r_rolled | to_roll,
        ctx_order=cs.order, ctx_keys=cs.keys,
        ctx_rid_c=cs.rid_c, ctx_rid_f=cs.rid_f, ctx_inv=cs.inv,
        ctx_safe_sh=cs.safe_sh, ctx_safe_ns=cs.safe_ns,
        ctx_safe_fsh=cs.safe_fsh,
        ctx_parent=parent, ctx_anc=anc, ctx_root=root_ok,
        ctx_pos=cs.pos, ctx_delta=cs.delta,
    )


def rolled_links(
    config: AggConfig, state: AggState, ts_lo: jnp.ndarray, ts_hi: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(calls, errors) [S, S] u32 from the PRE-AGGREGATED rollup buckets
    alone — the exact read the reference serves from its daily
    ``dependency`` table (SURVEY.md §3.5 "read PRE-AGGREGATED daily link
    rows ... merge days"). Correct whenever the window cannot intersect
    any span resident in the live ring (the host tracks the resident
    time range); costs a masked slot-sum instead of the ring lexsort."""
    bm = config.bucket_minutes
    lo_b = (ts_lo // jnp.uint32(bm)).astype(jnp.int32)
    hi_b = (ts_hi // jnp.uint32(bm)).astype(jnp.int32)
    sel = _slots_in_window(state.rollup_epoch, lo_b, hi_b)
    return (
        _masked_slot_sum(sel, state.rollup_calls),
        _masked_slot_sum(sel, state.rollup_errs),
    )


def dependency_links(
    config: AggConfig,
    state: AggState,
    ts_lo: jnp.ndarray,
    ts_hi: jnp.ndarray,
    ctx: linker.LinkContext = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(calls, errors) [S, S] u32 over [ts_lo, ts_hi] epoch minutes —
    live-ring links merged with the rolled-up buckets in the window (the
    reference's "merge days: sum callCount/errorCount", SURVEY.md §3.5).

    Pass a precomputed ``ctx`` (see linker.link_context) to skip the
    ring-sort half — the aggregator caches one per state version.
    """
    if ctx is None:
        ctx = fresh_link_context(state)
    in_window = (state.r_ts_min >= ts_lo) & (state.r_ts_min <= ts_hi)
    calls, errors = linker.emit_links(
        ctx, state.r_valid & ~state.r_rolled & in_window, config.max_services
    )
    rc, re = rolled_links(config, state, ts_lo, ts_hi)
    return calls + rc, errors + re


def key_quantiles(state: AggState, qs: jnp.ndarray) -> jnp.ndarray:
    """[keys, Q] latency quantiles from the histograms."""
    return histogram.quantile(state.hist, qs)


def windowed_hist(
    config: AggConfig, state: AggState, ts_lo: jnp.ndarray, ts_hi: jnp.ndarray
) -> jnp.ndarray:
    """[keys, BUCKETS] histogram summed over the time slices intersecting
    [ts_lo, ts_hi] epoch minutes — the windowed-percentile source.
    Coverage is the most recent T*slice_minutes; older windows return
    empty rows (callers fall back to the all-time ``hist``)."""
    sm = config.hist_slice_minutes
    lo_e = (ts_lo // jnp.uint32(sm)).astype(jnp.int32)
    hi_e = (ts_hi // jnp.uint32(sm)).astype(jnp.int32)
    sel = _slots_in_window(state.hist_t_epoch, lo_e, hi_e)
    return _masked_slot_sum(sel, state.hist_t)


def key_quantiles_digest(state: AggState, qs: jnp.ndarray) -> jnp.ndarray:
    """[keys, Q] latency quantiles from the t-digests (tighter tails)."""
    return tdigest.quantile(state.digest, qs)


def cardinalities(state: AggState) -> jnp.ndarray:
    """[services+1] estimated distinct traces (last row = global)."""
    return hll.estimate(state.hll)


def tt_sketches(
    config: AggConfig,
    state: AggState,
    lo_ep: jnp.ndarray,
    hi_ep: jnp.ndarray,
    ctx: linker.LinkContext = None,
):
    """Read the time-tier slots whose bucket epoch falls in
    ``[lo_ep, hi_ep]`` as ONE mergeable per-shard part:

    - ``epoch`` [W] i32: the slot epochs (host computes actual coverage),
    - ``regs``  [S+1, m] u8: register-max over selected slots,
    - ``digest`` [K, Cw, 2] f32: row-parallel recluster of the selected
      slots' compact digests (one row_merge over the W*Cw concat, the
      merge_many idiom),
    - ``calls``/``errs`` [S, S] u32: the same live-ring + rolled split
      as :func:`dependency_links`, at bucket granularity — un-rolled
      ring lanes whose bucket epoch falls in the range emit through
      ``ctx`` (pass the cached one to skip the ring-sort half), rolled
      lanes come from the ``tb_calls``/``tb_errs`` planes. Every lane
      is in exactly one of the two, so the split is exact.

    The sealer calls this with lo==hi (one bucket -> one segment); the
    windowed query path calls it for the unsealed suffix. The tier's
    query side never touches archive scans (lint rule ZT07 fences it)."""
    sel = _slots_in_window(state.tb_epoch, lo_ep, hi_ep)
    regs = jnp.max(
        jnp.where(sel[:, None, None], state.tb_hll, jnp.uint8(0)), axis=0
    )
    d = state.tb_digest  # [W, K, Cw, 2]
    w_tt, k, cw, _ = d.shape
    dm = jnp.stack(
        [d[..., 0], jnp.where(sel[:, None, None], d[..., 1], 0.0)], axis=-1
    )
    all_c = jnp.moveaxis(dm, 0, 1).reshape(k, w_tt * cw, 2)
    digest = tdigest.row_merge(jnp.zeros((k, cw, 2), jnp.float32), all_c)
    if ctx is None:
        ctx = fresh_link_context(state)
    g = jnp.uint32(config.time_bucket_minutes)
    ep_lane = (state.r_ts_min // g).astype(jnp.int32)
    in_w = (ep_lane >= lo_ep) & (ep_lane <= hi_ep)
    live_c, live_e = linker.emit_links(
        ctx, state.r_valid & ~state.r_rolled & in_w, config.max_services
    )
    calls = live_c + _masked_slot_sum(sel, state.tb_calls)
    errs = live_e + _masked_slot_sum(sel, state.tb_errs)
    return state.tb_epoch, regs, digest, calls, errs


@functools.lru_cache(maxsize=None)
def jit_ingest(config: AggConfig):
    """The compiled single-shard ingest step with state donation.

    Cached per config (AggConfig is a hashable NamedTuple): callers may
    treat this as cheap — repeat calls return the SAME jitted wrapper,
    so its trace cache persists instead of recompiling per call."""
    return jax.jit(
        functools.partial(ingest_step, config), donate_argnums=(0,)
    )
