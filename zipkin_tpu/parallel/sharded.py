"""ShardedAggregator: the SPMD aggregate tier over a device mesh.

State lives as one pytree with a leading ``[shards, ...]`` axis sharded
over the mesh; ingest is ``shard_map`` of the pure single-shard step;
reads merge with ``psum``/``pmax`` over ICI (SURVEY.md §2.8 mapping
table). Runs identically on one real TPU chip (mesh of 1), a v5e-8, or
the 8-virtual-device CPU backend used in CI.
"""

from __future__ import annotations

import functools
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from zipkin_tpu import obs, readpack
from zipkin_tpu.obs import critpath
from zipkin_tpu.obs import device as obs_device
from zipkin_tpu.obs import querytrace
from zipkin_tpu.ops import linker as dlink
from zipkin_tpu.tpu import ingest as ing
from zipkin_tpu.tpu.columnar import (
    SpanColumns,
    concat_remap,
    fuse_columns,
    remap_fused,
    route_columns,
    route_fused,
)
from zipkin_tpu.tpu.state import AggConfig, AggState, init_state

SHARD_AXIS = "shard"


def pmax_registers(regs: jnp.ndarray) -> jnp.ndarray:
    """Cross-shard HLL register max (u8), widened to u32 for the
    collective. A u8 all-reduce-max over four v5e chips returned
    registers BELOW the true max (chip run, PR 22: 1,143,723 of
    2,099,200 random u8 cells wrong in the bare collective; on real
    state, per-service estimates at 0.35-0.6 of the truth), where the
    u32 form was exact. A CPU mesh never shows it, so
    tests/test_chip_compile.py keeps u8 all-reduces out of the compiled
    read programs and ``chip_smoke.py --chips 4`` checks the answers."""
    return jax.lax.pmax(regs.astype(jnp.uint32), SHARD_AXIS).astype(jnp.uint8)


def unfuse_columns(fz: jnp.ndarray) -> SpanColumns:
    """Device-side inverse of :func:`zipkin_tpu.tpu.columnar.fuse_columns`:
    ``[11, n] u32`` packed wire image -> typed SpanColumns. The unpack is
    shifts/masks XLA fuses into the consuming ops — the 44 B/span wire
    (vs 68 B unpacked) is purely fewer host->device bytes."""
    sr = fz[9]
    kf = fz[10]
    u = jnp.uint32
    i32 = lambda a: a.astype(jnp.int32)
    return SpanColumns(
        trace_h=fz[0], tl0=fz[1], tl1=fz[2],
        s0=fz[3], s1=fz[4], p0=fz[5], p1=fz[6],
        shared=(kf & u(2)) != 0,
        kind=i32((kf >> u(4)) & u(7)),
        svc=i32(sr >> u(16)), rsvc=i32(sr & u(0xFFFF)),
        key=i32(kf >> u(8)),
        err=(kf & u(4)) != 0,
        dur=fz[7],
        has_dur=(kf & u(8)) != 0,
        ts_min=fz[8],
        valid=(kf & u(1)) != 0,
    )


@functools.lru_cache(maxsize=8)
def _compiled_programs(config: AggConfig, mesh: Mesh):
    """Compiled SPMD programs shared by every aggregator with the same
    (config, mesh) — constructing a store must not trigger recompiles."""
    n_shards = int(np.prod(mesh.devices.shape))
    sharding = NamedSharding(mesh, P(SHARD_AXIS))

    def _packed(inner, name):
        """Production wire variant of a read program: the same device
        program with a readpack.pack stage fused on the end, so the
        whole answer is ONE 1-D uint32 buffer — one device→host pull
        per query, however many logical outputs. ``name`` keeps the
        XPlane program attribution (jit_spmd_*) stable across rounds."""

        def wrapper(*args):
            out = inner(*args)
            if not isinstance(out, tuple):
                out = (out,)
            return readpack.pack(out)

        wrapper.__name__ = name
        return jax.jit(wrapper)

    # shard_map's static varying-manual-axes check can't see through
    # all_gather+row_merge, so every program tracing those passes
    # check_vma=False

    def _init() -> AggState:
        # broadcast the REAL initial leaves, not zeros: init_state's
        # sentinels are load-bearing (link_perm must be a permutation,
        # pend_key/epoch slots use -1 = empty; a zero-filled pend_key
        # even let an early flush fold phantom key-0 points)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_shards,) + a.shape),
            init_state(config),
        )

    init = jax.jit(_init, out_shardings=sharding)

    one = functools.partial(ing.ingest_step, config)

    def _make_step(pre_flush: bool, pre_rollup: bool):
        """Step program variants with the periodic maintenance programs
        FUSED in front: when the host decides a flush and/or rollup is
        due, dispatching one combined program instead of two or three
        saves a dispatch's fixed cost each time."""

        def spmd(state: AggState, fused: jnp.ndarray) -> AggState:
            squeeze = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            s = squeeze(state)
            if pre_flush:
                s = ing.flush_digest(config, s)
            if pre_rollup:
                s = ing.rollup_step(config, s)
            return expand(one(s, unfuse_columns(fused[0])))

        return jax.jit(
            shard_map(
                spmd,
                mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    step_variants = {
        (flush, rollup): _make_step(flush, rollup)
        for flush in (False, True)
        for rollup in (False, True)
    }

    def spmd_link_ctx(state: AggState):
        """The window-independent half of a dependency query, via the
        INCREMENTAL delta formulation (ops/delta_linker.py): persistent
        ctx rebuilt by the rollup's one full-union sort + a read-time
        sort of only the since-rollup delta segment — bit-identical to
        the from-scratch linker.link_context oracle (fuzzed in
        tests/test_incremental_ctx)
        without the full-ring union sort that cost ~29.6 ms of the
        41.3 ms r5 fresh read."""
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        ctx = ing.fresh_link_context(config, s)
        return jax.tree_util.tree_map(lambda a: a[None], ctx)

    link_ctx = jax.jit(
        shard_map(
            spmd_link_ctx, mesh=mesh,
            in_specs=(P(SHARD_AXIS),), out_specs=P(SHARD_AXIS),
            check_vma=False,
        )
    )

    def spmd_links(ctx, state: AggState, ts_lo, ts_hi):
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        c = jax.tree_util.tree_map(lambda a: a[0], ctx)
        calls, errors = ing.dependency_links(config, s, ts_lo, ts_hi, ctx=c)
        return jax.lax.psum(calls, SHARD_AXIS), jax.lax.psum(errors, SHARD_AXIS)

    links_sm = shard_map(
        spmd_links,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    links = _packed(links_sm, "spmd_links")

    def spmd_merge(state: AggState):
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        return (
            jax.lax.psum(s.hist, SHARD_AXIS),
            pmax_registers(s.hll),
            jax.lax.psum(s.counters, SHARD_AXIS),
        )

    merge_sm = shard_map(
        spmd_merge, mesh=mesh, in_specs=(P(SHARD_AXIS),), out_specs=P(),
        check_vma=False,
    )
    merge = _packed(merge_sm, "spmd_merge")

    def spmd_flush(state: AggState) -> AggState:
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        out = ing.flush_digest(config, s)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    flush = jax.jit(
        shard_map(
            spmd_flush, mesh=mesh, in_specs=(P(SHARD_AXIS),),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ),
        donate_argnums=(0,),
    )

    def spmd_rollup(state: AggState) -> AggState:
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        out = ing.rollup_step(config, s)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    rollup = jax.jit(
        shard_map(
            spmd_rollup, mesh=mesh, in_specs=(P(SHARD_AXIS),),
            out_specs=P(SHARD_AXIS), check_vma=False,
        ),
        donate_argnums=(0,),
    )

    def spmd_whist(state: AggState, ts_lo, ts_hi):
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        return jax.lax.psum(
            ing.windowed_hist(config, s, ts_lo, ts_hi), SHARD_AXIS
        )

    whist_sm = shard_map(
        spmd_whist, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()), out_specs=P(), check_vma=False,
    )
    whist = _packed(whist_sm, "spmd_whist")

    def _gather_recluster(local):
        """all_gather per-shard [K, C, 2] digests over ICI and recluster
        row-wise into one [K, C, 2] — shared by every digest read so the
        pending and no-pending variants stay bit-identical.

        On a ONE-shard mesh this is the identity: a shard's digest rows
        are already complete mean-sorted digests, and the r3 SLO capture
        showed the pointless self-merge was most of the 35.2 ms
        single-shard percentile read (VERDICT r3 order 7). n_shards is a
        trace-time constant, so each mesh compiles the right program."""
        from zipkin_tpu.ops import tdigest

        if n_shards == 1:
            return local
        allc = jax.lax.all_gather(local, SHARD_AXIS)  # [D, K, C, 2]
        d = allc.shape[0]
        k = config.max_keys
        c = config.digest_centroids
        flat = jnp.moveaxis(allc, 0, 1).reshape(k, d * c, 2)
        return tdigest.row_merge(jnp.zeros((k, c, 2), jnp.float32), flat)

    def _merged_digest_of(state: AggState):
        """Complete cross-shard digest as a PURE READ: fold each shard's
        pending points into a local partial (state untouched — a
        percentile query no longer stalls ingest with a flush-on-read),
        then gather + recluster."""
        from zipkin_tpu.ops import tdigest

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        w = (s.pend_key >= 0).astype(jnp.float32)
        keys = jnp.clip(s.pend_key, 0, config.max_keys - 1)
        partial = tdigest.compact_points(
            keys, s.pend_val, w, config.max_keys, config.digest_centroids
        )
        local = tdigest.row_merge(s.digest, partial)  # [K, C, 2]
        return _gather_recluster(local)

    digest_read_sm = shard_map(
        _merged_digest_of, mesh=mesh, in_specs=(P(SHARD_AXIS),),
        out_specs=P(), check_vma=False,
    )
    digest_read = _packed(digest_read_sm, "spmd_digest_read")

    # quantile reads computed ON DEVICE: one dispatch, [K, Q] + [K] counts
    # to the host instead of the dense [K, BUCKETS] histogram (28MB at
    # default shapes — the round-1 query path pulled it per request)
    def spmd_quant_digest(state: AggState, qs):
        from zipkin_tpu.ops import histogram, tdigest

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        merged = _merged_digest_of(state)
        counts = jax.lax.psum(histogram.total_count(s.hist), SHARD_AXIS)
        return tdigest.quantile(merged, qs), counts

    quant_digest_sm = shard_map(
        spmd_quant_digest, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P()), out_specs=P(), check_vma=False,
    )
    quant_digest = _packed(quant_digest_sm, "spmd_quant_digest")

    def spmd_quant_digest_nopend(state: AggState, qs):
        """Digest quantiles when the host KNOWS the pending buffer is
        empty (right after a flush): skips the 131k-lane pending fold —
        the one cost above the dispatch floor in the r2 query profile."""
        from zipkin_tpu.ops import histogram, tdigest

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        merged = _gather_recluster(s.digest)
        counts = jax.lax.psum(histogram.total_count(s.hist), SHARD_AXIS)
        return tdigest.quantile(merged, qs), counts

    quant_digest_nopend_sm = shard_map(
        spmd_quant_digest_nopend, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P()), out_specs=P(), check_vma=False,
    )
    quant_digest_nopend = _packed(
        quant_digest_nopend_sm, "spmd_quant_digest_nopend"
    )

    def spmd_quant_hist(state: AggState, qs):
        from zipkin_tpu.ops import histogram

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        merged = jax.lax.psum(s.hist, SHARD_AXIS)
        return histogram.quantile(merged, qs), histogram.total_count(merged)

    quant_hist_sm = shard_map(
        spmd_quant_hist, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P()), out_specs=P(), check_vma=False,
    )
    quant_hist = _packed(quant_hist_sm, "spmd_quant_hist")

    def spmd_quant_whist(state: AggState, ts_lo, ts_hi, qs):
        from zipkin_tpu.ops import histogram

        merged = spmd_whist(state, ts_lo, ts_hi)
        return histogram.quantile(merged, qs), histogram.total_count(merged)

    quant_whist_sm = shard_map(
        spmd_quant_whist, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(), P()), out_specs=P(), check_vma=False,
    )
    quant_whist = _packed(quant_whist_sm, "spmd_quant_whist")

    # dependency edges compacted ON DEVICE: the first E nonzero cells of
    # the merged [S, S] call matrix via prefix-sum compaction (cumsum +
    # searchsorted + gather), so a query ships 3 small [E] vectors to
    # the host instead of two dense matrices. Equivalent to the r4
    # top-E-by-calls: both exist to ship EVERY nonzero edge when they
    # fit in E — and when they don't, every returned slot is live, which
    # is exactly the host's dense-fallback trigger (store.py). The
    # compaction measured 0.88 ms vs top_k's 1.09 at [1024^2] (r5 A/B).
    num_edges = min(4096, config.max_services * config.max_services)

    def _edge_topk(calls, errors):
        cf = jax.lax.psum(calls, SHARD_AXIS).reshape(-1)
        ef = jax.lax.psum(errors, SHARD_AXIS).reshape(-1)
        nz = (cf > 0).astype(jnp.int32)
        cs = jnp.cumsum(nz)
        pos = jnp.searchsorted(
            cs, jnp.arange(1, num_edges + 1, dtype=jnp.int32), side="left"
        )
        pos = jnp.clip(pos, 0, cf.shape[0] - 1)
        have = jnp.arange(num_edges) < cs[-1]
        return (
            jnp.where(have, pos, 0).astype(jnp.int32),
            jnp.where(have, cf[pos], 0),
            jnp.where(have, ef[pos], 0),
        )

    def spmd_edges(ctx, state: AggState, ts_lo, ts_hi):
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        c = jax.tree_util.tree_map(lambda a: a[0], ctx)
        calls, errors = ing.dependency_links(config, s, ts_lo, ts_hi, ctx=c)
        return _edge_topk(calls, errors)

    edges_sm = shard_map(
        spmd_edges, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()), out_specs=P(),
        check_vma=False,
    )
    edges = _packed(edges_sm, "spmd_edges")

    def spmd_edges_fresh(ctxless_state: AggState, ts_lo, ts_hi):
        """The FRESH dependency read: first query after a write. One
        dispatch computes the link context — via the incremental DELTA
        formulation: persistent ctx + a sort of only the since-rollup
        segment (ops/delta_linker.py); the full-ring sort runs at rollup
        cadence, never here — plus the windowed top-E edges, and
        returns both so the host caches the
        ctx for follow-up windows. This program GATES the <50 ms query
        SLO with no amortized exclusions (VERDICT r3 order 1): r3 paid
        145.8 ms + 6.8 ms in two dispatches, r5's from-scratch fused
        read 41.3 ms, the delta read only the since-rollup segment."""
        s = jax.tree_util.tree_map(lambda a: a[0], ctxless_state)
        c = ing.fresh_link_context(config, s)
        calls, errors = ing.dependency_links(config, s, ts_lo, ts_hi, ctx=c)
        ctx_out = jax.tree_util.tree_map(lambda a: a[None], c)
        return ctx_out, _edge_topk(calls, errors)

    edges_fresh_sm = shard_map(
        spmd_edges_fresh, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()),
        out_specs=(P(SHARD_AXIS), P()),
        check_vma=False,
    )

    def _edges_fresh_packed(state, ts_lo, ts_hi):
        # ctx stays ON DEVICE (it primes the per-version cache; only the
        # edge triple crosses to the host, as one packed buffer)
        ctx, triple = edges_fresh_sm(state, ts_lo, ts_hi)
        return ctx, readpack.pack(triple)

    _edges_fresh_packed.__name__ = "spmd_edges_fresh"
    edges_fresh = jax.jit(_edges_fresh_packed)

    def spmd_edges_rolled(state: AggState, ts_lo, ts_hi):
        """Edges from the rollup buckets ALONE — no ring sort, no link
        context: the read path for windows the host proves cannot touch
        the live ring (the reference's read-the-daily-table path)."""
        s = jax.tree_util.tree_map(lambda a: a[0], state)
        calls, errors = ing.rolled_links(config, s, ts_lo, ts_hi)
        return _edge_topk(calls, errors)

    edges_rolled_sm = shard_map(
        spmd_edges_rolled, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P()), out_specs=P(), check_vma=False,
    )
    edges_rolled = _packed(edges_rolled_sm, "spmd_edges_rolled")
    # device-side state clone for snapshots: runs in ms on device, so
    # the aggregator lock is held only for the dispatch — the host pull
    # of the copy (~state_bytes over the transport) happens lock-free
    # while ingest continues against the original buffers
    snap_copy = jax.jit(
        lambda s: jax.tree_util.tree_map(jnp.copy, s),
        out_shardings=sharding,
    )

    def spmd_card(state: AggState):
        from zipkin_tpu.ops import hll as hll_ops

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        merged = pmax_registers(s.hll)
        return hll_ops.estimate(merged)  # [S+1] f32 — KBs, not registers

    card_sm = shard_map(
        spmd_card, mesh=mesh, in_specs=(P(SHARD_AXIS),), out_specs=P()
    )
    card = _packed(card_sm, "spmd_card")

    def spmd_overview(state: AggState, qs):
        """The coalesced sketch read: digest quantiles + per-key counts
        + HLL cardinalities in ONE dispatch — what the server's
        /api/v2/tpu/overview endpoint serves, replacing three separate
        aggregator dispatches (and three HTTP round trips from the UI
        sketch page) with one packed pull. Assumes the pending digest
        buffer is empty (the host flushes first, as the digest quantile
        path already does)."""
        from zipkin_tpu.ops import histogram, tdigest
        from zipkin_tpu.ops import hll as hll_ops

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        merged = _gather_recluster(s.digest)
        counts = jax.lax.psum(histogram.total_count(s.hist), SHARD_AXIS)
        est = hll_ops.estimate(pmax_registers(s.hll))
        return tdigest.quantile(merged, qs), counts, est

    overview_sm = shard_map(
        spmd_overview, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P()), out_specs=P(), check_vma=False,
    )
    overview = _packed(overview_sm, "spmd_overview")

    def spmd_ttread(ctx, state: AggState, lo_ep, hi_ep):
        """Time-tier windowed read (tpu/timetier.py): each shard masks
        its current-bucket leaves to the ``[lo_ep, hi_ep]`` bucket range
        (edges ride the cached link ``ctx`` for the live-ring half),
        then one cross-shard merge per sketch family — register-max for
        HLL, row-parallel recluster for the digests (the
        _gather_recluster idiom at the tier's own centroid count), psum
        for the edge counts. The sealer calls it with lo==hi to freeze
        one bucket into a segment; queries call it for the unsealed
        suffix of a window. ONE dispatch, one packed pull."""
        from zipkin_tpu.ops import tdigest

        s = jax.tree_util.tree_map(lambda a: a[0], state)
        c = jax.tree_util.tree_map(lambda a: a[0], ctx)
        ep, regs, digest, calls, errs = ing.tt_sketches(
            config, s, lo_ep, hi_ep, ctx=c
        )
        if n_shards > 1:
            ep = jax.lax.pmax(ep, SHARD_AXIS)
            regs = pmax_registers(regs)
            allc = jax.lax.all_gather(digest, SHARD_AXIS)
            d = allc.shape[0]
            k = config.max_keys
            cw = config.time_digest_centroids
            flat = jnp.moveaxis(allc, 0, 1).reshape(k, d * cw, 2)
            digest = tdigest.row_merge(
                jnp.zeros((k, cw, 2), jnp.float32), flat
            )
            calls = jax.lax.psum(calls, SHARD_AXIS)
            errs = jax.lax.psum(errs, SHARD_AXIS)
        return ep, regs, digest, calls, errs

    ttread_sm = shard_map(
        spmd_ttread, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(), P()), out_specs=P(),
        check_vma=False,
    )
    ttread = _packed(ttread_sm, "spmd_ttread")

    # the pre-pack (multi-output) jits, kept compilable for the packed
    # wire parity tests and tests/test_chip_compile.py — jit is
    # lazy, so an un-dispatched raw variant costs nothing
    raw = {
        "merge": jax.jit(merge_sm),
        "links": jax.jit(links_sm),
        "whist": jax.jit(whist_sm),
        "digest_read": jax.jit(digest_read_sm),
        "edges": jax.jit(edges_sm),
        "edges_fresh": jax.jit(edges_fresh_sm),
        "edges_rolled": jax.jit(edges_rolled_sm),
        "quant_digest": jax.jit(quant_digest_sm),
        "quant_digest_nopend": jax.jit(quant_digest_nopend_sm),
        "quant_hist": jax.jit(quant_hist_sm),
        "quant_whist": jax.jit(quant_whist_sm),
        "card": jax.jit(card_sm),
        "overview": jax.jit(overview_sm),
        "ttread": jax.jit(ttread_sm),
    }
    # Device-program observatory (obs/device.py): every dispatchable
    # program counts calls/compiles through a thin wrapper — the runtime
    # recompile detector. The raw variants stay unwrapped (parity-test
    # only, never dispatched in production).
    # Programs that return the state hand it on to the next call, which
    # donates it: their completion token is a marker behind the call
    # (token="state"); the step variants are counted apart, plain
    # against maintenance-fused.
    _w = obs_device.OBSERVATORY.wrap
    init = _w("spmd_init", init, token="state")
    step_variants = {
        k: _w("spmd_step" + ("_flush" if k[0] else "")
              + ("_rollup" if k[1] else ""), v, token="state",
              step="fused" if any(k) else "plain")
        for k, v in step_variants.items()
    }
    links = _w("spmd_links", links)
    merge = _w("spmd_merge", merge)
    flush = _w("spmd_flush", flush, token="state")
    rollup = _w("spmd_rollup", rollup, token="state")
    whist = _w("spmd_whist", whist)
    digest_read = _w("spmd_digest_read", digest_read)
    edges = _w("spmd_edges", edges)
    edges_fresh = _w("spmd_edges_fresh", edges_fresh)
    edges_rolled = _w("spmd_edges_rolled", edges_rolled)
    quant_digest = _w("spmd_quant_digest", quant_digest)
    quant_digest_nopend = _w("spmd_quant_digest_nopend", quant_digest_nopend)
    quant_hist = _w("spmd_quant_hist", quant_hist)
    quant_whist = _w("spmd_quant_whist", quant_whist)
    card = _w("spmd_card", card)
    link_ctx = _w("spmd_link_ctx", link_ctx)
    snap_copy = _w("spmd_snap_copy", snap_copy)
    overview = _w("spmd_overview", overview)
    ttread = _w("spmd_ttread", ttread)
    return (
        init, step_variants, links, merge, flush, rollup, whist, digest_read,
        edges, edges_fresh, edges_rolled, quant_digest, quant_digest_nopend,
        quant_hist, quant_whist, card, link_ctx, snap_copy, sharding,
        overview, ttread, raw,
    )


class ShardedAggregator:
    """Owns the sharded state and the compiled SPMD update/read programs."""

    def __init__(self, config: AggConfig, mesh: Optional[Mesh] = None) -> None:
        if mesh is None:
            from zipkin_tpu.parallel.mesh import make_mesh

            mesh = make_mesh()
        self.config = config
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape))
        (
            init, self._step_variants, self._links, self._merge, self._flush,
            self._rollup, self._whist, self._digest_read, self._edges,
            self._edges_fresh, self._edges_rolled, self._quant_digest,
            self._quant_digest_nopend, self._quant_hist, self._quant_whist,
            self._card, self._link_ctx, self._snap_copy, self._sharding,
            self._overview, self._ttread, self._raw,
        ) = _compiled_programs(config, mesh)
        self._step = self._step_variants[(False, False)]
        # device-resident LinkContext for the current write_version (the
        # sorted/joined half of dependency queries, reused across windows)
        self._ctx_cache = (-1, None)
        self.state: AggState = init()
        # Exact host-side counters: the device counters are u32 and wrap
        # after ~4.3B spans (~72 min at the north-star rate); these are the
        # source of truth for the API and snapshot resume markers.
        self.host_counters = {
            "spans": 0,
            "spansWithDuration": 0,
            "spansWithError": 0,
            "batches": 0,
            # tail-sampling verdict tallies (exact, host-counted at the
            # ingest_fused funnel; 0 when the sampling tier is off)
            "sampledKept": 0,
            "sampledDropped": 0,
        }
        # Guards every touch of self.state. Ingest DONATES the state
        # buffers, so a reader racing a step would touch deleted arrays
        # (or, for the flush-on-read path, silently drop a batch by
        # overwriting the step's result). Reentrant: read paths nest.
        # Instrumented (ISSUE 12): outermost wait/hold land in the
        # contention ledger and the query_lock_wait stage — the number
        # ROADMAP item 4's epoch-published read mirror must drive to
        # zero. Uncontended acquires take a non-blocking fast path.
        self.lock = querytrace.InstrumentedRLock(name="agg")
        # Host mirror of the per-shard digest pend_pos (identical on every
        # shard: each advances by the same padded lane count per step).
        # The host dispatches the flush program when the next batch would
        # overflow — keeping the decision out of the step removed a
        # lax.cond that copied both pending buffers every step (~45% of
        # step device time, PROFILE_r02.md).
        self._pend_lanes = 0
        # Lanes written since the last link rollup. When the next batch
        # would push this past rollup_segment (= R/2), the rollup program
        # runs first: it links + invalidates the half-ring ahead of the
        # cursor, so spans are never overwritten before their links are
        # folded into the time-bucketed rollup matrices.
        self._lanes_since_rollup = 0
        # Ring-RESIDENT time range: (ts_lo, ts_hi, cursor-before) per
        # batch still physically in some shard's ring — popped only when
        # EVERY shard has advanced ring_capacity past the batch's start
        # (per-shard cursors, since routing skews live counts). A query
        # window disjoint from every entry cannot touch any ring span —
        # live OR rolled-but-join-visible — so it is served from the
        # rollup matrices alone (no ring sort; VERDICT r2 order 4).
        # Batches with unknown range are recorded as covering everything.
        from collections import deque

        self._resident: "deque" = deque()
        self._shard_cursor = np.zeros(self.n_shards, np.int64)
        # Highest bucket epoch any ingested span has touched (host
        # mirror, from the same ts_range the resident ledger uses). The
        # time-tier sealer (tpu/timetier.py) seals epochs strictly below
        # this — the max-epoch bucket is the UNSEALED current bucket.
        self._tt_max_epoch = -1
        self.read_stats = {
            "rolled_only_reads": 0,
            "ctx_reads": 0,
            # device→host pulls made on behalf of queries (should track
            # query count 1:1 — the one-transfer invariant; pinned by
            # tests/test_readpack.py)
            "host_transfers": 0,
        }
        # Incremental link-ctx maintenance telemetry (/metrics gauges
        # ctxDeltaLanes / ctxMaintenanceMs / ctxAdvances): advances run
        # fused inside the rollup dispatch, so the ms figure is the
        # DEVICE time of the last ctx-advancing program (the roll-up-
        # fused step, or rollup_now's), as the completion clock of
        # obs/device.py saw it run: see _maintenance_done.
        self.ctx_stats = {"ctx_advances": 0, "ctx_maintenance_ms": 0.0}
        # write-ahead log seam (tpu/wal.py): when set, every fused batch
        # is logged inside the state lock and wal_seq records the last
        # sequence folded into self.state — snapshots read both under
        # the same lock so replay-from-snapshot is exact.
        self.wal_hook: Optional[callable] = None
        self.wal_seq = 0
        # tail-sampling gate (zipkin_tpu/sampling.HostSampler): when
        # installed, every batch through ingest_fused is scored with the
        # bit-exact host reference — observations feed the controller,
        # and the WAL persists only the KEPT lanes. Installed by the
        # storage adapter AFTER boot restore/replay (replayed batches are
        # already compacted and must not be re-observed).
        self.sampler = None
        # Monotonic counter bumped on EVERY state mutation (step, flush,
        # rollup, restore) — the read-cache invalidation key. Batch count
        # alone is not enough: rollup_now()/flush change query-visible
        # state without a new batch.
        self.write_version = 0

    # -- write path ------------------------------------------------------

    def ingest(self, cols: SpanColumns) -> None:
        """Route one host batch across shards and fold it in (the batch
        ships as one fused u32 array — one transfer, not 17)."""
        live_ts = cols.ts_min[cols.valid]
        with obs.span("route"):
            routed = route_fused(cols, self.n_shards)
        self.ingest_fused(
            routed,
            n_spans=int(cols.valid.sum()),
            n_dur=int((cols.valid & cols.has_dur).sum()),
            n_err=int((cols.valid & cols.err).sum()),
            ts_range=(
                (int(live_ts.min()), int(live_ts.max()))
                if live_ts.size
                else (0, 0)
            ),
        )

    def ingest_fused(
        self,
        fused: np.ndarray,
        n_spans: int,
        n_dur: int,
        n_err: int,
        ts_range=None,
    ) -> None:  # zt-dispatch-critical: the per-chunk device entry point — one device_put + one fused jitted step under the state lock
        """Fold one PRE-ROUTED packed wire image ``[shards, 11, per]``
        into the state — the entry point for producers that already hold
        the wire format (the multi-process parse tier, WAL replay). The
        caller supplies the live/duration/error counts (they are cheap
        at pack time and the image would need unpacking to recount)."""
        lanes = int(fused.shape[-1])  # per-shard lane count (padded)
        if lanes > min(self.config.digest_buffer, self.config.rollup_segment):
            raise ValueError(
                f"batch of {lanes} lanes/shard exceeds digest_buffer "
                f"({self.config.digest_buffer}) or rollup_segment "
                f"({self.config.rollup_segment}); chunk before ingest"
            )
        device_batch = jax.device_put(fused, self._sharding)
        # the write path's own wait for the lock (query_lock_wait and the
        # ledger count every outermost wait, the readers' with it); about
        # 0 where this thread holds the lock already (WAL replay)
        lock_wait = obs.span("ingest_lock_wait").start()
        with self.lock:
            lock_wait.stop()
            # contention-ledger attribution: this hold is the write path
            self.lock.relabel("ingest_fused")
            # fold due maintenance into ONE fused dispatch with the step
            need_flush = self._pend_lanes + lanes > self.config.digest_buffer
            need_rollup = (
                self._lanes_since_rollup + lanes > self.config.rollup_segment
            )
            step = self._step_variants[(need_flush, need_rollup)]
            seq = self.host_counters["batches"] + 1
            obs_device.tag_next(
                lanes, seq, self._maintenance_done if need_rollup else None
            )
            # the enqueue wall of an async dispatch: what ingest pays on
            # the host. The step's time ON the device is the completion
            # clock's (obs/device.py), under the same seq.
            with obs.span("device_dispatch", variant=step.__name__,
                          lanes=lanes, seq=seq) as dispatch:
                # the variant in the event's NAME too: every step is
                # jit_spmd on the device plane, and a trace reader that
                # prints names alone (chipbench/xplane.py --dump) shows it
                with dispatch.child(step.__name__):
                    self.state = step(self.state, device_batch)
            if need_flush:
                self._pend_lanes = 0
            if need_rollup:
                self._lanes_since_rollup = 0
                self.ctx_stats["ctx_advances"] += 1
            self._pend_lanes += lanes
            self._lanes_since_rollup += lanes
            self.write_version += 1
            c = self.host_counters
            c["spans"] += n_spans
            c["spansWithDuration"] += n_dur
            c["spansWithError"] += n_err
            c["batches"] += 1
            # resident-range bookkeeping (see __init__); unknown range =
            # (0, 2^32-1), conservatively intersecting every window
            lo, hi = ts_range if ts_range is not None else (0, (1 << 32) - 1)
            if (
                n_spans > 0
                and self.config.timetier_enabled
                and ts_range is not None
            ):
                self._tt_max_epoch = max(
                    self._tt_max_epoch,
                    int(hi) // self.config.time_bucket_minutes,
                )
            if n_spans > 0:
                # per-shard live counts straight from the wire image's
                # valid bits (row 10 bit 0) — the ring cursor advances by
                # live count, not padded lanes
                live_per_shard = (fused[:, 10, :] & 1).sum(
                    axis=1, dtype=np.int64
                )
                self._resident.append((lo, hi, self._shard_cursor.copy()))
                self._shard_cursor = self._shard_cursor + live_per_shard
            # zt-lint: disable=ZT09 — per RETIRED resident range (ring-wrap bookkeeping, one pop per overwritten batch), never per span
            while self._resident and (
                (self._shard_cursor - self._resident[0][2]).min()
                >= self.config.ring_capacity
            ):
                self._resident.popleft()
            if self.sampler is not None:
                # host reference verdicts over the SAME published tables
                # the device step just read (both under this lock, so a
                # controller publish can never straddle a batch): exact
                # tallies for the controller + kept-lane WAL compaction
                keep2d = self.sampler.verdict_fused(fused)
                seen_b, kept_b = self.sampler.observe(fused, keep2d)
                c["sampledKept"] += kept_b
                c["sampledDropped"] += seen_b - kept_b
                if self.wal_hook is not None:
                    compacted = self.sampler.compact_fused(fused, keep2d)  # zt-lint: disable=ZT09 — per SHARD (mesh-sized) fancy-index gather; the per-lane work inside is vectorized
                    if compacted is not None:
                        cf, k_spans, k_dur, k_err, k_ts = compacted
                        self.wal_seq = self.wal_hook(
                            cf, k_spans, k_dur, k_err, k_ts,
                            # pre-compaction tallies: replay restores the
                            # exact host counters from these (the record
                            # itself only carries the kept lanes)
                            extra={
                                "seen": seen_b, "kept": kept_b,
                                "seen_dur": n_dur, "seen_err": n_err,
                            },
                        )
            elif self.wal_hook is not None:
                self.wal_seq = self.wal_hook(
                    fused, n_spans, n_dur, n_err, ts_range
                )

    def _maintenance_done(self, device_s: float) -> None:  # zt-lint: disable=ZT04 — runs on the completion clock's thread, which must never take self.lock; one GIL-atomic store into a debug gauge
        """A roll-up (fused into a step, or ``rollup_now``'s own) has run:
        its time ON the device, from the completion clock's thread. With
        the device observatory off nothing reports, and the gauge stays
        where it was."""
        self.ctx_stats["ctx_maintenance_ms"] = device_s * 1000.0
        obs.record_relayed("rollup", device_s)

    @property
    def lane_cap(self) -> int:
        """Hard per-shard lane ceiling of one fused batch — the coalesce
        planner packs groups up to this (see :meth:`ingest_fused`)."""
        return min(self.config.digest_buffer, self.config.rollup_segment)

    def ingest_fused_multi(
        self,
        parts,
        n_spans: int,
        n_dur: int,
        n_err: int,
        ts_range=None,
        pad_to_multiple: int = 256,
    ) -> None:  # zt-dispatch-critical: the coalesced multi-chunk device entry point
        """Coalesce N pre-routed chunk images into ONE device batch and
        fold it with a single jitted step — the span-ring dispatcher's
        multi-chunk entry point (one ``concat_remap`` + one dispatch +
        one WAL record for the whole run of ready slots).

        ``parts`` is a sequence of ``(fused, svc_map, key_map)``; each
        ``fused`` may be a zero-copy ring-slot view — the gather into
        the freshly allocated bucket image is the only copy it takes,
        and the remap happens on the copied lanes. The bucket ladder
        (:func:`zipkin_tpu.tpu.ingest.lane_bucket`) keeps the device
        shape static across coalesce depths (ZT03). The counts are the
        caller's sums over the member chunks; pad lanes are zero
        (valid=0) so the image replays through :meth:`ingest_fused`
        bit-identically to having ingested it live.
        """
        if len(parts) == 1:
            # degenerate run: identical to the per-chunk path (remap in
            # place, no bucket padding) so coalesce_max=1 stays
            # byte-for-byte the pre-ring WAL stream
            fused, svc_map, key_map = parts[0]
            t0 = time.perf_counter()
            t0_ns = time.perf_counter_ns()
            remap_fused(fused, svc_map, key_map)
            obs.record("mp_lut_remap", time.perf_counter() - t0)
            critpath.stamp_active(
                critpath.SEG_LUT_REMAP, t0_ns, time.perf_counter_ns()
            )
            self.ingest_fused(fused, n_spans, n_dur, n_err, ts_range)
            return
        # zt-lint: disable=ZT09 — per CHUNK of the coalesced run (bounded
        # by coalesce_max), integer shape reads only
        total = sum(int(p[0].shape[-1]) for p in parts)
        cap = self.lane_cap
        if total > cap:
            raise ValueError(
                f"coalesced run of {total} lanes/shard exceeds the lane "
                f"cap ({cap}); the planner must split the run"
            )
        bucket = ing.lane_bucket(total, pad_to_multiple, cap)
        shards, rows = parts[0][0].shape[0], parts[0][0].shape[1]
        t0 = time.perf_counter()
        t0_ns = time.perf_counter_ns()
        out = np.zeros((shards, rows, bucket), np.uint32)
        concat_remap(parts, out)
        obs.record("coalesce", time.perf_counter() - t0)
        critpath.stamp_active(
            critpath.SEG_COALESCE, t0_ns, time.perf_counter_ns()
        )
        self.ingest_fused(out, n_spans, n_dur, n_err, ts_range)

    def set_sampler_tables(
        self, rate: np.ndarray, tail: np.ndarray, link: np.ndarray
    ) -> None:
        """Publish host-computed sampling tables to the device leaves.

        NOT a compiled program: a zero-copy leaf swap (device_put of the
        replicated tables + ``_replace``) under the state lock, so the
        next step — and every later one until the next publish — scores
        against exactly these tables. Publishing changes no query-visible
        answer (verdicts only gate retention), so write_version stays."""
        bt = lambda a: jax.device_put(
            np.ascontiguousarray(
                np.broadcast_to(a, (self.n_shards,) + a.shape)
            ),
            self._sharding,
        )
        with self.lock:
            self.state = self.state._replace(
                s_rate=bt(rate), s_tail=bt(tail), s_link=bt(link)
            )

    # -- read path (merged across shards over ICI) -----------------------
    #
    # Every entrypoint below ends in exactly ONE device→host transfer:
    # the compiled program packs its outputs into a single ZPK1 buffer on
    # device (readpack.pack fused as the program's last stage) and
    # self._pull makes the one counted jax.device_get. Do not add bare
    # np.asarray pulls here — ZT-lint rejects them (rules ZT01/ZT02,
    # gated in tier-1 by tests/test_lint_clean.py).

    def _pull(self, packed) -> list:  # zt-lint: disable=ZT04 — every caller holds self.lock (contract in the docstring); read_stats has no separate lock
        """THE query-path device→host pull: one counted transfer, then
        zero-copy unpack of the ZPK1 sections (callers hold the lock)."""
        self.read_stats["host_transfers"] += 1
        if querytrace.active() is not None:
            # device_wall: dispatch-done -> result device-ready, split
            # out from the transfer below so the per-query waterfall
            # separates device time from wire time. Only a traced query
            # pays the extra block (it is free on the CPU backend and
            # the pull would block identically anyway).
            t0 = time.perf_counter_ns()
            # zt-lint: disable=ZT06 — measurement IS the contract: only
            # a traced query takes this branch, and the pull below would
            # block identically; the split makes device wall observable
            packed = jax.block_until_ready(packed)
            querytrace.stamp_active(
                querytrace.QSEG_DEVICE_WALL, t0, time.perf_counter_ns()
            )
        return readpack.pull(packed)

    def merged_sketches(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(hist [K,B], hll [S+1,m], counters) merged over all shards."""
        with self.lock:
            hist, hll_regs, counters = self._pull(self._merge(self.state))
            return hist, hll_regs, counters

    def _link_context_cached(self):  # zt-lint: disable=ZT04 — callers (dependency_matrices, dependency_edges) hold self.lock around the cache check+fill
        """Device LinkContext for the current state (callers hold lock)."""
        version = self.write_version
        if self._ctx_cache[0] != version:
            t0 = time.perf_counter()
            self._ctx_cache = (version, self._link_ctx(self.state))
            obs.record("ctx_advance", time.perf_counter() - t0)
        return self._ctx_cache[1]

    def dependency_matrices(
        self, ts_lo_min: int, ts_hi_min: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        with self.lock:
            calls, errors = self._pull(self._links(
                self._link_context_cached(), self.state,
                jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min),
            ))
            return calls, errors

    def merged_digest(self) -> np.ndarray:
        """[K, C, 2] t-digest merged across shards in ONE device dispatch.

        A PURE READ: each shard's pending points are folded into a
        temporary partial on device (state untouched — no flush-on-read
        stalling ingest), shards all_gather over ICI, one row-parallel
        recluster, and only the final [K, C, 2] crosses to the host.
        """
        with self.lock:
            (digest,) = self._pull(self._digest_read(self.state))
            return digest

    def window_fully_rolled(self, ts_lo_min: int, ts_hi_min: int) -> bool:
        """True when no ring-resident span's timestamp can fall in the
        window — the rollup matrices alone then answer it exactly."""
        with self.lock:
            return all(
                ts_hi_min < lo or ts_lo_min > hi
                for lo, hi, _ in self._resident
            )

    def dependency_edges(
        self, ts_lo_min: int, ts_hi_min: int
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(flat_index, calls, errors) [E] — the nonzero-dominant cells of
        the merged link matrix, compacted on device (top-E by call count)
        so a dependency query pulls ~KBs, not two dense [S, S] matrices.

        Windows that cannot intersect any ring-resident span skip the
        link-context half entirely (the reference's read-the-daily-table
        path): one cheap masked-sum dispatch instead of the ring lexsort.
        """
        with self.lock:
            if self.window_fully_rolled(ts_lo_min, ts_hi_min):
                self.read_stats["rolled_only_reads"] += 1
                packed = self._edges_rolled(
                    self.state, jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min)
                )
            elif self._ctx_cache[0] != self.write_version:
                # FRESH read (first query after a write): one fused
                # dispatch computes ctx from the maintained sort order +
                # the windowed edges, and primes the ctx cache for
                # follow-up windows at this version. The ctx stays on
                # device; only the packed edge triple crosses.
                self.read_stats["ctx_reads"] += 1
                ctx, packed = self._edges_fresh(
                    self.state, jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min)
                )
                self._ctx_cache = (self.write_version, ctx)
            else:
                self.read_stats["ctx_reads"] += 1
                packed = self._edges(
                    self._ctx_cache[1], self.state,
                    jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min),
                )
            idx, calls, errors = self._pull(packed)
            return idx, calls, errors

    def _flush_now(self) -> None:  # zt-lint: disable=ZT04 — callers hold self.lock; the state swap + mirror reset must be one critical section, which is why this helper is lock-free
        """Compact the pending digest buffer and reset the host mirror —
        the ONLY correct way to run the flush program (state swap and
        mirror reset are one invariant). Callers hold the lock.

        Deliberately does NOT bump write_version: a flush is
        query-INVISIBLE (the pend-fold and no-pend digest reads are
        bit-identical by construction, and flush touches nothing else),
        so cached reads and the link context stay valid — which is what
        lets a percentile read flush opportunistically without
        invalidating every other cached answer."""
        self.state = self._flush(self.state)
        self._pend_lanes = 0
        self._wal_marker("ttflush")

    def _wal_marker(self, tag: str) -> None:  # zt-lint: disable=ZT04 — called from _flush_now/rollup_now, both under self.lock (same critical section as the state swap being recorded)
        """Log a ZERO-lane WAL record marking an explicit flush/rollup.

        The fused-step flush/rollup variants are replay-deterministic
        (the host re-derives them from lane counts), but the EXPLICIT
        paths — a percentile read's flush-then-read, the time-tier
        sealer's pre-seal flush/rollup — are not: t-digest folding is
        order-sensitive, so replay must re-apply them at the exact
        stream position for the time-bucket digests to come back
        bit-identical. Replay (tpu/wal.py) maps the marker back to
        flush_now/rollup_now; wal_hook is None during replay, so
        replayed markers never re-log."""
        if self.wal_hook is not None and self.config.timetier_enabled:
            self.wal_seq = self.wal_hook(
                np.zeros((self.n_shards, 11, 0), np.uint32),
                0, 0, 0, (0, 0), extra={tag: 1},
            )

    def warm_programs(self, cols: SpanColumns) -> None:
        """Compile every program the steady-state ingest loop can
        dispatch (all fused step variants that can occur for this batch
        size, plus the standalone flush/rollup) by running them on a real
        batch. First compiles take seconds to minutes each and must
        never land inside a timed or serving window.
        Ingests ``cols`` several times — call before real traffic."""
        for force_flush, force_rollup in (
            (False, False), (True, False), (False, True), (True, True)
        ):
            with self.lock:
                if force_flush:
                    self._pend_lanes = self.config.digest_buffer
                if force_rollup:
                    self._lanes_since_rollup = self.config.rollup_segment
            # ingest() picks the variant from the (possibly forced)
            # counters; when a non-forced combination cannot occur at
            # this batch size, ingest lawfully dispatches the variant
            # that WOULD run in production instead — also fine to warm.
            self.ingest(cols)
        self.rollup_now()
        self.flush_now()
        # zt-lint: disable=ZT06 — warm-up's whole point: retire every
        # compile before a timed or serving window can start
        self.block_until_ready()

    def rollup_now(self) -> None:
        """Run the link-rollup program (rollup_step — which also advances
        the persistent incremental link ctx) and reset the write-distance
        tracker. Public for tests and shutdown paths."""
        with self.lock:
            obs_device.tag_next(on_done=self._maintenance_done)
            self.state = self._rollup(self.state)
            self._lanes_since_rollup = 0
            self.ctx_stats["ctx_advances"] += 1
            self.write_version += 1
            self._wal_marker("ttroll")

    def flush_now(self) -> None:
        """Public digest flush (compile warm-up, shutdown, tests)."""
        with self.lock:
            self._flush_now()

    def windowed_histograms(self, ts_lo_min: int, ts_hi_min: int) -> np.ndarray:
        """[K, BUCKETS] histogram over the window, merged across shards
        (empty rows where the window predates the slice retention)."""
        with self.lock:
            (out,) = self._pull(self._whist(
                self.state, jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min)
            ))
            return out

    def quantiles(
        self,
        qs,
        source: str = "digest",
        ts_lo_min: Optional[int] = None,
        ts_hi_min: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """([K, Q] quantiles, [K] counts) computed ON device in a single
        dispatch; ``source`` is "digest" or "hist"; a (ts_lo_min,
        ts_hi_min) window uses the time-sliced histograms — both bounds
        required (a half-open window has no defined slice selection)."""
        if (ts_lo_min is None) != (ts_hi_min is None):
            raise ValueError(
                "ts_lo_min and ts_hi_min must be given together "
                f"(got ts_lo_min={ts_lo_min!r}, ts_hi_min={ts_hi_min!r})"
            )
        qarr = jnp.asarray(np.asarray(qs, np.float32))
        with self.lock:
            if ts_lo_min is not None:
                packed = self._quant_whist(
                    self.state, jnp.uint32(ts_lo_min), jnp.uint32(ts_hi_min),
                    qarr,
                )
            elif source == "digest":
                if self._pend_lanes:
                    # flush-then-read beats the pend-fold read variant:
                    # the fold costs the same compaction (75ms device at
                    # full shapes, QUERY_SLO r3 capture) WITHOUT
                    # advancing state, so every query would re-pay it;
                    # the flush pays it once and the read itself rides
                    # the cheap no-pend program
                    self._flush_now()
                packed = self._quant_digest_nopend(self.state, qarr)
            else:
                packed = self._quant_hist(self.state, qarr)
            q, n = self._pull(packed)
            return q, n

    def tt_read(self, lo_ep: int, hi_ep: int):
        """(slot_epochs [W], hll_regs [S+1, m], digest [K, Cw, 2],
        calls [S, S], errs [S, S]) for the bucket-epoch range
        ``[lo_ep, hi_ep]``, merged across shards on device — ONE packed
        pull (the tier's only device transfer per windowed query: the
        unsealed-suffix read; sealed buckets merge host-side from
        segments). A digest flush runs first so the bucket digests
        include every pending point (same flush-then-read economics as
        quantiles(); explicit-flush replay determinism is covered by the
        ttflush WAL marker)."""
        with self.lock:
            if self._pend_lanes:
                self._flush_now()
            ep, regs, digest, calls, errs = self._pull(self._ttread(
                self._link_context_cached(), self.state,
                jnp.int32(lo_ep), jnp.int32(hi_ep),
            ))
            return ep, regs, digest, calls, errs

    @property
    def tt_max_epoch(self) -> int:
        """Highest bucket epoch ingest has touched (-1: none yet)."""
        return self._tt_max_epoch

    def cardinalities(self) -> np.ndarray:
        """[S+1] HLL distinct-trace estimates (last row global), computed
        on device — only the estimates cross to the host, not registers."""
        with self.lock:
            (est,) = self._pull(self._card(self.state))
            return est

    def sketch_overview(self, qs) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """([K, Q] digest quantiles, [K] counts, [S+1] HLL estimates) in
        ONE dispatch and ONE transfer — the coalesced read behind the
        server's /api/v2/tpu/overview endpoint, which previously issued
        three aggregator reads (quantiles + cardinalities + counters)
        per HTTP request."""
        qarr = jnp.asarray(np.asarray(qs, np.float32))
        with self.lock:
            if self._pend_lanes:
                self._flush_now()  # same flush-then-read as quantiles()
            q, n, est = self._pull(self._overview(self.state, qarr))
            return q, n, est

    def sync_pend_lanes(self) -> None:
        """Re-derive the host pend mirror from device state (call after
        replacing ``self.state`` wholesale, e.g. snapshot restore)."""
        with self.lock:
            # routed through the counted chokepoint: a restore-time pull
            # is rare but should still show in the transfer ledger. ONE
            # packed pull covers the pend mirror and (tier on) the
            # restored current-bucket epochs — both i32 lanes.
            lanes = [self.state.pend_pos.reshape(-1)]
            if self.config.timetier_enabled:
                lanes.append(self.state.tb_epoch.reshape(-1))
            packed = readpack.device_get(jnp.concatenate(lanes))
            n_pend = self.state.pend_pos.size
            self._pend_lanes = int(packed[:n_pend].max())
            # write distance since the last rollup is not recorded in
            # state; assume the worst so the next batch rolls up first
            self._lanes_since_rollup = self.config.rollup_segment
            # restored ring content has unknown timestamps: one entry
            # covering every window keeps rolled-only reads conservative
            # until a full ring of new writes has displaced it
            self._resident.clear()
            self._resident.append(
                (0, (1 << 32) - 1, self._shard_cursor.copy())
            )
            if self.config.timetier_enabled:
                # restored current-bucket epochs ARE recorded in state;
                # the freshest one is the unsealed bucket after resume
                self._tt_max_epoch = int(packed[n_pend:].max())
            self.write_version += 1

    def state_arrays(self) -> list:
        """Consistent host copy of every state leaf (see state_clone)."""
        clone, _, _ = self.state_clone()
        return [np.asarray(leaf) for leaf in clone]

    def state_clone(self):
        """(device clone, wal_seq, host_counters copy), all captured
        ATOMICALLY under the lock — everything the snapshot records
        about one instant must come from the same locked section, or a
        batch ingested during the multi-second host pull would be both
        inside the recorded counters and after the recorded wal_seq
        (WAL replay would then double-count it). The lock is held only
        for the clone DISPATCH (ms); callers pull the clone's leaves
        lock-free while ingest continues against the live buffers."""
        with self.lock:
            return (
                self._snap_copy(self.state),
                self.wal_seq,
                dict(self.host_counters),
            )

    def block_until_ready(self) -> None:
        with self.lock:
            jax.tree_util.tree_map(lambda a: a.block_until_ready(), self.state)
        # ... and until the completion clock has booked what has run
        obs_device.OBSERVATORY.queue.wait_idle(10.0)
