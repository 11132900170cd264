"""The roll-up's incremental link context vs. the from-scratch oracle.

``rollup_step`` advances the persistent ctx leaves with one sort of the
join union (``delta_linker.advance``) and emits its links from the tree
that advance resolves. That tree and that LinkContext must be
BIT-IDENTICAL to ``linker.link_context`` run from scratch over the same
ring — under arbitrary ingest/flush/rollup interleavings, with sampling
flipping ``r_keep`` under the resolver's feet, and across crash-resume
(the resumed ctx leaves must put the reborn process on the exact same
answers). A fresh dependency read IS the oracle
(``ingest.fresh_link_context``); what is held of it here is its shape
on the chip (one sort, no search) and that the aggregator's fresh and
cached reads agree with it.

Bit-identity (not "same edges") is the contract because any crack in
the advance's candidate choice shows up first as a single divergent
parent lane, long before it corrupts an aggregate.
"""

from __future__ import annotations

import functools
import json
import random
from typing import NamedTuple

import jax
import numpy as np
import pytest

from tests.fixtures import TODAY_US, lots_of_spans
from zipkin_tpu import faults
from zipkin_tpu.model.json_v2 import encode_span_list
from zipkin_tpu.model.span import Endpoint, Kind, Span
from zipkin_tpu.ops import delta_linker, linker
from zipkin_tpu.storage.tpu import TpuStorage
from zipkin_tpu.tpu import ingest as ing
from zipkin_tpu.tpu.columnar import Vocab, pack_spans
from zipkin_tpu.tpu.state import AggConfig, init_state


@functools.lru_cache(maxsize=None)
def _ctx_programs(config):
    """One compile per config — a fresh jit per assert would recompile.
    Two programs: inside one jit XLA would merge the oracle's sort with
    the advance's and compare a value with itself."""
    return (
        jax.jit(lambda s: delta_linker.advance(
            ing.ring_link_input(s), ing.ctx_struct(s), config.rollup_segment
        )[4]),
        jax.jit(lambda s: linker.link_context(ing.ring_link_input(s))),
    )


def assert_ctx_identical(config, state, where=""):
    """The context the roll-up would emit from at this instant (the
    advance over the state's ctx leaves) == the from-scratch oracle,
    leaf-for-leaf."""
    advanced, oracle = _ctx_programs(config)
    got = advanced(state)
    want = oracle(state)
    for name, g, w in zip(got._fields, got, want):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w),
            err_msg=f"LinkContext.{name} diverged from oracle {where}",
        )


# ----------------------------------------------------------------------
# step-level fuzz: arbitrary ingest/rollup interleavings
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "seed,ring_pow",
    [(0, 6), (1, 6), (2, 7), (3, 8), (4, 8)],
)
def test_fuzz_interleavings_bit_identical(seed, ring_pow):
    """Random batch sizes, rollups at the host cadence plus extra
    spontaneous ones (including back-to-back => a zero-delta advance),
    checked at random instants. Tiny rings force many full wraps."""
    cfg = AggConfig(
        max_services=64, max_keys=256, hll_precision=9,
        digest_centroids=32, ring_capacity=1 << ring_pow,
    )
    seg = cfg.rollup_segment
    vocab = Vocab(max_services=64, max_keys=256)
    cols = pack_spans(
        lots_of_spans(12 * (1 << ring_pow), seed=seed),
        vocab, pad_to_multiple=8,
    )
    step = jax.jit(lambda s, b: ing.ingest_step(cfg, s, b))
    rollup = jax.jit(lambda s: ing.rollup_step(cfg, s))

    state = init_state(cfg)
    rnd = random.Random(seed * 101 + 7)
    lo, since, checks = 0, 0, 0
    while lo < cols.size:
        sz = rnd.choice([8, 16, 24, 32, seg // 2])
        sub = type(cols)(*(np.asarray(f[lo:lo + sz]) for f in cols))
        lo += sz
        lanes = sub.valid.shape[0]
        # the host cadence invariant ingest_fused enforces: never let
        # the since-rollup delta exceed the rollup segment
        if since + lanes > seg:
            state = rollup(state)
            since = 0
            if rnd.random() < 0.25:  # back-to-back: delta=0 advance
                state = rollup(state)
        state = step(state, sub)
        since += lanes
        if rnd.random() < 0.35:
            assert_ctx_identical(cfg, state, f"at span offset {lo}")
            checks += 1
    assert checks >= 5  # the fuzz actually sampled instants


def test_empty_ring_and_first_batches():
    """init_state's ctx leaves are a valid advance input: the advance
    over the pristine ring matches the oracle, as does every advance
    before the first rollup ever runs."""
    cfg = AggConfig(
        max_services=64, max_keys=256, hll_precision=9,
        digest_centroids=32, ring_capacity=1 << 7,
    )
    state = init_state(cfg)
    assert_ctx_identical(cfg, state, "on the pristine ring")
    vocab = Vocab(max_services=64, max_keys=256)
    cols = pack_spans(lots_of_spans(48, seed=9), vocab, pad_to_multiple=8)
    step = jax.jit(lambda s, b: ing.ingest_step(cfg, s, b))
    for lo in range(0, cols.size, 16):
        sub = type(cols)(*(np.asarray(f[lo:lo + 16]) for f in cols))
        state = step(state, sub)
        assert_ctx_identical(cfg, state, "before the first rollup")


# ----------------------------------------------------------------------
# the advance itself: one sort of the union serves tree and ctx (ISSUE 33)
# ----------------------------------------------------------------------


def tangled_spans(n, seed):
    """Chains of RPCs with what makes the joins hard: shared server
    halves with their client mate and without one (a mateless half must
    fall back to its parentId), span ids reported twice from different
    services, and a span that names itself as parent."""
    rng = random.Random(seed)
    svc = [Endpoint.create(f"svc{i:02d}", f"10.0.0.{i + 1}") for i in range(8)]
    spans = []
    trace = 0
    while len(spans) < n:
        trace += 1
        tid = f"{rng.getrandbits(63) | 1:016x}"
        parent, caller = None, rng.randrange(8)
        for level in range(rng.randint(1, 5)):
            sid = f"{(trace << 8 | level) + 1:016x}"
            callee = rng.randrange(8)
            common = dict(
                name=f"op{rng.randrange(6)}", timestamp=TODAY_US + trace * 1000 + level,
                duration=50 + rng.randrange(1000),
            )
            client = Span.create(
                tid, sid, parent_id=parent, kind=Kind.CLIENT,
                local_endpoint=svc[caller], remote_endpoint=svc[callee], **common,
            )
            server = Span.create(
                tid, sid, parent_id=parent, kind=Kind.SERVER, shared=True,
                local_endpoint=svc[callee], **common,
            )
            roll = rng.random()
            if roll < 0.45:
                spans += [client, server]       # shared half with its mate
            elif roll < 0.65:
                spans.append(server)            # shared half, no mate
            else:
                spans.append(client)
            if rng.random() < 0.15:             # the same id reported twice
                spans.append(Span.create(
                    tid, sid, parent_id=parent, kind=Kind.CLIENT,
                    local_endpoint=svc[rng.randrange(8)], **common,
                ))
            if rng.random() < 0.03:             # its own parent
                spans.append(Span.create(
                    tid, f"{(trace << 8 | 0x80 | level) + 1:016x}",
                    parent_id=f"{(trace << 8 | 0x80 | level) + 1:016x}",
                    kind=Kind.CLIENT, local_endpoint=svc[caller], **common,
                ))
            parent, caller = sid, callee
    return spans[:n]


class Advance(NamedTuple):
    """One roll-up of a trajectory, as numpy: what ``advance`` returned
    (twice, on the same input), what the from-scratch oracle says of the
    same ring, and the ctx leaves ``rollup_step`` left in the state."""

    since: int        # lanes written since the advance before
    got: tuple        # new ctx, parent, anc, root_ok, LinkContext
    again: tuple      # the same call a second time
    oracle: tuple     # parent, has_child, anc, root_ok, LinkContext
    state_ctx: tuple  # CtxStruct leaves of the state after rollup_step


@pytest.fixture(scope="module", params=[(21, 7), (22, 8), (23, 9)],
                ids=lambda p: f"seed{p[0]}-ring{1 << p[1]}")
def trajectory(request):
    """Four ring wraps of tangled spans in small batches, rolled up at
    the host cadence and twice on a part-written segment. The first
    advances see a ring that still has never-written (invalid) lanes."""
    seed, ring_pow = request.param
    cfg = AggConfig(
        max_services=64, max_keys=256, hll_precision=9,
        digest_centroids=32, ring_capacity=1 << ring_pow,
    )
    seg = cfg.rollup_segment
    cols = pack_spans(
        tangled_spans(4 << ring_pow, seed),
        Vocab(max_services=64, max_keys=256), pad_to_multiple=8,
    )
    step = jax.jit(lambda s, b: ing.ingest_step(cfg, s, b))
    rollup = jax.jit(lambda s: ing.rollup_step(cfg, s))

    advance = jax.jit(lambda s: delta_linker.advance(
        ing.ring_link_input(s), ing.ctx_struct(s), seg
    ))

    # a program of its own: inside one jit XLA would merge the oracle's
    # sort with the advance's and compare a value with itself
    @jax.jit
    def from_scratch(s):
        x = ing.ring_link_input(s)
        parent, child = linker.resolve_parents(x)
        anc, root_ok = linker.chase_ancestors(
            parent, jax.numpy.where(x.valid, x.kind, 0)
        )
        ctx = linker.apply_rules(x, parent, child, anc, root_ok)
        return parent, child, anc, root_ok, ctx

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    state = init_state(cfg)
    rnd = random.Random(seed)
    advances, reads = [], []
    lo = since = 0
    while lo < cols.size:
        sz = rnd.choice([8, 8, 16, 24])
        sub = type(cols)(*(np.asarray(f[lo:lo + sz]) for f in cols))
        lo += sz
        lanes = int(sub.valid.sum())
        # a roll-up where the host cadence asks for one, and every third
        # time on a segment only part-written
        early = len(advances) % 3 == 1 and since >= seg // 4
        if since + lanes > seg or early:
            got, want = advance(state), from_scratch(state)
            again = advance(state)
            state = rollup(state)
            advances.append(Advance(
                since, host(got), host(again), host(want),
                host(ing.ctx_struct(state)),
            ))
            since = 0
        state = step(state, sub)
        since += lanes
        reads.append((lo, since))
    return cfg, advances, reads


def test_advance_trajectory_is_long_enough(trajectory):
    cfg, advances, reads = trajectory
    assert len(advances) >= 8  # four wraps, two roll-ups a wrap
    assert any(0 < a.since < cfg.rollup_segment - 24 for a in advances)
    assert max(since for _, since in reads) == cfg.rollup_segment


def test_advance_tree_is_the_oracles(trajectory):
    """parent, anc, root_ok and the emit context (which carries
    has_child through rule 1) equal resolve_parents / chase_ancestors /
    apply_rules on the same ring, bit for bit, at every roll-up."""
    _, advances, _ = trajectory
    for k, a in enumerate(advances):
        _, parent, anc, root_ok, ctx = a.got
        o_parent, _o_child, o_anc, o_root, o_ctx = a.oracle
        for name, g, w in [("parent", parent, o_parent), ("anc", anc, o_anc),
                           ("root_ok", root_ok, o_root)]:
            np.testing.assert_array_equal(g, w, err_msg=f"{name}, roll-up {k}")
        for name, g, w in zip(ctx._fields, ctx, o_ctx):
            np.testing.assert_array_equal(
                g, w, err_msg=f"LinkContext.{name}, roll-up {k}")


def test_advance_has_child_is_the_oracles():
    """has_child leaves advance only inside the emit context, so it is
    held directly here, through the two halves advance is made of."""
    cfg = AggConfig(
        max_services=64, max_keys=256, hll_precision=9,
        digest_centroids=32, ring_capacity=1 << 7,
    )
    cols = pack_spans(
        tangled_spans(3 << 7, 5), Vocab(max_services=64, max_keys=256),
        pad_to_multiple=8,
    )
    step = jax.jit(lambda s, b: ing.ingest_step(cfg, s, b))

    @jax.jit
    def halves(s):
        x = ing.ring_link_input(s)
        su = linker.sort_union(x)
        mins = linker._run_min_ladder(linker.tree_channels(su), 2 << 7)
        return linker.choose_parents(x, su, *mins), linker.resolve_parents(x)

    state = init_state(cfg)
    for lo in range(0, cols.size, 32):
        state = step(state, type(cols)(*(np.asarray(f[lo:lo + 32]) for f in cols)))
        (parent, child), (o_parent, o_child) = halves(state)
        np.testing.assert_array_equal(np.asarray(parent), np.asarray(o_parent))
        np.testing.assert_array_equal(np.asarray(child), np.asarray(o_child))
        assert np.asarray(child).any()


def test_advance_ctx_is_a_sorted_union(trajectory):
    """keys lexicographically non-decreasing, equal keys in union-index
    order (the tie rule that makes the leaves reproducible), inv the
    inverse of order, run ids non-decreasing from 1 and stepping exactly
    where the keys change, safe candidates only from lanes the cursor
    cannot reach before the next advance."""
    cfg, advances, _ = trajectory
    n, seg = cfg.ring_capacity, cfg.rollup_segment
    for k, a in enumerate(advances):
        cs = a.got[0]
        keys = [cs.keys[i].astype(np.int64) for i in range(4)]
        packed = [tuple(int(kk[j]) for kk in keys) for j in range(2 * n)]
        assert packed == sorted(packed), f"keys unsorted, roll-up {k}"
        same = np.array([packed[j] == packed[j - 1] for j in range(1, 2 * n)])
        assert (np.diff(cs.order)[same] > 0).all(), f"tie order, roll-up {k}"
        np.testing.assert_array_equal(cs.inv[cs.order], np.arange(2 * n))
        np.testing.assert_array_equal(np.sort(cs.order), np.arange(2 * n))
        same3 = np.array([packed[j][:3] == packed[j - 1][:3]
                          for j in range(1, 2 * n)])
        for rid, eq in ((cs.rid_c, same3), (cs.rid_f, same)):
            assert rid[0] == 1
            np.testing.assert_array_equal(np.diff(rid), (~eq).astype(np.int32))
        for safe in (cs.safe_sh, cs.safe_ns, cs.safe_fsh):
            has = safe >= 0
            assert ((safe[has] - cs.pos) % n >= seg).all()
        assert cs.delta == 0


def test_advance_is_deterministic_and_is_what_rollup_step_stores(trajectory):
    _, advances, _ = trajectory
    for k, a in enumerate(advances):
        for name, g, again, stored in zip(
            a.got[0]._fields, a.got[0], a.again[0], a.state_ctx
        ):
            np.testing.assert_array_equal(
                g, again, err_msg=f"ctx.{name} differs on a second run, roll-up {k}")
            np.testing.assert_array_equal(
                g, stored, err_msg=f"ctx.{name} is not the state's, roll-up {k}")


# ----------------------------------------------------------------------
# structural fence: what would otherwise show only on the chip
# ----------------------------------------------------------------------


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.append((eqn.primitive.name, eqn))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _primitives(inner, out)
    return out


FENCE_CFG = AggConfig(
    max_services=64, max_keys=256, hll_precision=9,
    digest_centroids=32, ring_capacity=1 << 8,
)


def test_rollup_step_has_one_sort_and_one_loop():
    """ONE sort: the tree and the next ctx come from the same sorted
    operands. ONE loop: chase_ancestors' convergence-bounded while. A
    ``fori_loop`` with static bounds traces as ``scan``, so a binary
    search creeping back into the advance shows as a scan here and as a
    1-2 s ``while`` on the chip (PERF.md section 6, PR 33)."""
    prims = _primitives(
        jax.make_jaxpr(lambda s: ing.rollup_step(FENCE_CFG, s))(
            init_state(FENCE_CFG)
        ).jaxpr, [],
    )
    names = [name for name, _ in prims]
    assert names.count("sort") == 1
    (sort,) = [e for name, e in prims if name == "sort"]
    assert sort.invars[0].aval.shape == (2 * FENCE_CFG.ring_capacity,)
    assert sort.params["is_stable"] and sort.params["num_keys"] == 4
    assert names.count("while") == 1 and names.count("scan") == 0


@pytest.mark.parametrize("cfg", [FENCE_CFG, AggConfig()],
                         ids=["ring256", "server-default"])
def test_fresh_read_has_one_sort_and_no_search(cfg):
    """The fresh read resolves from scratch: ONE stable 4-key sort of
    the 2R-lane union, ONE loop (the chase's while). A binary search of
    stored keys traces as ``scan`` here and held every sender 0.67 s a
    read on the chip (PERF.md section 6), at the server's default size
    as at a small one."""
    state = jax.eval_shape(lambda: init_state(cfg))
    prims = _primitives(jax.make_jaxpr(ing.fresh_link_context)(state).jaxpr, [])
    names = [name for name, _ in prims]
    assert names.count("sort") == 1
    (sort,) = [e for name, e in prims if name == "sort"]
    assert sort.invars[0].aval.shape == (2 * cfg.ring_capacity,)
    assert sort.params["is_stable"] and sort.params["num_keys"] == 4
    assert names.count("scan") == 0 and names.count("while") == 1


# ----------------------------------------------------------------------
# aggregator-level: the real host cadence (ingest_fused / rollup_now)
# ----------------------------------------------------------------------

STORE_CFG = AggConfig(
    max_services=64, max_keys=256, hll_precision=8, digest_centroids=16,
    digest_buffer=4096, ring_capacity=4096, link_buckets=4,
    bucket_minutes=60, hist_slices=2, sampling=True,
)


def make_store(tmp_path, tag="", num_devices=2):
    return TpuStorage(
        config=STORE_CFG, num_devices=num_devices, batch_size=512,
        checkpoint_dir=str(tmp_path / f"ckpt{tag}"),
        wal_dir=str(tmp_path / f"wal{tag}"),
        archive_dir=str(tmp_path / f"archive{tag}"),
        sampling_budget=100.0,
        # the tests tick the controller by hand; its own 5 s thread
        # would otherwise publish at a moment of its choosing on a
        # loaded machine (and outlive ``del victim``)
        sampling_interval_s=3600.0,
    )


def payload(n, base):
    """Multi-level traces (real parent links), ~10% errors."""
    spans = []
    for i in range(n):
        tid = f"{(base + i) // 3 + 1:016x}"
        sid = f"{base + i + 1:016x}"
        parent = None if i % 3 == 0 else f"{base + i:016x}"
        spans.append({
            "traceId": tid, "id": sid,
            **({"parentId": parent} if parent else {}),
            "name": f"op{i % 5}",
            "timestamp": 1_700_000_000_000_000 + i,
            "duration": 1000 + (i % 50),
            "localEndpoint": {"serviceName": f"svc{i % 6}"},
            **({"tags": {"error": "true"}} if i % 10 == 0 else {}),
        })
    return json.dumps(spans).encode()


def squeeze_state(agg):
    """Single logical state from the sharded leaves (replicated ring)."""
    clone, _, _ = agg.state_clone()
    return type(clone)(*(np.asarray(leaf)[0] for leaf in clone))


def test_store_cadence_with_sampling_active(tmp_path):
    """Through the full TpuStorage path — fused flush/rollup variants,
    the sampling controller tightening tables mid-stream (r_keep flips
    under the resolver) — the maintained ctx stays on the oracle. The
    sketch/link plane sees 100% of spans regardless of verdicts, so
    sampling must be invisible to ctx parity."""
    store = make_store(tmp_path)
    try:
        for b in range(6):
            store.ingest_json_fast(payload(700, base=b * 100_000))
            if b == 2:
                assert store.sampling_controller.tick(1.0)  # tighten
            if b == 4:
                store.agg.rollup_now()  # spontaneous advance
            assert_ctx_identical(
                STORE_CFG, squeeze_state(store.agg), f"after batch {b}"
            )
    finally:
        store.close()


def oracle_edges(agg, ts_lo, ts_hi):
    """(flat_index, calls, errors) of every nonzero cell of the link
    matrix summed over the shards, each shard's links computed by
    ``dependency_links`` with no ctx (the from-scratch resolve)."""
    clone, _, _ = agg.state_clone()
    calls = errors = 0
    for k in range(np.asarray(clone.r_valid).shape[0]):
        shard = type(clone)(*(np.asarray(leaf)[k] for leaf in clone))
        c, e = ing.dependency_links(
            STORE_CFG, shard, np.uint32(ts_lo), np.uint32(ts_hi)
        )
        calls, errors = calls + np.asarray(c), errors + np.asarray(e)
    idx = np.flatnonzero(calls)
    return idx, calls.reshape(-1)[idx], errors.reshape(-1)[idx]


@pytest.mark.parametrize("devices", [1, 2])
def test_fresh_cached_and_from_scratch_edges_agree_across_rollup(
    tmp_path, devices
):
    """The aggregator's fresh read (``spmd_edges_fresh``, which primes
    the per-version ctx cache), its cached follow-up (``spmd_edges`` on
    that ctx) and ``dependency_links`` with no ctx, summed over the
    shards, give the same edges, before a roll-up and after it (the
    rolled half then answers from the buckets, the rest from the live
    ring)."""
    lo, hi = 0, 0xFFFFFFFF
    store = make_store(tmp_path, num_devices=devices)
    try:
        agg = store.agg
        reads = []
        for b in range(3):
            store.ingest_json_fast(
                encode_span_list(tangled_spans(700, seed=40 + b))
            )
            if b == 2:
                agg.rollup_now()
            before = agg.read_stats["ctx_reads"]
            fresh = agg.dependency_edges(lo, hi)
            assert agg._ctx_cache[0] == agg.write_version
            cached = agg.dependency_edges(lo, hi)
            assert agg.read_stats["ctx_reads"] == before + 2
            want = oracle_edges(agg, lo, hi)
            assert len(want[0]) > 0
            for got in (fresh, cached):
                have = got[1] > 0
                for g, w in zip(got, want):
                    np.testing.assert_array_equal(
                        np.asarray(g)[have], w, err_msg=f"after batch {b}")
            reads.append(want)
        # the roll-up between the last two reads moved no edge: the
        # batch before it added its own, and nothing else changed
        assert reads[2][1].sum() > reads[1][1].sum()
    finally:
        store.close()


# ----------------------------------------------------------------------
# crash-resume: resumed ctx leaves are bit-identical
# ----------------------------------------------------------------------


def test_crash_mid_wal_append_resumes_identical_ctx(tmp_path):
    """Kill the process mid-WAL-append (the PR-3 fault registry's
    nastiest instant) and reboot from disk: WAL replay re-runs the same
    fused steps, so the reborn ctx leaves — and the context the advance
    builds on them — must be bit-identical to the oracle AND to a second pristine
    boot from the same disk state."""
    victim = make_store(tmp_path)
    victim.ingest_json_fast(payload(900, base=1))
    assert victim.sampling_controller.tick(1.0)
    victim.ingest_json_fast(payload(900, base=200_000))

    faults.arm("wal.append.mid", nth=1, action="raise")
    try:
        with np.testing.assert_raises(faults.CrashpointTriggered):
            victim.ingest_json_fast(payload(900, base=400_000))
    finally:
        faults.disarm()
    del victim  # device state notionally lost; disk is all that survives

    reborn = make_store(tmp_path)
    try:
        s1 = squeeze_state(reborn.agg)
        assert_ctx_identical(STORE_CFG, s1, "after crash-resume")
        # determinism: a second boot from the same disk lands on the
        # exact same ctx leaves (replay is the only input)
        twin = make_store(tmp_path)
        try:
            s2 = squeeze_state(twin.agg)
            for name, a, b in zip(s1._fields, s1, s2):
                if name.startswith("ctx_"):
                    np.testing.assert_array_equal(
                        a, b, err_msg=f"{name} differs between boots"
                    )
        finally:
            twin.close()
        # and the resumed process keeps the invariant as it ingests on
        reborn.ingest_json_fast(payload(900, base=600_000))
        assert_ctx_identical(
            STORE_CFG, squeeze_state(reborn.agg), "post-resume ingest"
        )
    finally:
        reborn.close()


def test_snapshot_restore_resumes_identical_ctx(tmp_path):
    """ctx leaves ride the snapshot (SNAPSHOT_VERSION 4): restoring
    must reproduce them exactly, and sync_pend_lanes pins the host
    cadence so the first post-restore batch forces an advance before
    the delta can outgrow the rollup segment."""
    victim = make_store(tmp_path)
    victim.ingest_json_fast(payload(900, base=1))
    victim.snapshot()
    saved = {
        name: np.asarray(leaf)[0].copy()
        for name, leaf in zip(
            victim.agg.state._fields, victim.agg.state
        )
        if name.startswith("ctx_")
    }
    del victim

    reborn = make_store(tmp_path)
    try:
        # every ctx leaf restored bit-identically (WAL was truncated at
        # the snapshot, so nothing replays on top)
        for name, want in saved.items():
            np.testing.assert_array_equal(
                np.asarray(getattr(reborn.agg.state, name))[0], want,
                err_msg=f"{name} not restored bit-identically",
            )
        assert_ctx_identical(
            STORE_CFG, squeeze_state(reborn.agg), "after snapshot-restore"
        )
        reborn.ingest_json_fast(payload(600, base=700_000))
        assert_ctx_identical(
            STORE_CFG, squeeze_state(reborn.agg),
            "post-restore ingest (cadence pinned by sync_pend_lanes)",
        )
    finally:
        reborn.close()
