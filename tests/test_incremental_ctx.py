"""Incremental link context vs. the from-scratch oracle (ISSUE 5).

The tentpole claim: the persistent ctx leaves maintained by
``rollup_step`` (via ``delta_linker.advance``) plus the since-rollup
delta resolution (``delta_linker.delta_link_context``) produce a
LinkContext that is BIT-IDENTICAL to ``linker.link_context`` run from
scratch over the full ring — at every instant, under arbitrary
ingest/flush/rollup interleavings, with sampling flipping ``r_keep``
under the resolver's feet, and across crash-resume (the resumed ctx
leaves must put the reborn process on the exact same answers).

Bit-identity (not "same edges") is the contract because the delta
formulation's exactness argument is structural — the age partition
doomed/safe/delta covers every lane exactly once and the candidate
pick mirrors the oracle's first-inserted preference chain — and any
crack in that argument shows up first as a single divergent parent
lane, long before it corrupts an aggregate.
"""

from __future__ import annotations

import functools
import json
import random

import jax
import numpy as np
import pytest

from tests.fixtures import lots_of_spans
from zipkin_tpu import faults
from zipkin_tpu.ops import linker
from zipkin_tpu.storage.tpu import TpuStorage
from zipkin_tpu.tpu import ingest as ing
from zipkin_tpu.tpu.columnar import Vocab, pack_spans
from zipkin_tpu.tpu.state import AggConfig, init_state


@functools.lru_cache(maxsize=None)
def _ctx_programs(config):
    """One compile per config — a fresh jit per assert would recompile."""
    return (
        jax.jit(lambda s: ing.fresh_link_context(config, s)),
        jax.jit(lambda s: linker.link_context(ing.ring_link_input(s))),
    )


def assert_ctx_identical(config, state, where=""):
    """fresh (persistent ctx + delta) == from-scratch oracle, leaf-for-leaf."""
    fresh, oracle = _ctx_programs(config)
    got = fresh(state)
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
    """init_state's ctx leaves are a valid advance fixpoint: the very
    first fresh read (delta over an all-invalid ring) matches the
    oracle, as does every read before the first rollup ever runs."""
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
# aggregator-level: the real host cadence (ingest_fused / rollup_now)
# ----------------------------------------------------------------------

STORE_CFG = AggConfig(
    max_services=64, max_keys=256, hll_precision=8, digest_centroids=16,
    digest_buffer=4096, ring_capacity=4096, link_buckets=4,
    bucket_minutes=60, hist_slices=2, sampling=True,
)


def make_store(tmp_path, tag=""):
    return TpuStorage(
        config=STORE_CFG, num_devices=2, batch_size=512,
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


# ----------------------------------------------------------------------
# crash-resume: resumed ctx leaves are bit-identical
# ----------------------------------------------------------------------


def test_crash_mid_wal_append_resumes_identical_ctx(tmp_path):
    """Kill the process mid-WAL-append (the PR-3 fault registry's
    nastiest instant) and reboot from disk: WAL replay re-runs the same
    fused steps, so the reborn ctx leaves — and the fresh reads built on
    them — must be bit-identical to the oracle AND to a second pristine
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
