"""Staged BASELINE.json eval configs, runnable end to end.

Each stage prints one JSON line with pass/fail and measurements. Scales
are set for a single box; raise with env vars for full-scale runs:

  config0 — server smoke: POST the canonical TRACE, query it back.
  config1 — EVAL_SPANS (default 1M) synthetic spans: device t-digest
            p50/p99 per (service, spanName) vs exact truth.
  config2 — EVAL_LINK_SPANS (default 1M): device dependency links vs the
            host DependencyLinker oracle, edge-count parity.
  config3 — EVAL_HLL (default 100M) distinct trace hashes streamed into
            device HLL registers; estimate within 3*stderr.
  config4 — EVAL_REPLAY_SPANS (default 2M) streaming replay with mixed
            query load (dependencies + percentiles + cardinalities every
            N batches), sustained throughput reported.
  config5 — fan-out tier wire-to-ack gate: proto3 through the server
            boundary with sampling + WAL live; >=1M spans/s at >=2
            parse workers on a multi-core host, graceful measured
            degradation vs the same-run in-process budget on one core.
  config6 — SLO watchdog trip/clear: induced query_fresh burn through
            the production record site; alert within one long window,
            visible on /prometheus, clears after recovery.
  config7 — accuracy-drift trip/clear: undersized digest (C=4) on a
            bimodal stream; the shadow-measured drift gauge crosses
            0.20 and digest_p99_relerr trips, then clears after reset.
  config8 — overload flood gate: >=3x-capacity flood through the real
            HTTP boundary with WAL ENOSPC landing mid-flood; admitted
            ack p99 within SLO, every shed guided (HTTP Retry-After +
            gRPC retry-delay trailers), zero acked loss at durable
            parity, disk-full degrades (not crashes) and clears, B0
            back within one long window of flood end.
  config9 — tenant flood containment gate: tenant B floods >=3x its
            ingest budget through the real HTTP boundary (X-Tenant-Id)
            while A and C stay in budget; every shed is B's and
            tenant-scoped (X-Shed-Scope/X-Shed-Tenant + per-tenant
            Retry-After, gRPC shed-scope trailers), A/C hold ack and
            query SLOs at global B0, per-tenant acked attribution is
            exact, zero acked loss across mid-flood crash-resume, and
            the {tenant=} prometheus families render.

Run: python -m evals.run_configs [config0 config1 ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def _emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def config0() -> bool:
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tests.fixtures import TODAY, TRACE
    from zipkin_tpu.model import json_v2
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig

    async def scenario() -> bool:
        server = ZipkinServer(ServerConfig(storage_type="mem"))
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            resp = await client.post(
                "/api/v2/spans", data=json_v2.encode_span_list(TRACE),
                headers={"Content-Type": "application/json"})
            ok = resp.status == 202
            resp = await client.get(f"/api/v2/trace/{TRACE[0].trace_id}")
            ok &= resp.status == 200 and len(await resp.json()) == len(TRACE)
            resp = await client.get(
                f"/api/v2/dependencies?endTs={TODAY + 3_600_000}&lookback=86400000")
            links = {(l["parent"], l["child"]) for l in await resp.json()}
            ok &= links == {("frontend", "backend"), ("backend", "mysql")}
            return ok
        finally:
            await client.close()

    ok = asyncio.run(scenario())
    _emit(config="config0", passed=ok)
    return ok


def _dur_of(k: np.ndarray, j: np.ndarray) -> np.ndarray:
    """Deterministic per-key duration stream: occurrence j of key k gets
    a reproducible pseudo-random duration, so the EXACT per-key multiset
    can be regenerated vectorized at check time instead of being
    accumulated span-by-span during ingest (the r2 bookkeeping that
    capped the harness at ~8k spans/s — VERDICT r2 weak #6). Long-tailed
    on purpose: 1-in-64 durations land 100x out, so the p99 rank check
    exercises the digest's tail, not just its bulk."""
    from zipkin_tpu.tpu.columnar import _mix32

    h = _mix32((k.astype(np.uint32) << np.uint32(18)) ^ j.astype(np.uint32))
    base = (h % np.uint32(10_000)).astype(np.uint32) + 1
    tail = ((h >> np.uint32(16)) % np.uint32(64)) == 0
    return np.where(tail, base * np.uint32(100), base)


def config1() -> bool:
    """Device t-digest accuracy vs EXACT closed-form truth, in rank
    space, at array speed (10x the r2 harness rate — the corpus and the
    per-key truth are regenerated vectorized; pack-path correctness is
    the unit/contract suites' job)."""
    from zipkin_tpu.ops import tdigest
    from zipkin_tpu.parallel.mesh import make_mesh
    from zipkin_tpu.parallel.sharded import ShardedAggregator
    from zipkin_tpu.tpu.columnar import SpanColumns, Vocab, _hash2_np
    from zipkin_tpu.tpu.state import AggConfig

    total = int(os.environ.get("EVAL_SPANS", 1_000_000))
    n_keys = 200
    n_services = 10
    batch = 65_536
    cfg = AggConfig()
    agg = ShardedAggregator(cfg, mesh=make_mesh(1))
    vocab = Vocab(cfg.max_services, cfg.max_keys)
    for s in range(n_services):
        vocab.services.intern(f"svc{s:02d}")
    # record the vocab's id per synthetic key: the interner pre-reserves
    # a per-service catch-all row before each service's first named pair
    # (r4 overflow semantics), so ids are NOT dense k+1 anymore
    kid_of = np.zeros(n_keys, np.int32)
    for k in range(n_keys):
        nid = vocab.span_names.intern(f"op{k:03d}")
        kid_of[k] = vocab.key_id((k % n_services) + 1, nid)
    assert (kid_of > 0).all() and len(set(kid_of.tolist())) == n_keys

    ts_min = np.uint32(29_000_000)
    start = time.perf_counter()
    done = 0
    while done < total:
        n = min(batch, total - done)
        i = np.arange(done, done + batch, dtype=np.uint32)
        k = i % np.uint32(n_keys)
        dur = _dur_of(k, i // np.uint32(n_keys))
        valid = np.arange(batch) < n
        u0 = np.zeros(batch, np.uint32)
        cols = SpanColumns(
            trace_h=_hash2_np(i + np.uint32(1), u0), tl0=i + np.uint32(1),
            tl1=u0, s0=i + np.uint32(1), s1=u0, p0=u0, p1=u0,
            shared=np.zeros(batch, bool),
            kind=np.zeros(batch, np.int32),
            svc=(k.astype(np.int32) % n_services) + 1,
            rsvc=np.zeros(batch, np.int32),
            key=kid_of[k],
            err=np.zeros(batch, bool),
            dur=dur, has_dur=valid,
            ts_min=np.full(batch, ts_min, np.uint32),
            valid=valid,
        )
        agg.ingest(cols)
        done += n
    agg.block_until_ready()
    ingest_s = time.perf_counter() - start

    import jax.numpy as jnp

    digest = agg.merged_digest()
    qs = jnp.asarray(np.array([0.5, 0.99], np.float32))
    got = np.asarray(tdigest.quantile(digest, qs))

    worst = 0.0
    checked = failed = 0
    for k in range(n_keys):
        n_k = total // n_keys + (1 if k < total % n_keys else 0)
        if n_k < 300:
            continue
        # exact truth, regenerated vectorized
        d = np.sort(
            _dur_of(np.full(n_k, k, np.uint32), np.arange(n_k)).astype(
                np.float64
            )
        )
        kid = int(kid_of[k])
        # t-digest's guarantee is in RANK space (quantile error ~ eps at
        # the tails), not value space — for long-tailed durations a tiny
        # rank error is a large value error, so score the empirical rank
        # of each estimate instead of comparing values.
        rank50 = np.searchsorted(d, float(got[kid, 0])) / n_k
        rank99 = np.searchsorted(d, float(got[kid, 1])) / n_k
        err = max(abs(rank50 - 0.5), abs(rank99 - 0.99))
        worst = max(worst, err)
        ok_key = abs(rank50 - 0.5) < 0.02 and abs(rank99 - 0.99) < 0.01
        checked += 1
        failed += 0 if ok_key else 1
    ok = checked > 0 and failed == 0
    _emit(config="config1", passed=ok, spans=total, keys_checked=checked,
          keys_failed=failed, worst_rank_err=round(worst, 4),
          wall_spans_per_sec=round(total / ingest_s))
    return ok


def _link_corpus_batch(
    lo_pair: int, n_pairs: int, n_services: int, ts_min: int,
    pad_pairs: int = 0,
):
    """Columnar batch of ``n_pairs`` shared client/server RPC pairs with
    CLOSED-FORM link truth: pair i emits exactly one (svc_a(i) ->
    svc_b(i)) edge, error iff i % 8 == 0 (the server half carries the
    tag). Vectorized numpy construction — no Span objects — so the
    harness can reach BASELINE config2's 10M-span spec scale (the r2
    harness generated objects + ran the host linker over everything at
    7.4k spans/s; VERDICT r2 order 5).
    """
    from zipkin_tpu.tpu.columnar import SpanColumns, _hash2_np

    gen_pairs = max(pad_pairs, n_pairs)  # pad: constant lane count keeps
    i = np.arange(lo_pair, lo_pair + gen_pairs, dtype=np.uint32)  # one jit shape
    a = (i % np.uint32(n_services)).astype(np.int32) + 1
    b = ((i + 1 + i // np.uint32(n_services)) % np.uint32(n_services)).astype(
        np.int32
    ) + 1
    b = np.where(b == a, (b % n_services) + 1, b)
    err = (i % 8) == 0
    n = 2 * gen_pairs
    live = np.arange(gen_pairs) < n_pairs

    def interleave(client, server):
        out = np.empty(n, client.dtype)
        out[0::2] = client
        out[1::2] = server
        return out

    tl0 = i + np.uint32(1)
    tl1 = np.full(gen_pairs, 0x5EED, np.uint32)
    hi32 = _hash2_np(np.zeros(gen_pairs, np.uint32), np.zeros(gen_pairs, np.uint32))
    trace_h = _hash2_np(_hash2_np(tl0, tl1), hi32)
    dup = lambda x: interleave(x, x)
    zeros = np.zeros(n, np.uint32)
    cols = SpanColumns(
        trace_h=dup(trace_h), tl0=dup(tl0), tl1=dup(tl1),
        s0=dup(i + np.uint32(9)), s1=dup(np.zeros(gen_pairs, np.uint32)),
        p0=zeros, p1=zeros,
        shared=interleave(
            np.zeros(gen_pairs, bool), np.ones(gen_pairs, bool)
        ),
        kind=interleave(
            np.full(gen_pairs, 1, np.int32), np.full(gen_pairs, 2, np.int32)
        ),
        svc=interleave(a, b),
        rsvc=np.zeros(n, np.int32),
        key=np.zeros(n, np.int32),
        err=interleave(np.zeros(gen_pairs, bool), err),
        dur=dup((i % 10_000 + 1).astype(np.uint32)),
        has_dur=np.ones(n, bool),
        ts_min=np.full(n, ts_min, np.uint32),
        valid=dup(live),
    )
    return cols, (a[:n_pairs], b[:n_pairs], err[:n_pairs])


def config2() -> bool:
    """Device link aggregation at spec scale (10M spans) vs closed-form
    truth, with the host DependencyLinker cross-checking a 1-in-64 trace
    sample — the oracle stays in the loop at object speed while the
    volume runs at array speed. (Exhaustive device-vs-oracle parity on
    adversarial tree shapes is tests/test_parity_fuzz.py's job; this
    config proves the COUNTS at volume, through the production
    ring-rollup retention machinery rather than an oversized ring.)"""
    from tests.fixtures import TODAY_US
    from zipkin_tpu.internal.dependency_linker import DependencyLinker
    from zipkin_tpu.model.span import Endpoint, Kind, Span
    from zipkin_tpu.parallel.mesh import make_mesh
    from zipkin_tpu.parallel.sharded import ShardedAggregator
    from zipkin_tpu.tpu.columnar import Vocab
    from zipkin_tpu.tpu.state import AggConfig

    total = int(os.environ.get("EVAL_LINK_SPANS", 10_000_000))
    oracle_every = int(os.environ.get("EVAL_LINK_ORACLE_SAMPLE", 64))
    batch = 65_536
    n_services = 30
    cfg = AggConfig()
    agg = ShardedAggregator(cfg, mesh=make_mesh(1))
    vocab = Vocab(cfg.max_services, cfg.max_keys)
    for s in range(n_services):
        vocab.services.intern(f"svc{s:02d}")  # id s+1, matching the corpus
    ts_min = int(TODAY_US // 60_000_000)

    s1 = cfg.max_services
    calls_true = np.zeros((s1, s1), np.int64)
    errs_true = np.zeros((s1, s1), np.int64)
    linker = DependencyLinker()
    sample_calls = np.zeros((s1, s1), np.int64)
    sample_errs = np.zeros((s1, s1), np.int64)

    n_pairs_total = total // 2
    done = 0
    start = time.perf_counter()
    while done < n_pairs_total:
        n_pairs = min(batch // 2, n_pairs_total - done)
        cols, (a, b, err) = _link_corpus_batch(
            done, n_pairs, n_services, ts_min, pad_pairs=batch // 2
        )
        agg.ingest(cols)
        np.add.at(calls_true, (a, b), 1)
        np.add.at(errs_true, (a, b), err.astype(np.int64))
        # oracle sample: every Nth pair becomes real Span objects through
        # the reference-semantics host linker
        pick = np.arange(n_pairs) % oracle_every == 0
        for pa, pb, pe, pi in zip(
            a[pick], b[pick], err[pick], np.nonzero(pick)[0] + done
        ):
            tid = f"{int(pi) + 1:016x}"
            sid = f"{int(pi) + 9:016x}"
            trace = [
                Span.create(
                    trace_id=tid, id=sid, kind=Kind.CLIENT, name="op",
                    timestamp=TODAY_US, duration=10,
                    local_endpoint=Endpoint.create(f"svc{pa - 1:02d}", "10.0.0.1"),
                ),
                Span.create(
                    trace_id=tid, id=sid, kind=Kind.SERVER, shared=True,
                    name="op", timestamp=TODAY_US, duration=8,
                    local_endpoint=Endpoint.create(f"svc{pb - 1:02d}", "10.0.0.2"),
                    tags={"error": ""} if pe else {},
                ),
            ]
            linker.put_trace(trace)
            np.add.at(sample_calls, ([pa], [pb]), 1)
            np.add.at(sample_errs, ([pa], [pb]), int(pe))
        done += n_pairs
    agg.block_until_ready()
    elapsed = time.perf_counter() - start

    calls, errors = agg.dependency_matrices(0, 2**31)
    device_mism = int(
        (calls.astype(np.int64) != calls_true).sum()
        + (errors.astype(np.int64) != errs_true).sum()
    )
    # oracle cross-check: the host linker over the sampled traces must
    # reproduce the closed-form truth restricted to the sample
    oracle = {
        (l.parent, l.child): (l.call_count, l.error_count)
        for l in linker.link()
    }
    oracle_mism = 0
    for p, c in zip(*np.nonzero(sample_calls)):
        want = (int(sample_calls[p, c]), int(sample_errs[p, c]))
        got = oracle.get((f"svc{p - 1:02d}", f"svc{c - 1:02d}"))
        oracle_mism += got != want
    oracle_mism += sum(
        1
        for (pn, cn) in oracle
        if not (
            pn.startswith("svc")
            and sample_calls[int(pn[3:]) + 1, int(cn[3:]) + 1] > 0
        )
    )
    ok = device_mism == 0 and oracle_mism == 0
    _emit(config="config2", passed=ok, spans=done * 2,
          edges=int((calls_true > 0).sum()), mismatches=device_mism,
          oracle_sampled_traces=linker_traces(linker),
          oracle_mismatches=oracle_mism,
          spans_per_sec=round(done * 2 / elapsed))
    return ok


def linker_traces(linker) -> int:
    return int(sum(l.call_count for l in linker.link()))


def config3() -> bool:
    """HLL cardinality at 100M distinct trace ids THROUGH THE PRODUCTION
    INGEST PATH (VERDICT r4 order 5): spans with distinct ids stream
    through ``ShardedAggregator.ingest`` — the same fused jit'd
    ingest_step production traffic takes, with the HLL update inside it
    and the estimate read via the production psum/pmax merge program —
    not a bare ``hll.update`` loop on standalone registers. The rate
    reported is therefore FULL ingest-step throughput (digests, links,
    histograms all live), not an HLL-only number; both the global row
    and the per-service rows gate."""
    from zipkin_tpu.ops import hll
    from zipkin_tpu.parallel.mesh import make_mesh
    from zipkin_tpu.parallel.sharded import ShardedAggregator
    from zipkin_tpu.tpu.columnar import SpanColumns, _hash2_np
    from zipkin_tpu.tpu.state import AggConfig

    total = int(os.environ.get("EVAL_HLL", 100_000_000))
    batch = 65_536
    n_services = 32
    cfg = AggConfig()
    agg = ShardedAggregator(cfg, mesh=make_mesh(1))
    u0 = np.zeros(batch, np.uint32)
    hi32 = _hash2_np(u0, u0)  # th lanes are zero: production trace_h rule
    valid = np.ones(batch, bool)
    zi32 = np.zeros(batch, np.int32)
    zb = np.zeros(batch, bool)
    lane = np.arange(batch, dtype=np.uint64)

    def cols_at(done: int) -> SpanColumns:
        i64 = np.uint64(done + 1) + lane  # distinct 64-bit trace ids
        tl0 = (i64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        tl1 = (i64 >> np.uint64(32)).astype(np.uint32)
        svc = (i64 % np.uint64(n_services)).astype(np.int32) + 1
        return SpanColumns(
            trace_h=_hash2_np(_hash2_np(tl0, tl1), hi32),
            tl0=tl0, tl1=tl1, s0=tl0, s1=u0, p0=u0, p1=u0,
            shared=zb, kind=zi32, svc=svc, rsvc=zi32,
            key=(i64 % np.uint64(200)).astype(np.int32) + 1,
            err=zb, dur=(tl0 % np.uint32(10_000)) + np.uint32(1),
            has_dur=valid, ts_min=np.full(batch, 29_000_000, np.uint32),
            valid=valid,
        )

    agg.ingest(cols_at(0))  # warm: compiles outside the timed window
    agg.block_until_ready()
    done = batch
    start = time.perf_counter()
    while done < total:
        agg.ingest(cols_at(done))
        done += batch
    agg.block_until_ready()
    elapsed = time.perf_counter() - start
    est_rows = agg.cardinalities()  # production read: pmax merge on device
    est = float(est_rows[cfg.global_hll_row])
    err = abs(est - done) / done
    bound = 3 * hll.standard_error(cfg.hll_precision)
    per_svc = est_rows[1 : n_services + 1]
    svc_true = done / n_services
    svc_err = float(np.abs(per_svc - svc_true).max() / svc_true)
    ok = err < bound and svc_err < bound
    _emit(config="config3", passed=ok, ids=done, estimate=round(est),
          rel_err=round(err, 5), worst_service_rel_err=round(svc_err, 5),
          path="ShardedAggregator.ingest (production fused step)",
          ingest_spans_per_sec=round((done - batch) / elapsed))
    return ok


def config4() -> bool:
    """Streaming replay + mixed Lens query load at full-size AggConfig.

    r5 (VERDICT r4 order 1) makes the replay REAL rather than a
    recycled soak:

    - **Distinct identities at line rate**: the corpus is one encoded
      template whose trace ids carry a fixed 8-hex prefix; every batch
      byte-patches the prefix, so ~1B DISTINCT trace ids stream through
      dedup/HLL/archive (the archive_soak technique). The device HLL
      estimate is gated against the exact distinct count.
    - **Vocab churn at/over capacity**: service and span names embed a
      rotation token patched every EVAL_ROTATE_EVERY batches, so the
      cumulative key space runs far past max_services/max_keys and the
      per-service catch-all overflow path stays live for most of the
      run (gated: overflow counters must be nonzero at full scale).
    - **The disk archive runs LIVE on the ingest path** (budget-bounded;
      retention expected at 1B), and in-window complete-trace probes
      gate — "every acked trace queryable" is exercised at flagship
      scale, not in a separate soak.

    Query latency is measured two ways and BOTH gate the verdict:
    mid-stream (queueing behind the async ingest pipeline — in-flight
    depth bounded by EVAL_SYNC_EVERY_BATCHES) and quiesced (the query
    programs themselves, gated at the <50ms p50 SLO via XPlane device
    capture). min/p50/p99 all reported.
    """
    import dataclasses

    from tests.fixtures import lots_of_spans
    from zipkin_tpu import native
    from zipkin_tpu.model import json_v2
    from zipkin_tpu.parallel.mesh import make_mesh
    from zipkin_tpu.tpu.state import AggConfig
    from zipkin_tpu.tpu.store import TpuStorage

    total = int(os.environ.get("EVAL_REPLAY_SPANS", 2_000_000))
    if os.environ.get("EVAL_SMALL"):  # CPU smoke of the harness itself
        cfg = AggConfig(
            max_services=64, max_keys=256, hll_precision=8,
            digest_centroids=16, digest_buffer=1 << 15,
            ring_capacity=1 << 15, link_buckets=4, hist_slices=2,
        )
    else:
        cfg = AggConfig()
    batch = min(65_536, cfg.rollup_segment, cfg.digest_buffer)
    # EVAL_REPLAY_DURABLE=<dir>: run the replay with the full durability
    # plane live (WAL + periodic snapshots truncating covered segments),
    # reporting disk churn — the 1B-scale gate requires WAL/snapshot
    # growth bounded, not just throughput (VERDICT r3 order 3)
    durable_dir = os.environ.get("EVAL_REPLAY_DURABLE")
    # EVAL_RESUME_DIR=<dir> (ISSUE 3): crash-resumable flagship run. The
    # store boots by restoring <dir>/snap + replaying <dir>/wal, batch
    # indexing resumes from the eval_cursor.json sidecar (trace-id
    # prefixes stay disjoint across windows), and a ResumeSupervisor
    # watches the wire rate — a degraded window (or the per-window
    # deadline EVAL_WINDOW_DEADLINE_S) drains, snapshots, records the
    # cursor and exits EX_RESTART(75) for evals/resume_driver.py to
    # relaunch. Span counts ACCUMULATE across windows toward the target.
    resume_dir = os.environ.get("EVAL_RESUME_DIR")
    if resume_dir:
        durable_dir = resume_dir
    cursor_path = (
        os.path.join(resume_dir, "eval_cursor.json") if resume_dir else None
    )
    cursor = {"next_batch": 0, "distinct_traces": 0, "windows": 0}
    if cursor_path and os.path.exists(cursor_path):
        cursor.update(json.load(open(cursor_path)))
    it0 = cursor["next_batch"]
    snap_every = int(os.environ.get("EVAL_SNAPSHOT_EVERY_BATCHES", 448))
    # disk archive on the ingest path (r5): default ON at full scale,
    # budget-bounded so retention churns live; EVAL_ARCHIVE_DIR=off
    # disables (for A/B), EVAL_ARCHIVE_BYTES sets the budget
    arc_env = os.environ.get("EVAL_ARCHIVE_DIR", "")
    if arc_env.lower() in ("off", "none", "0"):
        arc_dir = None
    elif arc_env:
        arc_dir = arc_env
    else:
        import tempfile as _tf

        arc_dir = _tf.mkdtemp(prefix="config4_archive_")
    arc_bytes = int(os.environ.get("EVAL_ARCHIVE_BYTES", 12 << 30))
    arc_kw = dict(
        archive_dir=arc_dir, archive_max_bytes=arc_bytes,
        # small segments let a smoke run seal enough of them to ARM the
        # zone-map pruning gate (search_probe_gate below)
        archive_segment_bytes=int(
            os.environ.get("EVAL_ARCHIVE_SEGMENT_BYTES", 64 << 20)
        ),
    ) if arc_dir else {}
    # bound the async dispatch queue: sync every N batches so mid-stream
    # queries never queue behind an unbounded pipeline (r4's 488/500ms
    # whisker margin was mostly queue depth); 0 disables
    sync_every = int(os.environ.get("EVAL_SYNC_EVERY_BATCHES", 4))
    if durable_dir:
        from zipkin_tpu.storage.tpu import TpuStorage as _Durable

        store = _Durable(
            config=cfg, num_devices=1, batch_size=batch,
            max_span_count=100_000,
            checkpoint_dir=durable_dir + "/snap",
            wal_dir=durable_dir + "/wal",
            **arc_kw,
        )
    else:
        store = TpuStorage(
            config=cfg, mesh=make_mesh(1), pad_to_multiple=batch,
            archive_max_span_count=100_000,
            **arc_kw,
        )
    # template with patchable identity + rotation tokens: trace ids get
    # a fixed hex prefix (patched per batch -> fresh ids), service/span
    # names embed "roto0000" (patched per rotation epoch -> vocab churn)
    rotate_every = int(os.environ.get("EVAL_ROTATE_EVERY", 256))
    raw_corpus = lots_of_spans(batch, seed=400, services=40, span_names=80)

    def _tok(ep):  # 8 chars, non-hex prefix so it never collides with ids
        return f"rt{ep:06x}"

    template = []
    for s in raw_corpus:
        ep = dataclasses.replace(
            s.local_endpoint, service_name=s.local_service_name + "-roto0000"
        )
        rep = (
            dataclasses.replace(
                s.remote_endpoint,
                service_name=s.remote_service_name + "-roto0000",
            )
            if s.remote_endpoint is not None
            else None
        )
        template.append(
            dataclasses.replace(
                s, trace_id="feedface" + s.trace_id[8:],
                name=(s.name or "op") + "-roto0000",
                local_endpoint=ep, remote_endpoint=rep,
            )
        )
    payload_t = json_v2.encode_span_list(template)
    # exact distinct-trace count per patched batch (suffix collisions
    # inside the template are counted once; prefixes are disjoint)
    distinct_per_batch = len({s.trace_id for s in template})
    probe_tid_t = template[0].trace_id
    probe_n = sum(1 for x in template if x.trace_id == probe_tid_t)
    # getTraces search probes (ISSUE 4 satellite): the SELECTIVE query
    # names an epoch-0 rotated service — once the rotation moves past
    # epoch 0, segments sealed under later tokens cannot contain that
    # service id, so the archive's zone-map sidecars must prune them
    # without touching their pages (gated: archiveSearchSegmentsSkipped
    # rises). The BROAD query carries no predicates and early-stops on
    # the newest segments. Both ride the production getTraces path.
    sel_service = template[0].local_service_name.replace(
        "roto0000", _tok(0)
    )
    search_skipped0 = int(
        store.ingest_counters().get("archiveSearchSegmentsSkipped", 0)
    )

    rotate_every = max(rotate_every, 1)

    def patched(it: int):
        tag = f"{0x10000000 + it:08x}".encode()
        rot = _tok(it // rotate_every).encode()
        return (
            payload_t.replace(b"feedface", tag).replace(b"roto0000", rot),
            probe_tid_t.replace("feedface", tag.decode()),
        )

    corpus = template
    end_ts = max(s.timestamp for s in corpus if s.timestamp) // 1000 + 3_600_000
    lookback = 1000 * 86_400_000
    fast = native.available()
    resumed_spans = store.ingest_counters()["spans"] if resume_dir else 0
    if fast:
        # warm EVERY program the stream can hit (all fused step variants
        # + flush + rollup) — first compiles take minutes and must not
        # land inside the measurement
        store.warm(payload_t)
        sent = store.ingest_counters()["spans"]
    else:  # pragma: no cover - no C toolchain
        sent = resumed_spans

    KINDS = (
        "dependencies", "dependencies_fresh", "percentiles", "windowed",
        "cardinalities", "search_selective", "search_broad",
    )
    # host-side scans (from-scratch rebuild + archive searches): reported
    # with p50/p99 like everything else but excluded from the device-read
    # latency gates — they decode spans on the host by design
    HOST_SIDE = ("dependencies_fresh", "search_selective", "search_broad")
    lat: dict = {k: [] for k in KINDS}  # mid-stream (under ingest load)
    quiesced: dict = {k: [] for k in KINDS}

    def timed(kind, fn, into):
        q0 = time.perf_counter()
        fn()
        into[kind].append((time.perf_counter() - q0) * 1e3)

    batches = 0

    def query_round(into, fresh_version=True):
        # fresh_version bumps past BOTH the memoized pulls and the cached
        # link context (a post-write first query); fresh_version=False
        # re-pulls device reads but rides the cached context (the warm
        # repeated-query path a polling UI takes between writes)
        if fresh_version:
            store.agg.write_version += 1
        else:
            store.invalidate_read_cache()
        # the UI path: dependency answers may ride the bounded-staleness
        # cache under load (TPU_DEPS_MAX_STALE_MS) — exactly what a
        # polling Lens client experiences
        timed("dependencies",
              lambda: store.get_dependencies(end_ts, lookback).execute(),
              into)
        # the worst case: force a from-scratch recompute (answer + device
        # read caches cleared; under load the advanced write_version
        # also forces the link-context rebuild)
        def fresh():
            store.invalidate_read_cache()
            store.get_dependencies(end_ts, lookback).execute()

        timed("dependencies_fresh", fresh, into)
        timed("percentiles",
              lambda: store.latency_quantiles([0.5, 0.99]), into)
        timed("windowed",
              lambda: store.latency_quantiles(
                  [0.5, 0.99], end_ts=end_ts, lookback=lookback), into)
        timed("cardinalities", store.trace_cardinalities, into)
        if arc_dir:
            from zipkin_tpu.storage.spi import QueryRequest

            timed("search_selective",
                  lambda: store.get_traces_query(QueryRequest(
                      end_ts=end_ts, lookback=lookback, limit=5,
                      service_name=sel_service)).execute(), into)
            timed("search_broad",
                  lambda: store.get_traces_query(QueryRequest(
                      end_ts=end_ts, lookback=lookback, limit=10,
                  )).execute(), into)

    if fast:
        # compile the query programs outside the timed window (first-call
        # jit cost is not query latency)
        query_round(lat)
        for v in lat.values():
            v.clear()

    warm = sent  # spans ingested before the timed window opened
    probe_every = int(os.environ.get("EVAL_PROBE_EVERY", 64))
    # graceful wall deadline (seconds, 0 = none): without a deadline a
    # degraded window turns the flagship run into an artifact-less stall. On expiry the
    # stream STOPS CLEANLY and every gate evaluates at the scale
    # actually reached — reported beside the target, never silently.
    deadline_s = float(os.environ.get("EVAL_WALL_DEADLINE_S", 0) or 0)
    progress_every = int(os.environ.get("EVAL_PROGRESS_EVERY", 128))
    deadline_hit = False
    probes: list = []
    probes_incomplete = 0
    acked: list = []  # patched probe tids, oldest first (bounded)
    distinct_traces = cursor["distinct_traces"]
    sup = None
    tripped = None
    if resume_dir:
        from zipkin_tpu.runtime.supervisor import ResumeSupervisor

        sup = ResumeSupervisor(
            store,
            window_s=float(os.environ.get("EVAL_SUP_WINDOW_S", 5.0)),
            degraded_fraction=float(
                os.environ.get("EVAL_DEGRADED_FRACTION", 0.25)
            ),
            degraded_windows=int(os.environ.get("EVAL_DEGRADED_WINDOWS", 3)),
            deadline_s=float(os.environ.get("EVAL_WINDOW_DEADLINE_S", 0) or 0),
        )
        sup.observe(sent)  # establishes the window clock
    start = time.perf_counter()
    while sent < total:
        if deadline_s and time.perf_counter() - start > deadline_s:
            deadline_hit = True
            break
        if fast:
            payload, tid = patched(it0 + batches)
            n, _ = store.ingest_json_fast(payload)
            acked.append(tid)
            distinct_traces += distinct_per_batch
        else:  # pragma: no cover
            chunk = corpus[:batch]
            store.accept(chunk).execute()
            n = len(chunk)
        sent += n
        batches += 1
        if sup is not None:
            tripped = sup.observe(sent)
            if tripped:
                break
        if sync_every and batches % sync_every == 0:
            # bound the in-flight dispatch queue (see docstring)
            store.agg.block_until_ready()
        if batches % 8 == 0:  # mixed query load mid-stream
            query_round(lat)
        if fast and arc_dir and batches % probe_every == 0:
            # complete-trace probe of a trace acked ~half a window ago:
            # recent enough to be in archive retention, old enough to
            # prove the ack was durable, under full ingest load
            probe = acked[max(0, len(acked) - probe_every // 2 - 1)]
            p0 = time.perf_counter()
            got = store.get_trace(probe).execute()
            probes.append((time.perf_counter() - p0) * 1e3)
            if len(got) != probe_n:
                probes_incomplete += 1
            if len(acked) > 4 * probe_every:
                del acked[: 2 * probe_every]
        if durable_dir and batches % snap_every == 0:
            # the durability plane under load: snapshot clones the state
            # on device (ms under the lock), pulls lock-free, truncates
            # WAL segments the snapshot covers — disk stays bounded
            store.snapshot()
        if progress_every and batches % progress_every == 0:
            print(json.dumps({
                "progress": sent,
                "of": total,
                "spans_per_sec": round(
                    (sent - warm) / (time.perf_counter() - start)
                ),
            }), file=sys.stderr, flush=True)
    store.agg.block_until_ready()

    def _write_cursor():
        tmp = cursor_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({
                "next_batch": it0 + batches,
                "distinct_traces": distinct_traces,
                "windows": cursor["windows"] + 1,
                "spans": sent,
            }, f)
        os.replace(tmp, cursor_path)

    if tripped:
        # degraded/deadline window: drain + exit snapshot, record the
        # cursor, and exit restartable — the relaunch restores from the
        # snapshot and the cumulative span count keeps climbing
        from zipkin_tpu.runtime.supervisor import EX_RESTART

        sup.finalize()
        _write_cursor()
        _emit(config="config4", window=cursor["windows"] + 1,
              window_tripped=tripped, window_exit=EX_RESTART,
              resumed_from_spans=resumed_spans, spans=sent,
              target_spans=total, supervisor=sup.stats(),
              restore=dict(getattr(store, "restore_stats", {})),
              window_spans_per_sec=round(
                  (sent - warm) / max(time.perf_counter() - start, 1e-9)))
        sys.exit(EX_RESTART)

    if not lat["dependencies"]:
        query_round(lat)  # never skip the query half at small smoke scales
    elapsed = time.perf_counter() - start

    # Quiesced rounds: the mid-stream numbers include queueing behind the
    # async ingest pipeline (reads and writes share the chip). With the
    # stream drained these measure the query programs themselves — the
    # first round pays the per-version link-context rebuild, later rounds
    # ride the cached context (the polling-UI path between writes). The
    # staleness cache is disabled here: quiesced rounds must measure
    # device reads, not cache hits.
    store._deps_max_stale_ms = 0.0
    query_round(quiesced)
    for _ in range(7):
        query_round(quiesced, fresh_version=False)

    # Program-time capture (VERDICT r2 order 3): host-clock noise makes
    # wall-minus-floor unreliable, so the 50ms SLO gate conditions on
    # XPlane-captured DEVICE time per query program. Amortized programs
    # are excluded:
    # link_ctx is per-write-version (queries ride the cache), flush
    # advances ingest state the stream would flush anyway.
    program_ms: dict = {}
    capture_error = None
    trace_dir = None
    captured_round = False
    try:
        import tempfile as _tempfile

        import jax as _jax

        trace_dir = _tempfile.mkdtemp(prefix="config4_slo_trace_")
        with _jax.profiler.trace(trace_dir):
            # a FRESH round: write_version bumps, so the capture includes
            # spmd_edges_fresh — the first-query-after-write program the
            # r4 gate conditions on (plus the cached-read programs from
            # the same round's later queries)
            query_round(quiesced, fresh_version=True)
            captured_round = True
            # dispatch the BOUNDED amortized programs so their presence
            # check can fail loudly if a rename/regression hides them
            store.agg.rollup_now()
            store.agg.flush_now()
            store.agg.block_until_ready()
        from benchmarks.xplane_tools import device_op_totals, latest_xspace

        for op, (us, n) in device_op_totals(latest_xspace(trace_dir)).items():
            if op.startswith("jit_spmd_"):
                name = op.split("(")[0][len("jit_"):]
                program_ms[name] = round(
                    max(program_ms.get(name, 0.0), us / 1e3 / max(n, 1)), 3
                )
    except Exception as e:  # pragma: no cover - capture best-effort
        capture_error = str(e)
    finally:
        # the capture round's timings include profiler overhead: drop
        # them whether or not the xplane parse succeeded
        if captured_round:
            for v in quiesced.values():
                if v:
                    v.pop()
        if trace_dir:
            import shutil as _shutil

            _shutil.rmtree(trace_dir, ignore_errors=True)

    # Dispatch floor: a trivial one-scalar dispatch+fetch carries zero
    # meaningful device work; its wall time is the backend's fixed
    # per-dispatch cost. Program time = wall -
    # floor; benchmarks/query_slo.py holds the XPlane capture proving
    # the subtraction (committed as QUERY_SLO artifacts).
    import jax
    import jax.numpy as jnp

    tiny = jax.jit(lambda x: x + 1)
    tiny(jnp.uint32(1)).block_until_ready()
    floor = []
    for _ in range(15):
        f0 = time.perf_counter()
        np.asarray(tiny(jnp.uint32(1)))
        floor.append((time.perf_counter() - f0) * 1e3)
    floor_p50 = sorted(floor)[len(floor) // 2]

    def stats(xs):
        if not xs:
            return None
        xs = sorted(xs)
        return {"min": round(xs[0], 1), "p50": round(xs[len(xs) // 2], 1),
                "p99": round(xs[min(len(xs) - 1, int(len(xs) * 0.99))], 1)}

    counters = store.ingest_counters()
    q_stats = {k: stats(v) for k, v in lat.items()}
    quiesced_stats = {k: stats(v) for k, v in quiesced.items()}
    # Gates (r4, per VERDICT r3 order 1):
    # (a) captured DEVICE time per query program < 50ms — INCLUDING the
    #     fresh dependency read (spmd_edges_fresh: link context from the
    #     maintained union-sort order + windowed edges in one dispatch).
    #     spmd_link_ctx is no longer an amortized exclusion; the
    #     remaining amortized programs carry explicit bounds so cost
    #     cannot silently migrate into them (r3 weak #6);
    # (b) under-load p50 < 500ms for every UI read (the staleness cache
    #     + rolled-only reads are what a polling client rides);
    # (c) under-load from-scratch dependency rebuild p50 < 5s, reported.
    AMORTIZED_BOUNDS = {"spmd_flush": 150.0, "spmd_rollup": 150.0,
                        "spmd_quant_digest": 150.0}
    # flush + rollup are guaranteed to fire during the load phase, so
    # their ABSENCE from the capture fails the gate (a program that
    # stopped being captured must not vacuously pass its bound);
    # spmd_quant_digest is the superseded pend-fold read the eval no
    # longer dispatches — bounded only if something dispatches it.
    AMORTIZED_REQUIRED = {"spmd_flush", "spmd_rollup"}
    gated_programs = {
        k: v for k, v in program_ms.items() if k not in AMORTIZED_BOUNDS
    }
    if gated_programs:
        slo_program_ok = all(
            v < 50.0 for v in gated_programs.values()
        ) and all(
            program_ms[k] < bound if k in program_ms
            else k not in AMORTIZED_REQUIRED
            for k, bound in AMORTIZED_BOUNDS.items()
        )
        slo_gate = "program_device_time"
    else:
        # capture unavailable (no protoc / profiler broken): fall back
        # to wall-minus-floor — noisier, but never skips the gate
        # entirely
        slo_program_ok = all(
            s is None or (s["p50"] - floor_p50) < 50.0
            for k, s in quiesced_stats.items()
            if k not in HOST_SIDE
        )
        slo_gate = "wall_minus_floor"
    load_ok = all(
        s is None or s["p50"] < 500.0
        for k, s in q_stats.items() if k not in HOST_SIDE
    )
    fresh_ok = (
        q_stats["dependencies_fresh"] is None
        or q_stats["dependencies_fresh"]["p50"] < 5000.0
    )
    slo_ok = slo_program_ok and load_ok and fresh_ok
    trace_readable = bool(store.get_service_names().execute())

    # r5 realism gates (VERDICT r4 order 1) ------------------------------
    # (a) HLL vs the EXACT distinct-trace count (disjoint byte-patched
    #     prefixes make it closed-form); warm replays the template once
    #     more, contributing its distinct set a second time (same ids)
    hll_gate = None
    if fast and distinct_traces:
        true_distinct = distinct_traces + distinct_per_batch  # + warm
        from zipkin_tpu.ops import hll as _hll

        est = store.trace_cardinalities()["_global"]
        hll_err = abs(est - true_distinct) / true_distinct
        hll_bound = 3 * _hll.standard_error(cfg.hll_precision)
        hll_gate = {
            "distinct_trace_ids": true_distinct,
            "hll_estimate": round(est),
            "rel_err": round(hll_err, 5),
            "bound_3sigma": round(hll_bound, 5),
            "passed": hll_err < hll_bound,
        }
    # (b) complete-trace probes from the live archive under load
    probe_gate = None
    if fast and arc_dir and probes:
        ps = sorted(probes)
        probe_gate = {
            "probes": len(probes),
            "incomplete": probes_incomplete,
            "p50_ms": round(ps[len(ps) // 2], 1),
            "max_ms": round(ps[-1], 1),
            "passed": probes_incomplete == 0,
        }
    # (c) vocab churn kept the catch-all overflow path live whenever the
    #     rotation schedule pushed past capacity
    epochs = batches // rotate_every + 1
    # per-epoch vocab footprint derived from the template itself (every
    # epoch re-interns the same shape under rotated names)
    svcs_per_epoch = len(
        {s.local_service_name for s in template}
        | {s.remote_service_name for s in template if s.remote_service_name}
    )
    keys_per_epoch = len(
        {(s.local_service_name, s.name) for s in template}
    )
    churn_expected = fast and (
        svcs_per_epoch * epochs > cfg.max_services
        or keys_per_epoch * epochs > cfg.max_keys
    )
    overflow_seen = int(
        counters.get("serviceVocabOverflow", 0)
        + counters.get("keyVocabOverflow", 0)
        + counters.get("nativeVocabOverflow", 0)
    )
    churn_gate = None
    if churn_expected:
        churn_gate = {
            "rotation_epochs": epochs,
            "vocab_overflow_updates": overflow_seen,
            "passed": overflow_seen > 0,
        }
    # (d) selective search pruned by zone maps: once the rotation has
    #     moved past epoch 0 AND at least one later segment sealed, the
    #     epoch-0 service query must have skipped segments without
    #     touching their pages; with nothing to prune yet the gate stays
    #     disarmed (reported, trivially passing) — same policy as the
    #     churn gate above
    search_gate = None
    if fast and arc_dir and lat["search_selective"]:
        seg_count = int(counters.get("archiveSegments", 0))
        skipped = int(
            counters.get("archiveSearchSegmentsSkipped", 0)
            - search_skipped0
        )
        armed = epochs >= 2 and seg_count >= 2
        search_gate = {
            "selective_service": sel_service,
            "segments": seg_count,
            "segments_skipped": skipped,
            "armed": armed,
            "passed": (skipped > 0) if armed else True,
        }
    realism_ok = all(
        g is None or g["passed"]
        for g in (hll_gate, probe_gate, churn_gate, search_gate)
    )
    ok = (
        counters["spans"] == sent
        and bool(lat["dependencies"])
        and trace_readable  # fast mode must stay queryable (r1 gap)
        and realism_ok
    )
    durability = None
    if durable_dir:
        def _du(path):
            total = 0
            for root, _, files in os.walk(path):
                for f in files:
                    try:
                        total += os.path.getsize(os.path.join(root, f))
                    except OSError:
                        pass
            return total

        store.snapshot()  # final snapshot truncates the last WAL tail
        durability = {
            "snapshots_taken": batches // max(snap_every, 1) + 1,
            "wal_bytes_final": _du(durable_dir + "/wal"),
            "snapshot_bytes_final": _du(durable_dir + "/snap"),
        }
    archive_stats = None
    if arc_dir:
        archive_stats = {
            k: v for k, v in counters.items() if k.startswith("archive")
        }
    if resume_dir:
        _write_cursor()
    _emit(config="config4", passed=bool(ok and slo_ok), spans=sent,
          target_spans=total, wall_deadline_hit=deadline_hit,
          window=cursor["windows"] + 1 if resume_dir else None,
          resumed_from_spans=resumed_spans if resume_dir else None,
          restore=dict(getattr(store, "restore_stats", {}))
          if resume_dir else None,
          supervisor=sup.stats() if sup else None,
          fast_path=fast,
          sustained_spans_per_sec=round((sent - warm) / elapsed),
          distinct_identity_gate=hll_gate,
          archive_probe_gate=probe_gate,
          vocab_churn_gate=churn_gate,
          search_probe_gate=search_gate,
          archive=archive_stats,
          rotate_every_batches=rotate_every,
          sync_every_batches=sync_every,
          query_rounds=len(lat["dependencies"]),
          query_latency_under_load_ms=q_stats,
          query_latency_quiesced_ms=quiesced_stats,
          dispatch_floor_ms=round(floor_p50, 2),
          query_program_device_ms=program_ms,
          slo_gate=slo_gate,
          capture_error=capture_error,
          slo_program_device_under_50ms=slo_program_ok,
          under_load_p50_under_500ms=load_ok,
          archive_readable_in_fast_mode=trace_readable,
          durability=durability)
    return bool(ok and slo_ok)


def config5() -> bool:
    """Parse fan-out tier gate (ingest fan-out PR): wire-to-ack spans/s
    through the REAL server boundary with the durability plane live.

    Multi-core host (>=2 cores): proto3 over HTTP with >=2 parse
    workers, device-side sampling armed (~50% hash drop) and the WAL
    attached, must sustain >= EVAL_FANOUT_TARGET (default 1M) spans/s
    wire-to-ack.

    One-core host: the workers can only time-slice the core, so the
    gate is GRACEFUL DEGRADATION instead of a fixed number — the serial
    wire-to-ack rate must hold >= EVAL_FANOUT_DEGRADE_FRAC (default
    0.8) of the SAME-RUN in-process proto3 budget (the 510k JSON / 839k
    proto3 single-core figures of PROFILE_r06 §1, re-measured on this
    box so the gate tracks the hardware it runs on, not a calibration
    from another machine). The fan-out rate at 2 workers is measured
    and reported alongside as the degradation record, ungated.
    """
    import asyncio
    import tempfile

    from tests.fixtures import lots_of_spans
    from zipkin_tpu import native
    from zipkin_tpu.model import proto3
    from zipkin_tpu.sampling import RATE_ONE
    from zipkin_tpu.storage.tpu import TpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    if not native.available():
        _emit(config="config5", passed=False, error="native codec unavailable")
        return False

    cores = os.cpu_count() or 1
    total = int(os.environ.get("EVAL_FANOUT_SPANS", 1_048_576))
    target = float(os.environ.get("EVAL_FANOUT_TARGET", 1_000_000))
    degrade_frac = float(os.environ.get("EVAL_FANOUT_DEGRADE_FRAC", 0.8))
    batch = 65_536
    spans = lots_of_spans(2 * batch, seed=7, services=40, span_names=120)
    payloads = [
        proto3.encode_span_list(spans[i : i + batch])
        for i in range(0, len(spans), batch)
    ]

    def make_store(td: str) -> TpuStorage:
        store = TpuStorage(
            config=AggConfig(sampling=True), batch_size=batch,
            num_devices=1, wal_dir=td + "/wal",
        )
        # ~50% hash drop, rare clause off — sampling verdicts live on
        # the ack path, exactly the bench.py "sampling" mode arming
        rate = np.full_like(store.sampler.rate, RATE_ONE // 2)
        link = np.full_like(store.sampler.link, 1000)
        store.sampler.set_tables(rate, store.sampler.tail, link)
        store.install_sampler()
        return store

    # leg 0 — SAME-RUN in-process proto3 budget: parse+pack+route+feed
    # with sampling + WAL, no server boundary. The 1-core denominator.
    with tempfile.TemporaryDirectory() as td:
        store = make_store(td)
        store.warm(payloads[0])
        posted = 0
        t0 = time.perf_counter()
        i = 0
        while posted < total:
            accepted, dropped = store.ingest_json_fast(
                payloads[i % len(payloads)]
            )
            posted += accepted + dropped
            i += 1
        store.agg.block_until_ready()
        inproc_rate = posted / (time.perf_counter() - t0)
        store.close()

    async def wire_leg(workers: int, port: int) -> float:
        from benchmarks.server_bench import _drive
        from zipkin_tpu.server.app import ZipkinServer
        from zipkin_tpu.server.config import ServerConfig

        with tempfile.TemporaryDirectory() as td:
            storage = make_store(td)
            server = ZipkinServer(
                ServerConfig(
                    port=port, host="127.0.0.1", storage_type="tpu",
                    tpu_fast_ingest=True, tpu_mp_workers=workers,
                ),
                storage=storage,
            )
            await server.start()
            storage.warm(payloads[0])
            stats = {}
            elapsed = await _drive(
                server, port, "proto3", payloads, batch, total, stats
            )
            if server._mp_ingester is not None:
                t1 = time.perf_counter()
                await asyncio.to_thread(server._mp_ingester.drain)
                elapsed += time.perf_counter() - t1
            storage.agg.block_until_ready()
            await server.stop()
            # posted spans over wall time: sampling drops on the ack
            # path are WORK done, not throughput lost
            return total / elapsed

    port = int(os.environ.get("EVAL_FANOUT_PORT", 19619))
    legs = {}
    if cores >= 2:
        fan_workers = min(4, cores)
        legs[f"fanout_w{fan_workers}"] = round(
            asyncio.run(wire_leg(fan_workers, port)), 1
        )
        ok = legs[f"fanout_w{fan_workers}"] >= target
        gate = "multi_core_absolute"
    else:
        legs["serial_w0"] = round(asyncio.run(wire_leg(0, port)), 1)
        # degradation record: the fan-out under core starvation
        legs["fanout_w2"] = round(asyncio.run(wire_leg(2, port + 1)), 1)
        ok = legs["serial_w0"] >= degrade_frac * inproc_rate
        gate = "one_core_degradation"
    _emit(config="config5", passed=bool(ok), cores=cores, gate=gate,
          wire_to_ack_spans_per_sec=legs,
          inprocess_proto3_spans_per_sec=round(inproc_rate, 1),
          target_spans_per_sec=target, degrade_frac=degrade_frac,
          spans_posted=total, sampling="~50% hash drop", wal="attached")
    return bool(ok)


def config6() -> bool:
    """SLO watchdog trip/clear probe (ISSUE 9): induce a real burn on
    the query_fresh latency SLO through the production record site, and
    assert the multi-window watchdog trips within one long window, shows
    the alert gauge on /prometheus, then clears after recovery.

    The burn is physical, not mocked: forced fresh dependency reads
    (read cache invalidated each rep) run the real read path, and the
    over-threshold latency stream is recorded through the same
    ``obs.record("query_fresh", ...)`` call ``_cached_read`` uses — so
    the whole chain recorder -> windowed delta rings -> burn-rate
    evaluation -> alert gauges is the production chain. Windows are
    shrunk via the server config knobs (tick 0.25 s, short 2 s / long
    4 s) so both phases complete in seconds; the read path drives the
    ticks exactly as an unstarted embedded server would.
    """
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from tests.fixtures import TRACE
    from zipkin_tpu import obs
    from zipkin_tpu.model import json_v2
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig
    from zipkin_tpu.storage.tpu import TpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    short_s, long_s = 2.0, 4.0

    async def scenario() -> dict:
        storage = TpuStorage(
            config=AggConfig(max_services=64, max_keys=256,
                             hll_precision=9, digest_centroids=32,
                             ring_capacity=1 << 13),
            num_devices=1,
        )
        # warm the read path BEFORE the server builds its windowed
        # plane: the first fresh read pays the compile wall (seconds,
        # honestly recorded as query_fresh), which would otherwise be a
        # real — but uninteresting — burn. The windows baseline at
        # construction excludes everything recorded before it.
        storage.accept(TRACE).execute()
        end_ts = max(s.timestamp for s in TRACE) // 1000 + 60_000
        for _ in range(3):
            storage.invalidate_read_cache()
            storage.get_dependencies(end_ts, 86_400_000).execute()
        server = ZipkinServer(
            ServerConfig(
                storage_type="tpu",
                obs_windows_tick_s=0.25,
                obs_slo_short_s=short_s, obs_slo_long_s=long_s,
            ),
            storage=storage,
        )
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()

        async def verdict():
            body = await (await client.get("/api/v2/tpu/statusz")).json()
            return next(v for v in body["slo"]["specs"]
                        if v["name"] == "query_fresh_p99"), body["slo"]

        try:
            await client.post(
                "/api/v2/spans", data=json_v2.encode_span_list(TRACE),
                headers={"Content-Type": "application/json"})

            # phase A — healthy: fast fresh reads, no alert
            for _ in range(4):
                storage.invalidate_read_cache()
                await client.get(
                    f"/api/v2/dependencies?endTs={end_ts}&lookback=86400000")
                await asyncio.sleep(0.3)
            v, _ = await verdict()
            healthy = not v["alert"]

            # phase B — burn: every fresh read's latency lands way over
            # the 50 ms threshold (recorded through the production site)
            burn_t0 = time.perf_counter()
            tripped_after = None
            while time.perf_counter() - burn_t0 < 3 * long_s:
                storage.invalidate_read_cache()
                await client.get(
                    f"/api/v2/dependencies?endTs={end_ts}&lookback=86400000")
                for _ in range(4):
                    obs.record("query_fresh", 0.080)
                v, _ = await verdict()
                if v["alert"]:
                    tripped_after = time.perf_counter() - burn_t0
                    break
                await asyncio.sleep(0.3)
            text = await (await client.get("/prometheus")).text()
            alert_on_prom = \
                'zipkin_tpu_slo_alert{slo="query_fresh_p99"} 1' in text
            burn_on_prom = bool(
                [l for l in text.splitlines()
                 if l.startswith('zipkin_tpu_slo_burn_rate{slo="query_fresh_p99"')
                 and float(l.rsplit(" ", 1)[1]) >= 2.0])

            # phase C — recovery: healthy traffic only; the burn ages
            # out of the long window and the alert clears
            rec_t0 = time.perf_counter()
            cleared_after = None
            while time.perf_counter() - rec_t0 < 4 * long_s:
                storage.invalidate_read_cache()
                await client.get(
                    f"/api/v2/dependencies?endTs={end_ts}&lookback=86400000")
                v, slo = await verdict()
                if not v["alert"]:
                    cleared_after = time.perf_counter() - rec_t0
                    break
                await asyncio.sleep(0.3)
            return {
                "healthy_baseline": healthy,
                "tripped_after_s": tripped_after and round(tripped_after, 2),
                "alert_on_prometheus": alert_on_prom,
                "burn_rate_on_prometheus": burn_on_prom,
                "cleared_after_s": cleared_after and round(cleared_after, 2),
                "trips": slo["trips"], "clears": slo["clears"],
            }
        finally:
            await client.close()
            await server.stop()

    r = asyncio.run(scenario())
    ok = bool(
        r["healthy_baseline"]
        # trip must land within one evaluation (long) window of the
        # burn becoming visible, with one tick+poll of slack
        and r["tripped_after_s"] is not None
        and r["tripped_after_s"] <= long_s + 1.0
        and r["alert_on_prometheus"] and r["burn_rate_on_prometheus"]
        and r["cleared_after_s"] is not None
        and r["trips"] >= 1 and r["clears"] >= 1
    )
    _emit(config="config6", passed=ok, short_s=short_s, long_s=long_s,
          threshold_ms=50.0, **r)
    return ok


def config7() -> bool:
    """Accuracy-drift trip/clear probe (ISSUE 10): run the device plane
    with a deliberately undersized t-digest (C=4) and feed it a bimodal
    duration stream it cannot summarize — the accuracy observatory's
    shadow measures the real digest-vs-ground-truth p99 gap, the drift
    gauge (excess over the shadow's own sampling noise) crosses the
    0.20 SLO limit, and the digest_p99_relerr alert trips within one
    long window. Recovery (state cleared, well-behaved unimodal stream)
    clears it.

    The drift is physical, not mocked: spans go through POST
    /api/v2/spans, the shadow taps the production dispatch path, and
    the rollup pulls the actual device digest through the packed read
    chokepoint. The healthy phase proves the converse: the same C=4
    digest on a narrow unimodal stream shows near-zero drift, so the
    alert keys on genuine mis-sizing, not on the small digest per se.
    """
    import asyncio
    import random

    from aiohttp.test_utils import TestClient, TestServer

    import numpy as np

    from zipkin_tpu.model import json_v2
    from zipkin_tpu.model.span import Endpoint, Kind, Span
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig
    from zipkin_tpu.storage.tpu import TpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    short_s, long_s = 2.0, 4.0
    ep = Endpoint.create("checkout", "10.0.0.7")
    seq = [0]

    def make_spans(n, durs):
        out = []
        ts = int(time.time() * 1e6)
        for d in durs[:n]:
            seq[0] += 1
            out.append(Span.create(
                trace_id=f"{seq[0]:016x}", id=f"{seq[0]:016x}",
                name="charge", kind=Kind.SERVER, local_endpoint=ep,
                timestamp=ts + seq[0], duration=int(d),
            ))
        return out

    rng = random.Random(23)
    unimodal = lambda n: [rng.gauss(1000, 40) for _ in range(n)]
    bimodal = lambda n: [
        100_000 if rng.random() < 0.10 else 1000 for _ in range(n)
    ]

    async def scenario() -> dict:
        storage = TpuStorage(
            config=AggConfig(max_services=64, max_keys=256,
                             hll_precision=9, digest_centroids=4,
                             ring_capacity=1 << 13),
            num_devices=1,
        )
        core = getattr(storage, "delegate", storage)
        # warm the packed read programs BEFORE the server builds its
        # windowed plane: the first rollup's compile wall (seconds)
        # must not masquerade as phase-A time
        storage.accept(make_spans(64, unimodal(64))).execute()
        np.asarray(core.agg.merged_digest())
        np.asarray(core.agg.cardinalities())
        core.agg.dependency_edges(0, (1 << 32) - 1)
        server = ZipkinServer(
            ServerConfig(
                storage_type="tpu",
                obs_windows_tick_s=0.25,
                obs_slo_short_s=short_s, obs_slo_long_s=long_s,
                obs_shadow_rollup_s=0.0,  # roll up on every tick
            ),
            storage=storage,
        )
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()

        async def verdict():
            body = await (await client.get("/api/v2/tpu/statusz")).json()
            v = next(x for x in body["slo"]["specs"]
                     if x["name"] == "digest_p99_relerr")
            return v, body

        async def post(spans):
            resp = await client.post(
                "/api/v2/spans", data=json_v2.encode_span_list(spans),
                headers={"Content-Type": "application/json"})
            assert resp.status == 202

        try:
            # phase A — healthy: the undersized digest still summarizes
            # a narrow unimodal stream fine; drift stays under the limit
            await post(make_spans(2000, unimodal(2000)))
            await asyncio.sleep(4 * 0.25)
            v, body = await verdict()
            healthy = not v["alert"]
            healthy_drift = body["accuracy"]["gauges"][
                "accuracyDigestP99Drift"]

            # phase B — drift: bimodal stream the C=4 digest cannot
            # hold; the observatory measures the gap against its exact
            # reservoir and the drift gauge crosses the limit
            await post(make_spans(4000, bimodal(4000)))
            burn_t0 = time.perf_counter()
            tripped_after = None
            drift_seen = 0.0
            while time.perf_counter() - burn_t0 < 3 * long_s:
                v, body = await verdict()
                drift_seen = max(drift_seen, body["accuracy"]["gauges"][
                    "accuracyDigestP99Drift"])
                if v["alert"]:
                    tripped_after = time.perf_counter() - burn_t0
                    break
                await asyncio.sleep(0.2)
            text = await (await client.get("/prometheus")).text()
            alert_on_prom = \
                'zipkin_tpu_slo_alert{slo="digest_p99_relerr"} 1' in text

            # phase C — recovery: drop the poisoned state on both sides
            # of the comparison, return to well-behaved traffic
            core.clear()
            server._obs_shadow.reset()
            await post(make_spans(2000, unimodal(2000)))
            rec_t0 = time.perf_counter()
            cleared_after = None
            while time.perf_counter() - rec_t0 < 4 * long_s:
                v, body = await verdict()
                if not v["alert"]:
                    cleared_after = time.perf_counter() - rec_t0
                    break
                await asyncio.sleep(0.2)
            return {
                "healthy_baseline": healthy,
                "healthy_drift": round(healthy_drift, 4),
                "drift_seen": round(drift_seen, 4),
                "tripped_after_s": tripped_after and round(tripped_after, 2),
                "alert_on_prometheus": alert_on_prom,
                "cleared_after_s": cleared_after and round(cleared_after, 2),
                "trips": body["slo"]["trips"],
                "clears": body["slo"]["clears"],
            }
        finally:
            await client.close()
            await server.stop()

    r = asyncio.run(scenario())
    ok = bool(
        r["healthy_baseline"]
        and r["healthy_drift"] < 0.20
        and r["drift_seen"] > 0.20
        and r["tripped_after_s"] is not None
        and r["tripped_after_s"] <= long_s + 1.0
        and r["alert_on_prometheus"]
        and r["cleared_after_s"] is not None
        and r["trips"] >= 1 and r["clears"] >= 1
    )
    _emit(config="config7", passed=ok, short_s=short_s, long_s=long_s,
          drift_limit=0.20, digest_centroids=4, **r)
    return ok


def config8() -> bool:
    """Overload flood gate (ISSUE 13): a >=3x-queue-capacity concurrent
    flood through the real HTTP boundary while the device feed is
    artificially slow AND the WAL hits ENOSPC mid-flood. The gate:

    - admitted-traffic wire-to-ack p99 stays within the ack SLO this
      gate enforces (250 ms; the r01 flood measured ~213 ms),
    - every shed carries backoff guidance — Retry-After/X-Retry-After-Ms
      on the HTTP 429s, and a real-channel gRPC Report shed at B3 lands
      as RESOURCE_EXHAUSTED with retry-delay trailing metadata,
    - the disk-full window degrades to the flagged at-risk mode (not a
      crash) and the next committed snapshot clears it,
    - zero acked-span loss at durable parity: a cold boot from the same
      WAL/checkpoint dirs replays to exactly the acked span set,
    - the brownout ladder restores B0 within one long SLO window
      (300 ticks at the 1 Hz production cadence) of flood end.
    """
    import asyncio
    import tempfile

    import grpc
    import grpc.aio
    from aiohttp.test_utils import TestClient, TestServer

    from zipkin_tpu import faults
    from zipkin_tpu.model import json_v2, proto3
    from zipkin_tpu.model.span import Endpoint, Span
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig
    from zipkin_tpu.server.grpc import METHOD, GrpcCollectorServer
    from zipkin_tpu.storage.tpu import TpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    workers, depth = 1, 2
    capacity = workers * depth
    per = int(os.environ.get("EVAL_FLOOD_PER", 40))
    n_flood = int(os.environ.get("EVAL_FLOOD_N", 18))
    ack_slo_ms = float(os.environ.get("EVAL_FLOOD_ACK_SLO_MS", 250.0))
    long_window_ticks = 300
    cfg = dict(max_services=64, max_keys=256, hll_precision=8,
               digest_centroids=16, digest_buffer=1 << 14,
               ring_capacity=1 << 14, link_buckets=4, hist_slices=2)

    def spans_for(i, n):
        ep = Endpoint.create(service_name=f"svc{i % 8}", ip="10.0.0.1")
        return [
            Span.create(
                trace_id=f"{0xE800_0000 + i:016x}",
                id=f"{(i << 16) + j + 1:016x}",
                name=f"op{j % 8}",
                timestamp=1_753_000_000_000_000 + i * 1000 + j,
                duration=500 + j, local_endpoint=ep,
            )
            for j in range(n)
        ]

    async def scenario(tmp) -> dict:
        storage = TpuStorage(
            config=AggConfig(**cfg), num_devices=1, batch_size=512,
            checkpoint_dir=os.path.join(tmp, "ckpt"),
            wal_dir=os.path.join(tmp, "wal"),
        )
        server = ZipkinServer(
            ServerConfig(storage_type="tpu", tpu_fast_ingest=True,
                         tpu_mp_workers=workers, tpu_mp_queue_depth=depth,
                         obs_windows_enabled=False),
            storage=storage,
        )
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            # the flood window: device feed artificially slow (the real
            # reason queues back up in production) + ENOSPC on the first
            # WAL append — the disk fills WHILE the tier is overloaded
            faults.arm_resource("feed.latency", nth=1, count=6,
                                latency_ms=120)
            faults.arm_resource("wal.append", nth=1, count=1)

            async def post(i):
                t0 = time.perf_counter()
                resp = await client.post(
                    "/api/v2/spans",
                    data=json_v2.encode_span_list(spans_for(i, per)),
                    headers={"Content-Type": "application/json"},
                )
                await resp.release()
                return (resp.status, dict(resp.headers),
                        (time.perf_counter() - t0) * 1000.0)

            results = await asyncio.gather(
                *[post(i) for i in range(n_flood)]
            )
            acked = [r for r in results if r[0] == 202]
            shed = [r for r in results if r[0] == 429]
            guided = [
                r for r in shed
                if int(r[1].get("Retry-After", 0)) >= 1
                and int(r[1].get("X-Retry-After-Ms", 0)) > 0
            ]
            ack_p99_ms = (float(np.percentile([r[2] for r in acked], 99))
                          if acked else None)
            await asyncio.to_thread(server._mp_ingester.drain)
            faults.disarm()

            counters = storage.ingest_counters()
            degraded = (counters.get("walEnospc") == 1
                        and counters.get("walMissedRecords") == 1
                        and counters.get("durabilityAtRisk") == 1)
            acked_spans = per * len(acked)
            device_parity = \
                int(storage.agg.host_counters["spans"]) == acked_spans
            # recovery action: a committed snapshot re-covers the lost
            # WAL record (the device state it captures includes that
            # batch) and the at-risk flag clears
            snap_ok = storage.snapshot() is not None
            at_risk_cleared = \
                storage.ingest_counters()["durabilityAtRisk"] == 0

            revived = TpuStorage(
                config=AggConfig(**cfg), num_devices=1, batch_size=512,
                checkpoint_dir=os.path.join(tmp, "ckpt"),
                wal_dir=os.path.join(tmp, "wal"),
            )
            durable_parity = \
                int(revived.agg.host_counters["spans"]) == acked_spans
            revived.close()

            # gRPC twin of the 429: pin the ladder at B3 (the flood in
            # signal form) and Report over a real channel. B3 keeps a
            # 5% bulk lifeline, so probe a few times for a shed — an
            # admitted probe is the controller working as designed.
            ctl = server._overload
            for _ in range(8):
                ctl.evaluate({"critpathQueueSaturation": 0.9})
            grpc_guided = False
            gsrv = GrpcCollectorServer(server.collector,
                                       host="127.0.0.1", port=0)
            await gsrv.start()
            try:
                async with grpc.aio.insecure_channel(
                    f"127.0.0.1:{gsrv.port}"
                ) as ch:
                    method = ch.unary_unary(METHOD)
                    for k in range(5):
                        try:
                            await method(proto3.encode_span_list(
                                spans_for(0x9000 + k, 4)))
                        except grpc.aio.AioRpcError as err:
                            md = {key: v for key, v in
                                  (err.trailing_metadata() or ())}
                            grpc_guided = (
                                err.code()
                                == grpc.StatusCode.RESOURCE_EXHAUSTED
                                and md.get("retry-delay", "").endswith("s")
                                and int(md.get("retry-delay-ms", 0)) > 0
                            )
                            break
            finally:
                await gsrv.stop()

            # flood end: calm ticks only — B0 must come back inside one
            # long window (3 levels x dwell 5 + EMA decay is ~20 ticks)
            ticks_to_b0 = None
            for t in range(1, long_window_ticks + 1):
                if ctl.evaluate({"critpathQueueSaturation": 0.0}) == 0:
                    ticks_to_b0 = t
                    break

            return {
                "offered": n_flood,
                "queue_capacity": capacity,
                "offered_over_capacity": round(n_flood / capacity, 1),
                "acked": len(acked), "shed": len(shed),
                "sheds_with_guidance": len(guided),
                "acked_ack_p99_ms": ack_p99_ms and round(ack_p99_ms, 2),
                "enospc_degraded_not_crashed": degraded,
                "device_parity": device_parity,
                "snapshot_cleared_at_risk": snap_ok and at_risk_cleared,
                "durable_parity": durable_parity,
                "grpc_shed_guided": grpc_guided,
                "calm_ticks_to_b0": ticks_to_b0,
                "ladder_transitions": len(ctl.status()["history"]),
            }
        finally:
            faults.disarm()
            await client.close()
            await server.stop()

    with tempfile.TemporaryDirectory(prefix="eval_config8_") as tmp:
        r = asyncio.run(scenario(tmp))
    ok = bool(
        r["offered_over_capacity"] >= 3.0
        and r["acked"] > 0 and r["shed"] > 0
        and r["acked"] + r["shed"] == r["offered"]
        and r["sheds_with_guidance"] == r["shed"]
        and r["acked_ack_p99_ms"] is not None
        and r["acked_ack_p99_ms"] <= ack_slo_ms
        and r["enospc_degraded_not_crashed"]
        and r["device_parity"] and r["durable_parity"]
        and r["snapshot_cleared_at_risk"]
        and r["grpc_shed_guided"]
        and r["calm_ticks_to_b0"] is not None
        and r["calm_ticks_to_b0"] <= long_window_ticks
    )
    _emit(config="config8", passed=ok, ack_slo_ms=ack_slo_ms,
          long_window_ticks=long_window_ticks, **r)
    return ok


def config9() -> bool:
    """Tenant flood containment gate (ISSUE 18): three tenants share
    one server; tenant B floods >=3x its per-tenant ingest budget
    through the real HTTP boundary (``X-Tenant-Id`` header) while A and
    C stay inside theirs. The gate:

    - every 429 is B's, carries ``X-Shed-Scope: tenant`` /
      ``X-Shed-Tenant: B`` and Retry-After guidance derived from B's
      own bucket deficit; A and C are never shed,
    - A/C wire-to-ack p99 and mid-flood query p99 stay inside SLO, and
      the GLOBAL brownout ladder never leaves B0 (zero transitions) —
      containment, not degradation,
    - per-tenant admission posture: B at level >=2, A and C at 0,
      visible on /statusz and as ``{tenant=}`` prometheus families,
    - per-tenant acked attribution through the fan-out tier is exact
      (mpTenantTable spans == per * that tenant's 202s),
    - a gRPC Report as B over a real channel sheds RESOURCE_EXHAUSTED
      with ``shed-scope: tenant`` trailing metadata,
    - zero acked-span loss for every tenant across a MID-flood
      crash-resume (cold boot between flood waves replays exactly the
      acked set) and again at flood end,
    - calm ticks return B to level 0 within one long SLO window.
    """
    import asyncio
    import tempfile

    import grpc
    import grpc.aio
    from aiohttp.test_utils import TestClient, TestServer

    from zipkin_tpu.model import json_v2, proto3
    from zipkin_tpu.model.span import Endpoint, Span
    from zipkin_tpu.runtime.tenant import TENANT_HEADER
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig
    from zipkin_tpu.server.grpc import METHOD, GrpcCollectorServer
    from zipkin_tpu.storage.tpu import TpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    # queue capacity comfortably above concurrent offered load: the
    # per-tenant budget must be the ONLY control that sheds here
    workers, depth = 2, 16
    per = int(os.environ.get("EVAL_TENANT_SPANS_PER", 40))
    n_flood = int(os.environ.get("EVAL_TENANT_FLOOD_N", 16))
    n_calm_posts = 3
    ack_slo_ms = float(os.environ.get("EVAL_TENANT_ACK_SLO_MS", 250.0))
    query_slo_ms = float(os.environ.get("EVAL_TENANT_QUERY_SLO_MS", 250.0))
    long_window_ticks = 300
    cfg = dict(max_services=64, max_keys=256, hll_precision=8,
               digest_centroids=16, digest_buffer=1 << 14,
               ring_capacity=1 << 14, link_buckets=4, hist_slices=2)

    def spans_for(i, n):
        ep = Endpoint.create(service_name=f"svc{i % 8}", ip="10.0.0.1")
        return [
            Span.create(
                trace_id=f"{0xE900_0000 + i:016x}",
                id=f"{(i << 16) + j + 1:016x}",
                name=f"op{j % 8}",
                timestamp=1_753_000_000_000_000 + i * 1000 + j,
                duration=500 + j, local_endpoint=ep,
            )
            for j in range(n)
        ]

    # size B's budget off the real wire payload: burst = 4 payloads, so
    # a 16-payload burst is a 4x flood while A/C's 3 stay inside
    body_len = len(json_v2.encode_span_list(spans_for(0, per)))
    budget_bytes_per_s = 4.0 * body_len

    def revive_spans(tmp):
        """Cold boot from the live server's WAL/ckpt dirs: the acked
        set a crash at this instant would replay to."""
        revived = TpuStorage(
            config=AggConfig(**cfg), num_devices=1, batch_size=512,
            checkpoint_dir=os.path.join(tmp, "ckpt"),
            wal_dir=os.path.join(tmp, "wal"),
        )
        n = int(revived.agg.host_counters["spans"])
        revived.close()
        return n

    async def scenario(tmp) -> dict:
        storage = TpuStorage(
            config=AggConfig(**cfg), num_devices=1, batch_size=512,
            checkpoint_dir=os.path.join(tmp, "ckpt"),
            wal_dir=os.path.join(tmp, "wal"),
        )
        server = ZipkinServer(
            ServerConfig(storage_type="tpu", tpu_fast_ingest=True,
                         tpu_mp_workers=workers, tpu_mp_queue_depth=depth,
                         obs_windows_enabled=False,
                         tenant_ingest_bytes_per_s=budget_bytes_per_s,
                         tenant_ingest_burst_s=1.0,
                         tenant_flood_ratio=2.0, tenant_dwell_ticks=3),
            storage=storage,
        )
        ctl = server._overload
        client = TestClient(TestServer(server.make_app()))
        await client.start_server()
        try:
            seq = iter(range(1, 1 << 20))

            async def post(tenant):
                i = next(seq)
                t0 = time.perf_counter()
                resp = await client.post(
                    "/api/v2/spans",
                    data=json_v2.encode_span_list(spans_for(i, per)),
                    headers={"Content-Type": "application/json",
                             TENANT_HEADER: tenant},
                )
                await resp.release()
                return (tenant, resp.status, dict(resp.headers),
                        (time.perf_counter() - t0) * 1000.0)

            async def query():
                t0 = time.perf_counter()
                resp = await client.get("/api/v2/services")
                await resp.release()
                return (resp.status,
                        (time.perf_counter() - t0) * 1000.0)

            async def wave():
                posts = (
                    [post("B") for _ in range(n_flood)]
                    + [post("A") for _ in range(n_calm_posts)]
                    + [post("C") for _ in range(n_calm_posts)]
                )
                queries = [query() for _ in range(8)]
                out = await asyncio.gather(*posts, *queries)
                return out[:len(posts)], out[len(posts):]

            results, queries = await wave()
            await asyncio.to_thread(server._mp_ingester.drain)
            acked_so_far = per * sum(
                1 for r in results if r[1] == 202
            )
            # mid-flood crash-resume: cold boot between flood waves
            durable_parity_mid = (
                await asyncio.to_thread(revive_spans, tmp)
            ) == acked_so_far

            res2, q2 = await wave()  # the flood resumes post-"crash"
            results += res2
            queries += q2
            await asyncio.to_thread(server._mp_ingester.drain)

            by = {
                t: [r for r in results if r[0] == t]
                for t in ("A", "B", "C")
            }
            sheds = [r for r in results if r[1] == 429]
            guided = [
                r for r in sheds
                if r[2].get("X-Shed-Scope") == "tenant"
                and r[2].get("X-Shed-Tenant") == "B"
                and int(r[2].get("Retry-After", 0)) >= 1
                and int(r[2].get("X-Retry-After-Ms", 0)) > 0
            ]
            ac_ack_ms = [r[3] for t in ("A", "C") for r in by[t]
                         if r[1] == 202]
            ack_p99_ms = (float(np.percentile(ac_ack_ms, 99))
                          if ac_ack_ms else None)
            q_ms = [ms for st, ms in queries if st == 200]
            query_p99_ms = (float(np.percentile(q_ms, 99))
                            if len(q_ms) == len(queries) else None)

            acked_n = {t: sum(1 for r in by[t] if r[1] == 202)
                       for t in by}
            mp_table = server._mp_ingester.stats()["mpTenantTable"]
            attribution_exact = all(
                mp_table.get(t, {}).get("spans", 0) == per * acked_n[t]
                for t in ("A", "B", "C")
            )

            # aggregate posture AT flood peak: feed the ladder the real
            # fan-out queue saturation — containment means it stays B0
            stats = server._mp_ingester.stats()
            qsat = max(
                row["queueDepth"] for row in stats["mpWorkerTable"]
            ) / depth
            ctl.evaluate({"critpathQueueSaturation": qsat})
            c = ctl.counters()
            global_b0 = (c["overloadLevel"] == 0
                         and c["overloadTransitions"] == 0)
            levels = {t: c.get(f"tenantLevel_{t}") for t in ("A", "B", "C")}

            statusz = (
                await (await client.get("/api/v2/tpu/statusz")).json()
            )
            statusz_b_level = (
                statusz["overload"]["tenants"]["tenants"]["B"]["level"]
            )
            prom = await (await client.get("/prometheus")).text()
            prom_lines = [
                ln for ln in prom.splitlines()
                if ln.startswith("zipkin_tpu_tenant_") and "{" in ln
            ]
            prom_ok = (
                any('zipkin_tpu_tenant_level{tenant="B"}' in ln
                    for ln in prom_lines)
                and any('tenant="A"' in ln for ln in prom_lines)
                and all(
                    len(ln.rsplit(" ", 1)) == 2
                    and float(ln.rsplit(" ", 1)[1]) >= 0.0
                    for ln in prom_lines
                )
            )

            # gRPC twin: Report AS B over a real channel while B's
            # bucket is dry — big payloads so refill cannot outrun the
            # probe loop; an admitted probe is budget headroom working
            grpc_guided = False
            grpc_admitted_spans = 0
            gsrv = GrpcCollectorServer(server.collector,
                                       host="127.0.0.1", port=0)
            await gsrv.start()
            try:
                async with grpc.aio.insecure_channel(
                    f"127.0.0.1:{gsrv.port}"
                ) as ch:
                    method = ch.unary_unary(METHOD)
                    for k in range(6):
                        n = per * 2
                        try:
                            await method(
                                proto3.encode_span_list(
                                    spans_for(0x9100 + k, n)
                                ),
                                metadata=(("x-tenant-id", "B"),),
                            )
                            grpc_admitted_spans += n
                        except grpc.aio.AioRpcError as err:
                            md = {key: v for key, v in
                                  (err.trailing_metadata() or ())}
                            grpc_guided = (
                                err.code()
                                == grpc.StatusCode.RESOURCE_EXHAUSTED
                                and md.get("shed-scope") == "tenant"
                                and md.get("shed-tenant") == "B"
                                and int(md.get("retry-delay-ms", 0)) > 0
                            )
                            break
            finally:
                await gsrv.stop()
            await asyncio.to_thread(server._mp_ingester.drain)

            acked_spans = (
                per * sum(acked_n.values()) + grpc_admitted_spans
            )
            device_parity = \
                int(storage.agg.host_counters["spans"]) == acked_spans
            durable_parity = (
                await asyncio.to_thread(revive_spans, tmp)
            ) == acked_spans

            # calm: pressure decays tick-by-tick, the bucket refills in
            # real time — pace the ticks so both can happen
            ticks_to_calm = None
            for t in range(1, long_window_ticks + 1):
                ctl.evaluate({"critpathQueueSaturation": 0.0})
                c = ctl.counters()
                if (c["overloadLevel"] == 0
                        and c.get("tenantLevel_B", 0) == 0):
                    ticks_to_calm = t
                    break
                await asyncio.sleep(0.02)

            return {
                "budget_payloads_per_burst": 4,
                "b_offered_over_budget": round(n_flood / 4.0, 1),
                "acked": {t: acked_n[t] for t in ("A", "B", "C")},
                "shed": len(sheds),
                "sheds_tenant_scoped_to_b": len(guided),
                "a_c_sheds": sum(
                    1 for t in ("A", "C") for r in by[t] if r[1] == 429
                ),
                "ac_ack_p99_ms": ack_p99_ms and round(ack_p99_ms, 2),
                "query_p99_ms": (query_p99_ms
                                 and round(query_p99_ms, 2)),
                "attribution_exact": attribution_exact,
                "global_stays_b0": global_b0,
                "tenant_levels": levels,
                "statusz_b_level": statusz_b_level,
                "prom_tenant_families_ok": prom_ok,
                "grpc_shed_guided": grpc_guided,
                "device_parity": device_parity,
                "durable_parity_mid_flood": durable_parity_mid,
                "durable_parity": durable_parity,
                "calm_ticks_to_level0": ticks_to_calm,
            }
        finally:
            await client.close()
            await server.stop()

    with tempfile.TemporaryDirectory(prefix="eval_config9_") as tmp:
        r = asyncio.run(scenario(tmp))
    ok = bool(
        r["b_offered_over_budget"] >= 3.0
        and r["acked"]["A"] == 2 * n_calm_posts
        and r["acked"]["C"] == 2 * n_calm_posts
        and r["a_c_sheds"] == 0
        and r["acked"]["B"] >= 1 and r["shed"] >= 1
        and r["acked"]["B"] + r["shed"] == 2 * n_flood
        and r["sheds_tenant_scoped_to_b"] == r["shed"]
        and r["ac_ack_p99_ms"] is not None
        and r["ac_ack_p99_ms"] <= ack_slo_ms
        and r["query_p99_ms"] is not None
        and r["query_p99_ms"] <= query_slo_ms
        and r["attribution_exact"]
        and r["global_stays_b0"]
        and r["tenant_levels"]["B"] >= 2
        and r["tenant_levels"]["A"] == 0
        and r["tenant_levels"]["C"] == 0
        and r["statusz_b_level"] >= 2
        and r["prom_tenant_families_ok"]
        and r["grpc_shed_guided"]
        and r["device_parity"]
        and r["durable_parity_mid_flood"] and r["durable_parity"]
        and r["calm_ticks_to_level0"] is not None
        and r["calm_ticks_to_level0"] <= long_window_ticks
    )
    _emit(config="config9", passed=ok, ack_slo_ms=ack_slo_ms,
          query_slo_ms=query_slo_ms,
          long_window_ticks=long_window_ticks, **r)
    return ok


ALL = {"config0": config0, "config1": config1, "config2": config2,
       "config3": config3, "config4": config4, "config5": config5,
       "config6": config6, "config7": config7, "config8": config8,
       "config9": config9}


def main() -> None:
    wanted = sys.argv[1:] or list(ALL)
    ok = True
    for name in wanted:
        ok &= ALL[name]()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
