"""Query-SLO program-time artifact (VERDICT r2 order 3).

The round-2 verdict's finding: config4's quiesced p50 (76-374 ms)
failed the <50 ms gate, and the builder's claim that the fixed cost
of a dispatch dominates was an *argument*, not a *measurement*. This harness produces the measurement:

1. ingest QUERY_SLO_SPANS (default 20M) through the production fast
   path at full-size AggConfig;
2. measure the DISPATCH FLOOR — the wall time of a trivial one-scalar
   jitted dispatch+fetch, which contains zero meaningful device work;
3. wall-time each read program at the aggregator level (caches
   bypassed): dependencies with cached link context, the rolled-only
   dependency read, digest percentiles, windowed percentiles,
   cardinalities, and the link-context rebuild itself;
4. XPlane-capture one round of the reads and attribute actual
   device-op time per program.

Output: one JSON line (committed as QUERY_SLO_r03.json by the round
runner) with, per read: host wall stats, wall-minus-floor, and the
captured device time. The <50 ms SLO holds when wall-minus-floor (and
the device time backing it) is under 50 ms.

r08 (ISSUE 14) adds the concurrent mirror A/B: the same mixed reader
workload against the raw aggregator lock (the r07 baseline that spent
77.5% of query time in lock_wait) and against the epoch-published read
mirror, at 8 and 32 threads, with staleness-at-serve percentiles and a
mirror-vs-fresh byte-parity check at the publish instant.

r09 (ISSUE 15) adds the time-tier section: a dedicated store ingests a
full day of 5-minute buckets (sealed through the production tt_seal
protocol, fine ring -> coarse blocks -> disk), then (a) decomposes the
host-side merge cost per lookback span (5m / 1h / 24h: covering
segments, coarse-vs-fine split, merge wall), (b) measures the unsealed
current-bucket read (the one packed device pull a live window pays),
(c) runs the mixed windowed/cumulative concurrent leg at 8 threads
through the mirror's demand-registered ``ttq:`` keys — the windowed
query_wall p99 < 50 ms / lock-wait < 10% gate — and (d) audits the
windowed shadow-accuracy gauges at full live-bench coverage (the
NO-ALERT check for the default windowed drift SloSpecs).

Run from the repo root: ``python -m benchmarks.query_slo``.
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time


def _stats(xs):
    xs = sorted(xs)
    return {
        "min": round(xs[0], 2),
        "p50": round(xs[len(xs) // 2], 2),
        "max": round(xs[-1], 2),
    }


def _percentile(xs, q):
    return xs[min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))]


def _serving_worker(seg_params, idx, iters, qs, end_ts_ms, lookback_ms,
                    barrier, out_q):
    """Spawn target for the multi-process serving leg (ISSUE 19).

    Module-level so the spawn context can import it; the child
    re-import of this file executes only the light top-level (json/os/
    time), and the serving imports below never pull jax — a reader
    process maps the segment read-only and has NO store, so zero
    aggregator-lock acquisitions is architectural, not sampled (ZT13
    proves every serve chain lock-free statically; the parity suite
    proves the lock ledger flat at runtime).

    Serves the same mixed workload as the thread legs — quantiles /
    cardinalities / dependencies round-robin, offset by worker index —
    against live publishes (the parent keeps cutting epochs, so views
    re-decode and re-memoize at every generation swap). Reports
    (idx, measured_wall_s, per-query walls, reader counters)."""
    import time as _t

    from zipkin_tpu.serving.segment import MirrorSegment
    from zipkin_tpu.serving.shape import SegmentMiss, SegmentView

    seg = MirrorSegment.attach(seg_params)
    view = SegmentView(seg, idx)
    kinds = (
        lambda: view.serve_quantiles(qs),
        lambda: view.serve_cardinalities(),
        lambda: view.serve_dependencies(end_ts_ms, lookback_ms),
    )
    try:
        # first touches demand-register back to the publisher; spin
        # until the epoch carries every workload key (the timed loop
        # measures steady-state serving, not first-touch registration)
        deadline = _t.monotonic() + 60
        for kind in kinds:
            while True:
                try:
                    kind()
                    break
                except SegmentMiss:
                    if _t.monotonic() > deadline:
                        raise
                    # pace retries under the publish cadence: every
                    # miss re-pushes the demand key, and a hot retry
                    # loop would overflow the stripe before the next
                    # tick drains it
                    _t.sleep(0.1)
        barrier.wait(timeout=120)
        durs = []
        t0 = _t.perf_counter()
        for j in range(iters):
            t1 = _t.perf_counter()
            kinds[(idx + j) % 3]()
            durs.append((_t.perf_counter() - t1) * 1e3)
        wall = _t.perf_counter() - t0
        out_q.put((idx, wall, durs, dict(view.counters())))
    finally:
        seg.close()


def _serving_leg(store, qs, end_ts_ms, n_procs, iters,
                 churn_payload) -> dict:
    """Scale-out serving leg: N reader PROCESSES over the shm mirror
    segment, publisher + ingest churn live in this (ingest) process.
    The thread legs above share the GIL and, on the lock side, the
    aggregator lock; this leg is the ISSUE 19 counterfactual — readers
    that share nothing with ingest but the segment bytes."""
    import multiprocessing as mp
    import threading

    from zipkin_tpu.serving.segment import MirrorSegment

    lookback_ms = end_ts_ms  # the whole retained window, like the legs above
    seg = MirrorSegment(readers=n_procs, capacity=16 << 20)
    ctx = mp.get_context("spawn")
    barrier = ctx.Barrier(n_procs + 1)
    out_q = ctx.Queue()
    stop = threading.Event()
    lock_counts = {}
    procs = []
    try:
        store.attach_mirror_segment(seg)
        assert store.publish_mirror(force=True)
        lock_counts["before"] = store.ingest_counters().get(
            "queryLockAcquisitions", 0
        )

        def publisher():
            while not stop.is_set():
                store.publish_mirror(force=True)  # drains reader demand
                time.sleep(0.05)

        def ingester():
            while not stop.is_set():
                store.ingest_json_fast(churn_payload)
                time.sleep(0.01)

        pub = threading.Thread(target=publisher, daemon=True)
        ing = threading.Thread(target=ingester, daemon=True)
        pub.start()
        ing.start()
        procs = [
            ctx.Process(
                target=_serving_worker,
                args=(seg.params(), i, iters, qs, end_ts_ms, lookback_ms,
                      barrier, out_q),
                daemon=True,
            )
            for i in range(n_procs)
        ]
        for p in procs:
            p.start()
        barrier.wait(timeout=300)  # every worker warmed and ready
        results = [out_q.get(timeout=600) for _ in range(n_procs)]
        for p in procs:
            p.join(timeout=60)
        stop.set()
        pub.join(timeout=10)
        ing.join(timeout=60)
        lock_counts["after"] = store.ingest_counters().get(
            "queryLockAcquisitions", 0
        )

        durs = sorted(d for r in results for d in r[2])
        total = n_procs * iters
        # aggregate wall = the slowest worker's measured loop (workers
        # start together at the barrier; queue drain is excluded)
        wall_s = max(r[1] for r in results)
        qps = total / wall_s
        counters = [r[3] for r in results]
        seg_status = seg.status()
        return {
            "reader_processes": n_procs,
            "queries_per_process": iters,
            "total_queries": total,
            "wall_s": round(wall_s, 3),
            "qps": round(qps, 1),
            "query_wall_ms": {
                "p50": round(_percentile(durs, 0.50), 4),
                "p90": round(_percentile(durs, 0.90), 4),
                "p99": round(_percentile(durs, 0.99), 4),
                "max": round(durs[-1], 4),
            },
            # architectural, statically proven (ZT13) and runtime-
            # checked (parity suite): reader processes hold no store,
            # so no code path can reach the aggregator lock
            "reader_lock_acquisitions": 0,
            # the publisher/churn threads DO take the lock — one hold
            # per epoch tick, in the ingest process, as designed
            "ingest_lock_acquisitions_during_leg": int(
                lock_counts["after"] - lock_counts["before"]
            ),
            "segment_publishes": seg_status["publishes"],
            "segment_generation": seg_status["generation"],
            "reader_demand_requests": sum(
                c.get("readerDemandRequests", 0) for c in counters
            ),
            "reader_demand_overflow": sum(
                c.get("readerDemandOverflow", 0) for c in counters
            ),
            "reader_memo_hits": sum(
                c.get("readerMemoHits", 0) for c in counters
            ),
            "staleness_at_serve_ms": {
                "max": round(
                    max(c.get("readerServeAgeMaxMs", 0.0)
                        for c in counters), 3
                ),
            },
        }
    finally:
        stop.set()
        for p in procs:
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
        store.mirror.segment_sink = None
        seg.close()


def _concurrent_leg(store, end_ts_ms: int, qs, n_threads: int,
                    use_mirror: bool, ingest_payload=None) -> dict:
    """Concurrent-read leg, both sides of the ISSUE 14 A/B.

    r07 established the baseline (``use_mirror=False``): with every read
    serialized behind one RLock, 8 readers spent 77.5% of attributed
    query time in lock_wait and query_wall p99 hit 136.8 ms. The mirror
    leg (``use_mirror=True``) runs the SAME mixed workload through the
    epoch-published read mirror — and runs it HARSHER: a live ingest
    thread keeps advancing write_version and a publisher thread cuts
    epochs at tick cadence, so serves are genuinely stale-bounded, the
    seqlock is exercised against concurrent publishes, and the reported
    staleness-at-serve percentiles are real, not vacuous zeros. The
    query-plane observatory decomposes the p99 (lock_wait vs device vs
    mirror_serve) from INSIDE the pipeline, and the windowed telemetry
    plane cross-checks the stitched query count + p99 so the harness and
    the observatory cannot silently diverge."""
    import threading

    from zipkin_tpu import obs
    from zipkin_tpu.obs.windows import WindowedTelemetry

    iters = int(os.environ.get("QUERY_SLO_CONC_ITERS", 12))
    store.set_query_observatory(True)
    store.mirror.enabled = use_mirror
    if use_mirror:
        # warm pass: register every workload key with the mirror's
        # demand registry (a first touch is a deliberate miss-and-
        # register), then cut an epoch that carries them — the timed
        # leg measures steady-state serving, not first-touch
        # registration falling through to the lock
        store.invalidate_read_cache()
        store.get_dependencies(end_ts_ms, end_ts_ms).execute()
        store.latency_quantiles(qs)
        store.publish_mirror(force=True)
    store.querytrace.reset()
    obs.RECORDER.reset()  # quiesced: ingest done, reads not yet started
    windows = WindowedTelemetry(obs.RECORDER, tick_s=1.0)
    serves0 = store.mirror.serves
    stale0 = store.mirror.stale_serves

    walls_ms = [[] for _ in range(n_threads)]
    ages_ms = [[] for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads)
    stop = threading.Event()

    # the mirror leg's readers are staleness-tolerant dashboard clients:
    # they pass an explicit per-request staleness_ms (the opt-in knob the
    # HTTP routes expose), because default requests only see a
    # version-stale epoch while the lock is actually contended — gate
    # numbers should rest on the declared contract, not probe timing
    staleness = store.mirror.max_stale_ms if use_mirror else None

    def reader(k: int) -> None:
        barrier.wait()
        for j in range(iters):
            kind = (k + j) % 3
            t1 = time.perf_counter()
            if kind == 0:
                # fresh: drop memoized pulls so the read crosses the
                # device (dispatch + packed transfer under the lock) —
                # on the mirror leg the published epoch outlives the
                # cache invalidation, so the SAME request serves
                # lock-free instead
                store.invalidate_read_cache()
                store.get_dependencies(
                    end_ts_ms, end_ts_ms, staleness_ms=staleness,
                ).execute()
            elif kind == 1:
                # cached: deps answered from the staleness-bounded cache
                # (mirror leg: from the published epoch)
                store.get_dependencies(
                    end_ts_ms, end_ts_ms, staleness_ms=staleness,
                ).execute()
            else:
                store.latency_quantiles(qs, staleness_ms=staleness)
            walls_ms[k].append((time.perf_counter() - t1) * 1e3)
            if use_mirror:
                # staleness-at-serve sample: the gauge the serve this
                # thread just completed wrote (GIL-atomic read; a racing
                # serve's age is an equally valid sample)
                ages_ms[k].append(store.mirror.serve_age_ms)

    def publisher() -> None:
        # the windows ticker's role, at bench cadence
        while not stop.is_set():
            store.publish_mirror()
            stop.wait(0.05)

    def ingester() -> None:
        # keep write_version moving faster than the publish cadence so
        # mirror serves are genuinely stale (version-matched serves
        # report age 0 by contract) and the staleness percentiles mean
        # something
        while not stop.is_set():
            store.ingest_json_fast(ingest_payload)
            stop.wait(0.002)

    background = []
    if use_mirror:
        background.append(threading.Thread(target=publisher))
        if ingest_payload is not None:
            background.append(threading.Thread(target=ingester))
    threads = [
        threading.Thread(target=reader, args=(k,)) for k in range(n_threads)
    ]
    for t in background:
        t.start()
    if use_mirror and ingest_payload is not None:
        # steady-state head start: don't release readers until churn has
        # moved write_version past the warm epoch at least once. An
        # 8-thread leg can finish in ~10 ms — faster than the first
        # background ingest completes — and a leg timed entirely inside
        # the warm epoch would report vacuous all-zero staleness.
        v0 = store.agg.write_version
        deadline = time.perf_counter() + 5.0
        while store.agg.write_version == v0 and time.perf_counter() < deadline:
            time.sleep(0.005)
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    stop.set()
    for t in background:
        t.join()

    # stitch BEFORE the tick so the relayed query_wall observations land
    # inside the tick's delta and the windowed cross-check sees them all
    store.querytrace.stitch()
    windows.tick()
    wf = store.querytrace.waterfall()
    flat = sorted(w for per in walls_ms for w in per)
    total = len(flat)
    p99_ms = _percentile(flat, 0.99)
    segs = {s["name"]: s["sumUs"] for s in wf["segments"]}
    lock_wait_us = segs.get("lock_wait", 0)
    device_us = segs.get("device_dispatch", 0) + segs.get("device_wall", 0)
    transfer_us = segs.get("readpack_transfer", 0) + segs.get("unpack", 0)
    mirror_us = segs.get("mirror_serve", 0)
    attributed = max(1, sum(segs.values()))

    win_wall = windows.window(3600.0).stage("query_wall")
    win_p99_ms = win_wall.p99_us / 1e3
    lock = wf["lock"]
    out = {
        "mirror": use_mirror,
        "staleness_request_ms": staleness,
        "threads": n_threads,
        "queries": total,
        "queries_per_sec": round(total / elapsed, 1),
        "wall_ms": _stats(flat),
        "p99_ms": round(p99_ms, 2),
        "conservation_p50": wf["conservation"]["p50"],
        # where the concurrent p99 actually goes: serialized waiting on
        # the aggregator lock vs device program time vs the packed pull
        # vs the lock-free mirror serve
        "split_us": {
            "lock_wait": lock_wait_us,
            "device": device_us,
            "transfer": transfer_us,
            "mirror_serve": mirror_us,
            "other": attributed - lock_wait_us - device_us
            - transfer_us - mirror_us,
        },
        "split_fraction": {
            "lock_wait": round(lock_wait_us / attributed, 4),
            "device": round(device_us / attributed, 4),
            "transfer": round(transfer_us / attributed, 4),
            "mirror_serve": round(mirror_us / attributed, 4),
        },
        "lock": {
            "acquisitions": lock["queryLockAcquisitions"],
            "contended": lock["queryLockContended"],
            "waiters_high_water": lock["queryLockWaitersHighWater"],
            "wait_p99_us": lock["queryLockWaitP99Us"],
            "hold_p99_us": lock["queryLockHoldP99Us"],
        },
        # windowed-plane cross-check: the stitcher relays every folded
        # wall into query_wall, so the plane must see exactly the
        # harness's query count, and its (log2-bucketed) p99 must track
        # the harness p99
        "windowed_query_wall_count": win_wall.count,
        "windowed_query_wall_p99_ms": round(win_p99_ms, 3),
        "windowed_count_matches": bool(win_wall.count == total),
        "windowed_p99_agrees": bool(
            total > 0 and 0.25 * p99_ms <= win_p99_ms <= 2.5 * p99_ms
        ),
    }
    if use_mirror:
        ages = sorted(a for per in ages_ms for a in per)
        out["mirror_serves"] = store.mirror.serves - serves0
        out["mirror_stale_serves"] = store.mirror.stale_serves - stale0
        out["staleness_at_serve_ms"] = {
            "p50": round(_percentile(ages, 0.5), 3),
            "p90": round(_percentile(ages, 0.9), 3),
            "p99": round(_percentile(ages, 0.99), 3),
            "max": round(ages[-1], 3),
        } if ages else None
    return out


# -- ISSUE 15: time-disaggregated sketch tier ---------------------------

_TT_G = 5                    # time_bucket_minutes
_TT_BASE_MIN = 10_000_000    # deterministic anchor, divisible by _TT_G
_LB_5M, _LB_1H, _LB_24H = 300_000, 3_600_000, 86_400_000


def _tt_epoch_spans(ep_offsets, per, seed):
    """Client chains inside the given bucket epochs (offsets from the
    anchor) — the windowed workload's span soup, one rng stream so the
    shadow audit sees exactly what the store ingested."""
    import random

    from zipkin_tpu.model.span import Endpoint, Kind, Span

    rng = random.Random(seed)
    svcs = [
        Endpoint.create(f"svc{i:02d}", f"10.0.1.{i + 1}") for i in range(8)
    ]
    spans = []
    seq = 0
    for off in ep_offsets:
        for _ in range(per):
            seq += 1
            trace_id = f"{rng.getrandbits(63) | 1:016x}"
            t_min = _TT_BASE_MIN + off * _TT_G + rng.randrange(_TT_G)
            parent_id = None
            caller = rng.randrange(len(svcs))
            for level in range(rng.randint(1, 3)):
                span_id = f"{(seq << 8 | level) + 1:016x}"
                err = {"error": "boom"} if rng.random() < 0.02 else {}
                spans.append(Span.create(
                    trace_id=trace_id, id=span_id, parent_id=parent_id,
                    name=f"op{rng.randrange(12):02d}",
                    kind=Kind.CLIENT,
                    local_endpoint=svcs[(caller + level) % len(svcs)],
                    remote_endpoint=svcs[(caller + level + 1) % len(svcs)],
                    timestamp=t_min * 60_000_000 + rng.randrange(1000),
                    duration=int(rng.paretovariate(1.2) * 1000) + 50,
                    tags=err,
                ))
                parent_id = span_id
    return spans


def _tt_concurrent_leg(store, qs, end_ts_ms, n_threads: int) -> dict:
    """Mixed windowed/cumulative concurrent reads through the mirror.

    Every windowed request canonicalizes to a bucket-aligned
    ``ttq:<lo_ep>:<hi_ep>`` demand key, so after the warm pass + one
    publish the whole leg serves off the published WindowAnswers —
    lock-free regardless of lookback width. The decomposition proves it
    the same way the r08 leg did: querytrace waterfall segments, with
    lock_wait share as the gate."""
    import threading

    from zipkin_tpu import obs
    from zipkin_tpu.obs.windows import WindowedTelemetry

    iters = int(os.environ.get("QUERY_SLO_CONC_ITERS", 12))
    store.set_query_observatory(True)
    store.mirror.enabled = True
    staleness = store.mirror.max_stale_ms

    def q_5m():
        store.latency_quantiles(
            qs, end_ts=end_ts_ms, lookback=_LB_5M, staleness_ms=staleness
        )

    def q_1h():
        store.latency_quantiles(
            qs, end_ts=end_ts_ms, lookback=_LB_1H, staleness_ms=staleness
        )

    def card_24h():
        store.trace_cardinalities(
            end_ts=end_ts_ms, lookback=_LB_24H, staleness_ms=staleness
        )

    def deps_1h():
        store.get_dependencies(
            end_ts_ms, _LB_1H, staleness_ms=staleness
        ).execute()

    def q_cumulative():
        store.latency_quantiles(qs, staleness_ms=staleness)

    workload = [q_5m, q_1h, card_24h, deps_1h, q_cumulative]
    for fn in workload:  # register demand keys (deliberate first-touch)
        fn()
    store.publish_mirror(force=True)
    store.querytrace.reset()
    obs.RECORDER.reset()
    windows = WindowedTelemetry(obs.RECORDER, tick_s=1.0)
    serves0 = store.mirror.serves

    walls_ms = [[] for _ in range(n_threads)]
    barrier = threading.Barrier(n_threads)

    def reader(k: int) -> None:
        barrier.wait()
        for j in range(iters):
            fn = workload[(k + j) % len(workload)]
            t1 = time.perf_counter()
            fn()
            walls_ms[k].append((time.perf_counter() - t1) * 1e3)

    threads = [
        threading.Thread(target=reader, args=(k,)) for k in range(n_threads)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    store.querytrace.stitch()
    windows.tick()
    wf = store.querytrace.waterfall()
    flat = sorted(w for per in walls_ms for w in per)
    total = len(flat)
    p99_ms = _percentile(flat, 0.99)
    segs = {s["name"]: s["sumUs"] for s in wf["segments"]}
    lock_wait_us = segs.get("lock_wait", 0)
    mirror_us = segs.get("mirror_serve", 0)
    attributed = max(1, sum(segs.values()))
    win_wall = windows.window(3600.0).stage("query_wall")
    ttq_keys = sorted(
        k for k in store.mirror._demand if k.startswith("ttq:")
    )
    return {
        "threads": n_threads,
        "staleness_request_ms": staleness,
        "queries": total,
        "queries_per_sec": round(total / elapsed, 1),
        "wall_ms": _stats(flat),
        "p99_ms": round(p99_ms, 2),
        "mirror_serves": store.mirror.serves - serves0,
        "ttq_demand_keys": ttq_keys,
        "split_fraction": {
            "lock_wait": round(lock_wait_us / attributed, 4),
            "mirror_serve": round(mirror_us / attributed, 4),
        },
        "windowed_query_wall_count": win_wall.count,
        "windowed_query_wall_p99_ms": round(win_wall.p99_us / 1e3, 3),
        "windowed_count_matches": bool(win_wall.count == total),
    }


def _timetier_section(small: bool, qs) -> dict:
    """The r09 artifact's time-tier section: seal a day of buckets,
    decompose merge cost per lookback, gate the concurrent windowed
    leg, audit the windowed shadow gauges."""
    from zipkin_tpu.model import json_v2
    from zipkin_tpu.obs.accuracy import AccuracyEstimator
    from zipkin_tpu.obs.shadow import HostShadow
    from zipkin_tpu.storage.tpu import TpuStorage as HostedTpuStorage
    from zipkin_tpu.tpu.state import AggConfig

    epochs = int(os.environ.get("QUERY_SLO_TT_EPOCHS", 288))  # 24 h of 5 m
    per = 128  # traces per bucket (~2x spans; keeps per-bucket p99 stable)
    if small:
        config = AggConfig(
            max_services=64, max_keys=256, hll_precision=8,
            digest_centroids=16, digest_buffer=1 << 16,
            ring_capacity=1 << 16, link_buckets=4, hist_slices=2,
            time_buckets=4, time_bucket_minutes=_TT_G,
        )
    else:
        config = AggConfig(time_bucket_minutes=_TT_G)
    arch = tempfile.mkdtemp(prefix="query_slo_tt_")
    store = HostedTpuStorage(
        config=config, num_devices=1, batch_size=4096, archive_dir=arch,
    )
    try:
        # -- ingest a day in bucket order, sealing as the ticker would --
        # blocks of W-1 epochs: the sealer never seals the CURRENT
        # (still-filling) bucket, so advancing by a full W per seal
        # would recycle each block's top slot before its seal — W-1
        # keeps every finished bucket resident until sealed, exactly
        # the steady-state the production tick cadence guarantees
        spans_all = []
        block = max(1, int(config.time_buckets) - 1)
        t_ing0 = time.perf_counter()
        for lo in range(0, epochs, block):
            batch = _tt_epoch_spans(
                range(lo, min(lo + block, epochs)), per=per, seed=lo + 1
            )
            spans_all.extend(batch)
            store.ingest_json_fast(json_v2.encode_span_list(batch))
            store.tt_seal()
        # the live bucket (epoch `epochs`) starts filling; sealing now
        # finishes the day: sealed_through = epochs-1, current unsealed
        live_block = _tt_epoch_spans([epochs], per=per, seed=epochs + 1)
        spans_all.extend(live_block)
        store.ingest_json_fast(json_v2.encode_span_list(live_block))
        store.tt_seal()
        ingest_wall = time.perf_counter() - t_ing0
        tier = store.timetier
        sealed_end_ts = (_TT_BASE_MIN + epochs * _TT_G) * 60_000 - 1

        # -- merge-cost decomposition per lookback span -----------------
        reps = 5
        merge_cost = {}
        for label, lb in (("5m", _LB_5M), ("1h", _LB_1H), ("24h", _LB_24H)):
            lo_ep, hi_ep = store._tt_epochs(sealed_end_ts, lb)
            parts, covered, missing = tier.cover(lo_ep, hi_ep)  # warms LRU
            coarse = sum(1 for p in parts if p.hi_ep > p.lo_ep)
            xs = []
            for _ in range(reps):
                t1 = time.perf_counter()
                tier.window(store.agg, lo_ep, hi_ep)
                xs.append((time.perf_counter() - t1) * 1e3)
            merge_cost[label] = {
                "epochs": hi_ep - lo_ep + 1,
                "segments_merged": len(parts),
                "coarse_blocks": coarse,
                "fine_segments": len(parts) - coarse,
                "covered": covered,
                "missing": missing,
                "merge_wall_ms": _stats(xs),
            }

        # -- unsealed current bucket: the one packed device pull --------
        live_end_ts = (_TT_BASE_MIN + (epochs + 1) * _TT_G) * 60_000 - 1
        lo_ep, hi_ep = store._tt_epochs(live_end_ts, _LB_5M)
        xs = []
        for _ in range(reps):
            t1 = time.perf_counter()
            ans = tier.window(store.agg, lo_ep, hi_ep)
            xs.append((time.perf_counter() - t1) * 1e3)
        merge_cost["5m_unsealed"] = {
            "epochs": hi_ep - lo_ep + 1,
            "reaches_device": bool(ans.unsealed),
            "merge_wall_ms": _stats(xs),
        }

        # -- windowed shadow-accuracy audit at full coverage ------------
        shadow = HostShadow(
            bucket_minutes=_TT_G, link_rate=0.0, seed=11,
            svc_resolver=store.vocab.services.get,
        )
        shadow.offer_spans(spans_all)
        shadow.drain()
        acc = AccuracyEstimator(store, shadow, rollup_s=0.0)
        g = acc.rollup()
        # limits = the default windowed SloSpecs (obs/slo.py)
        shadow_report = {
            "coverage": g["accuracyShadowCoverage"],
            "windowed_digest_p99_relerr":
                g["accuracyWindowedDigestP99RelErr"],
            "windowed_digest_p99_drift": g["accuracyWindowedDigestP99Drift"],
            "windowed_hll_relerr": g["accuracyWindowedHllRelErr"],
            "windowed_hll_drift": g["accuracyWindowedHllDrift"],
            "no_alert": bool(
                g["accuracyWindowedDigestP99Drift"] < 0.20
                and g["accuracyWindowedHllDrift"] < 0.15
            ),
        }

        # -- the concurrent windowed gate (8 threads, via mirror) -------
        concurrent = _tt_concurrent_leg(store, qs, sealed_end_ts, 8)
        slo = {
            "p99_ms": concurrent["p99_ms"],
            "p99_under_50ms": bool(concurrent["p99_ms"] < 50.0),
            "lock_wait_share": concurrent["split_fraction"]["lock_wait"],
            "lock_wait_under_10pct": bool(
                concurrent["split_fraction"]["lock_wait"] < 0.10
            ),
            "shadow_no_alert": shadow_report["no_alert"],
        }
        counters = dict(tier.counters)
        return {
            "bucket_minutes": _TT_G,
            "epochs_sealed": tier.sealed_through - (_TT_BASE_MIN // _TT_G) + 1,
            "spans": len(spans_all),
            "ingest_wall_s": round(ingest_wall, 2),
            "segments": {
                "fine": counters.get("ttSegmentsFine", 0),
                "coarse": counters.get("ttSegmentsCoarse", 0),
                "disk": counters.get("ttSegmentsDisk", 0),
            },
            "merge_cost": merge_cost,
            "shadow_windowed": shadow_report,
            "concurrent_windowed_8t": concurrent,
            "slo": slo,
        }
    finally:
        store.close()
        shutil.rmtree(arch, ignore_errors=True)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tests.fixtures import lots_of_spans
    from zipkin_tpu import readpack
    from zipkin_tpu.model import json_v2
    from zipkin_tpu.parallel.mesh import make_mesh
    from zipkin_tpu.tpu.state import AggConfig
    from zipkin_tpu.tpu.store import TpuStorage

    total = int(os.environ.get("QUERY_SLO_SPANS", 20_000_000))
    reps = int(os.environ.get("QUERY_SLO_REPS", 10))

    if os.environ.get("QUERY_SLO_SMALL"):  # CPU smoke of the harness
        config = AggConfig(
            max_services=64, max_keys=256, hll_precision=8,
            digest_centroids=16, digest_buffer=1 << 16,
            ring_capacity=1 << 16, link_buckets=4, hist_slices=2,
        )
    else:
        config = AggConfig()
    batch = min(65_536, config.rollup_segment, config.digest_buffer)
    store = TpuStorage(config=config, mesh=make_mesh(1), pad_to_multiple=batch)
    agg = store.agg
    spans = lots_of_spans(2 * batch, seed=7, services=40, span_names=120)
    payloads = [
        json_v2.encode_span_list(spans[i : i + batch])
        for i in range(0, len(spans), batch)
    ]
    store.warm(payloads[0])

    sent = warm_spans = store.ingest_counters()["spans"]
    t0 = time.perf_counter()
    i = 0
    while sent < total:
        n, _ = store.ingest_json_fast(payloads[i % len(payloads)])
        sent += n
        i += 1
    agg.block_until_ready()
    ingest_wall = time.perf_counter() - t0

    end_min = int(max(s.timestamp for s in spans if s.timestamp) // 60_000_000)
    lo_min, hi_min = 0, end_min + 60

    # -- dispatch floor: trivial dispatch + fetch ------------------------
    tiny = jax.jit(lambda x: x + 1)
    tiny(jnp.uint32(1)).block_until_ready()  # compile
    floor = []
    for _ in range(max(reps, 15)):
        f0 = time.perf_counter()
        np.asarray(tiny(jnp.uint32(1)))
        floor.append((time.perf_counter() - f0) * 1e3)

    # -- the read programs, caches bypassed ------------------------------
    qs = [0.5, 0.99]

    def deps_ctx_cached():
        agg.dependency_edges(lo_min, hi_min)

    def deps_ctx_fresh():
        # force the FRESH path: first-query-after-write dispatches the
        # fused spmd_edges_fresh (maintained-order ctx + edges) — the
        # program that now gates the 50 ms SLO with no exclusions
        with agg.lock:
            agg._ctx_cache = (-1, None)
        agg.dependency_edges(lo_min, hi_min)

    def deps_rolled_only():
        # a window provably disjoint from ring residency: served from the
        # rollup matrices alone (the reads return empty — cost identical)
        assert agg.window_fully_rolled(1, 2)
        agg.dependency_edges(1, 2)

    def percentiles_pend_fold():
        # the r2 read path: fold the pending buffer on EVERY read
        # (packed like every read program — one pull)
        with agg.lock:
            readpack.pull(
                agg._quant_digest(agg.state, jnp.asarray(qs, jnp.float32))
            )

    def percentiles():
        # the production path: opportunistic flush (amortized — it
        # advances state the ingest stream would flush anyway), then the
        # cheap no-pend program on every subsequent read
        agg.quantiles(qs)

    def windowed():
        agg.quantiles(qs, ts_lo_min=lo_min, ts_hi_min=hi_min)

    def cardinalities():
        agg.cardinalities()

    reads = {
        "dependencies_ctx_cached": deps_ctx_cached,
        "dependencies_ctx_fresh": deps_ctx_fresh,
        "dependencies_rolled_only": deps_rolled_only,
        "percentiles_pend_fold": percentiles_pend_fold,
        "percentiles_digest": percentiles,
        "percentiles_windowed": windowed,
        "cardinalities": cardinalities,
    }
    walls = {}
    transfers = {}
    for name, fn in reads.items():
        fn()  # compile + warm ctx where applicable
        xs = []
        tc0 = readpack.transfer_count()
        for _ in range(reps):
            t1 = time.perf_counter()
            fn()
            xs.append((time.perf_counter() - t1) * 1e3)
        # device→host pulls per query through the readpack chokepoint —
        # the one-transfer invariant, measured (was 2-3 per read before
        # the packed wire format)
        transfers[name] = round(
            (readpack.transfer_count() - tc0) / reps, 2
        )
        walls[name] = xs

    # -- flight-recorder cross-check (ISSUE 6) ---------------------------
    # Store-level fresh reads travel _cached_read, which records the
    # query_fresh stage; the recorder's p50 must agree with the wall
    # this harness measures for the same calls — within log2-bucket
    # resolution (the reported bound is < 2x above the true value).
    from zipkin_tpu import obs
    from zipkin_tpu.obs.windows import WindowedTelemetry

    obs.RECORDER.reset()  # quiesced: ingest finished, reads are serial
    # windowed plane attached post-reset: its baseline is the zeroed
    # recorder, so one tick after the loop captures the whole run
    windows = WindowedTelemetry(obs.RECORDER, tick_s=1.0)
    end_ts_ms = hi_min * 60_000
    store_walls = []
    for _ in range(reps):
        store.invalidate_read_cache()  # every rep takes the fresh path
        t1 = time.perf_counter()
        store.get_dependencies(end_ts_ms, end_ts_ms).execute()
        store_walls.append((time.perf_counter() - t1) * 1e3)
    windows.tick()
    rec_fresh = obs.RECORDER.snapshot().stage("query_fresh")
    wall_p50 = _stats(store_walls)["p50"]
    rec_p50 = rec_fresh.p50_us / 1e3
    recorder_report = {
        "store_fresh_read_wall_ms": _stats(store_walls),
        "recorder_query_fresh_p50_ms": round(rec_p50, 3),
        "recorder_query_fresh_p99_ms": round(rec_fresh.p99_us / 1e3, 3),
        "recorder_query_fresh_count": rec_fresh.count,
        # a fresh dependency read is one _cached_read miss (the edges
        # pull) that dominates the wall, so the recorder's p50 tracks
        # the harness number from inside the pipeline — the log2 bucket
        # bound and the harness's own call overhead set the window
        "agrees_with_wall": bool(
            rec_fresh.count >= reps and 0.25 * wall_p50 <= rec_p50 <= 1.25 * wall_p50
        ),
    }
    # ISSUE 9: the WINDOWED p99 over a window covering the whole
    # quiesced run must (a) agree exactly with the cumulative plane —
    # the delta-merge oracle, same buckets, same walk — and (b) agree
    # with the harness wall the same way the cumulative p50 does.
    win_fresh = windows.window(3600.0).stage("query_fresh")
    wall_p99 = round(sorted(store_walls)[
        min(len(store_walls) - 1, int(0.99 * len(store_walls)))], 2)
    win_p99 = win_fresh.p99_us / 1e3
    recorder_report["windowed_query_fresh_p99_ms"] = round(win_p99, 3)
    recorder_report["windowed_matches_cumulative"] = bool(
        win_fresh.count == rec_fresh.count
        and win_fresh.p99_us == rec_fresh.p99_us
    )
    recorder_report["windowed_agrees_with_wall"] = bool(
        win_fresh.count >= reps and 0.25 * wall_p99 <= win_p99 <= 1.25 * wall_p99
    )

    # -- legacy (3-pull) vs packed (1-pull) dependency-edge A/B ----------
    # The raw (pre-pack) program still compiles; pulling its three
    # arrays separately is exactly the pre-change read path. Parity must
    # be byte-identical — packing is a wire format, not a recompute.
    tc0 = readpack.transfer_count()
    packed_res = agg.dependency_edges(lo_min, hi_min)
    packed_transfers = readpack.transfer_count() - tc0
    with agg.lock:
        raw_out = agg._raw["edges"](
            agg._link_context_cached(), agg.state,
            jnp.uint32(lo_min), jnp.uint32(hi_min),
        )
    legacy_res = tuple(np.asarray(a) for a in raw_out)  # one pull EACH
    edges_ab = {
        "legacy_transfers": len(legacy_res),
        "packed_transfers": int(packed_transfers),
        "parity_byte_identical": bool(all(
            p.dtype == l.dtype and np.array_equal(p, l)
            for p, l in zip(packed_res, legacy_res)
        )),
    }

    # -- XPlane capture: actual device time per read ---------------------
    # Host-clock noise makes wall-minus-floor an unreliable
    # program-time estimator, so the SLO verdict conditions on CAPTURED
    # device time per program.
    # Ordering (r07 bugfix): the capture runs BEFORE the concurrent
    # legs. r07 ran them first, so by capture time the concurrent leg
    # had rewarmed every cache the capture-side reads were supposed to
    # force — and when the capture itself failed (no protoc on the
    # host) fresh_read_captured_ms went null with nothing backing
    # it. The wall-minus-floor fallback below closes the second hole.
    device_ms = {}
    program_ms = {}
    try:
        from benchmarks.xplane_tools import device_op_totals, latest_xspace

        trace_dir = tempfile.mkdtemp(prefix="query_slo_trace_")
        with jax.profiler.trace(trace_dir):
            for fn in reads.values():
                fn()
            # dispatch the BOUNDED amortized programs explicitly so the
            # bound check below can require their presence (the fused
            # step variants embed flush/rollup under a different program
            # name, so nothing else guarantees the standalone programs
            # appear in this capture)
            agg.rollup_now()
            agg.flush_now()
            agg.block_until_ready()
        space = latest_xspace(trace_dir)
        totals = device_op_totals(space)
        for op, (us, n) in sorted(
            totals.items(), key=lambda kv: -kv[1][0]
        )[:24]:
            device_ms[op] = {"total_ms": round(us / 1e3, 3), "count": n}
        for op, (us, n) in totals.items():
            if op.startswith("jit_spmd_"):
                name = op.split("(")[0][len("jit_"):]
                per = us / 1e3 / max(n, 1)
                program_ms[name] = round(
                    max(program_ms.get(name, 0.0), per), 3
                )
        shutil.rmtree(trace_dir, ignore_errors=True)
    except Exception as e:  # pragma: no cover - capture is best-effort
        device_ms = {"error": str(e)}

    # per-QUERY programs gate the SLO. The r4 change: the FRESH
    # dependency read (spmd_edges_fresh — link context from the
    # maintained sort order + windowed edges, one dispatch) GATES like
    # any other query program; spmd_link_ctx is no longer excluded as
    # amortized (VERDICT r3 order 1). Still amortized: spmd_flush
    # (advances ingest state the stream would flush anyway),
    # spmd_rollup (runs once per rollup_segment writes), and
    # spmd_quant_digest (the superseded pend-fold read kept for
    # comparison) — but each now has an explicit BOUND so a regression
    # that shifts cost into them cannot pass unnoticed (r3 weak #6).
    AMORTIZED_BOUNDS = {"spmd_flush": 150.0, "spmd_rollup": 150.0,
                        "spmd_quant_digest": 150.0}
    # the harness dispatches every bounded program (pend-fold read,
    # flush via percentiles, rollup during the load), so ABSENCE from
    # the capture is itself a failure — a program that silently stopped
    # being captured must not vacuously pass its bound
    gated = {
        k: v for k, v in program_ms.items() if k not in AMORTIZED_BOUNDS
    }
    slo_device = bool(gated) and all(v < 50.0 for v in gated.values())
    amortized_ok = all(
        k in program_ms and program_ms[k] < bound
        for k, bound in AMORTIZED_BOUNDS.items()
    )
    slo_device = slo_device and amortized_ok

    floor_p50 = _stats(floor)["p50"]
    # wall/device per read: how much of the observed wall is transfer +
    # dispatch overhead vs actual device work (1.0 = pure device time)
    READ_PROGRAM = {
        "dependencies_ctx_cached": "spmd_edges",
        "dependencies_ctx_fresh": "spmd_edges_fresh",
        "dependencies_rolled_only": "spmd_edges_rolled",
        "percentiles_pend_fold": "spmd_quant_digest",
        "percentiles_digest": "spmd_quant_digest_nopend",
        "percentiles_windowed": "spmd_quant_whist",
        "cardinalities": "spmd_card",
    }
    wall_over_device = {
        name: round(_stats(walls[name])["p50"] / program_ms[prog], 2)
        for name, prog in READ_PROGRAM.items()
        if program_ms.get(prog)
    }
    # ISSUE 5 gate: the fresh read now computes ctx via the incremental
    # delta formulation (persistent ctx + since-rollup segment), so it
    # carries its own tighter target on top of the 50 ms SLO; ctx
    # maintenance runs fused inside the rollup dispatch and must stay
    # inside the rollup's 150 ms amortized bound (checked above).
    fresh_ms = program_ms.get("spmd_edges_fresh")
    fresh_src = "xplane"
    if fresh_ms is None:
        # r07 backfill: capture unavailable (protoc missing on the
        # host) left the gate vacuously false. Wall-minus-floor
        # over the timed fresh-read loop is the conservative stand-in —
        # it overstates device time (dispatch + transfer included), so
        # passing the target on it is strictly safe.
        fresh_ms = round(
            max(_stats(walls["dependencies_ctx_fresh"])["p50"] - floor_p50,
                0.0), 2,
        )
        fresh_src = "wall_minus_floor"
    ctx_report = {
        "fresh_read_target_ms": 35.0,
        "fresh_read_captured_ms": fresh_ms,
        "fresh_read_capture_source": fresh_src,
        "fresh_read_under_target": bool(
            fresh_ms is not None and fresh_ms < 35.0
        ),
        "ctx_advances": agg.ctx_stats["ctx_advances"],
        "last_advance_host_wall_ms": round(
            agg.ctx_stats["ctx_maintenance_ms"], 2
        ),
        "delta_lanes_outstanding": agg._lanes_since_rollup,
        "delta_sort_lanes": 2 * config.rollup_segment,
        "full_ring_union_lanes": 2 * config.ring_capacity,
    }

    # -- concurrent reads: lock-path baseline vs mirror (ISSUE 14) --------
    # Four legs, same mixed workload: the r07 lock-bound baseline
    # (mirror off) and the epoch-published mirror, at 8 and 32 reader
    # threads. The mirror legs run with live ingest + a tick-cadence
    # publisher, so staleness-at-serve is real. Lock legs run first at
    # each width so the mirror cannot warm anything for them.
    # small churn payload: a full-size batch takes longer to ingest than
    # a whole mirror leg runs, so write_version would never advance
    # mid-leg and every staleness sample would be a vacuous zero
    churn_payload = json_v2.encode_span_list(spans[:2048])
    concurrent = {}
    for n_threads in (8, 32):
        for use_mirror in (False, True):
            leg = _concurrent_leg(
                store, end_ts_ms, qs, n_threads, use_mirror,
                ingest_payload=churn_payload,
            )
            concurrent[
                f"{'mirror' if use_mirror else 'lock'}_{n_threads}t"
            ] = leg
    store.mirror.enabled = True

    # mirror-vs-fresh parity at the publish instant: with writers quiet,
    # an epoch cut now and the locked fresh read must produce the same
    # bytes — the publisher runs the SAME read programs at _cached_read
    # key granularity, so any divergence is a real bug, not jitter.
    agg.block_until_ready()
    store.publish_mirror(force=True)
    serves0 = store.mirror.serves
    mirror_rows = store.latency_quantiles(qs)
    mirror_card = store.trace_cardinalities()
    mirror_served = store.mirror.serves - serves0
    parity = {
        "percentiles_identical": bool(
            json.dumps(mirror_rows, sort_keys=True)
            == json.dumps(store.latency_quantiles(qs, staleness_ms=0),
                          sort_keys=True)
        ),
        "cardinalities_identical": bool(
            json.dumps(mirror_card, sort_keys=True)
            == json.dumps(store.trace_cardinalities(staleness_ms=0),
                          sort_keys=True)
        ),
        "reads_were_mirror_served": bool(mirror_served == 2),
    }

    # the ISSUE 14 acceptance gate, spelled out against the r07 numbers
    m8 = concurrent["mirror_8t"]
    r07 = {"p99_ms": 136.76, "lock_wait_share": 0.7755}
    slo_concurrent = {
        "p99_ms": m8["p99_ms"],
        "p99_under_50ms": bool(m8["p99_ms"] < 50.0),
        "lock_wait_share": m8["split_fraction"]["lock_wait"],
        "lock_wait_under_10pct": bool(
            m8["split_fraction"]["lock_wait"] < 0.10
        ),
        "vs_r07": {
            "p99_ms_r07": r07["p99_ms"],
            "p99_delta_ms": round(m8["p99_ms"] - r07["p99_ms"], 2),
            "lock_wait_share_r07": r07["lock_wait_share"],
            "lock_wait_share_delta": round(
                m8["split_fraction"]["lock_wait"]
                - r07["lock_wait_share"], 4,
            ),
        },
    }

    # -- time-disaggregated sketch tier (ISSUE 15) -----------------------
    timetier = _timetier_section(
        bool(os.environ.get("QUERY_SLO_SMALL")), qs
    )

    # -- scale-out read serving: reader PROCESSES over the shm segment ---
    # (ISSUE 19) Same mixed workload as the thread legs, but the
    # readers are separate processes attached to the mirror segment —
    # no GIL sharing, no store, no lock to reach. Publisher + ingest
    # churn keep running in THIS process so staleness-at-serve is real.
    serving = _serving_leg(
        store, qs, end_ts_ms,
        int(os.environ.get("QUERY_SLO_SERVING_PROCS", 8)),
        int(os.environ.get("QUERY_SLO_SERVING_ITERS", 20_000)),
        churn_payload,
    )
    r08_mirror_8t = 1536.6  # QUERY_SLO_r08.json concurrent.mirror_8t.qps
    slo_serving = {
        "qps": serving["qps"],
        "qps_target_10x_r08": round(10 * r08_mirror_8t, 1),
        "qps_over_10x_r08": bool(serving["qps"] >= 10 * r08_mirror_8t),
        "p99_ms": serving["query_wall_ms"]["p99"],
        "p99_under_50ms": bool(serving["query_wall_ms"]["p99"] < 50.0),
        "reader_lock_acquisitions": serving["reader_lock_acquisitions"],
        "vs_r08": {
            "mirror_8t_qps_r08": r08_mirror_8t,
            "speedup": round(serving["qps"] / r08_mirror_8t, 1),
        },
    }

    out = {
        "artifact": "query_slo",
        "spans": sent,
        # warm-up spans predate the timed window: exclude them
        "ingest_spans_per_sec": round((sent - warm_spans) / ingest_wall),
        "dispatch_floor_ms": _stats(floor),
        "reads_wall_ms": {k: _stats(v) for k, v in walls.items()},
        "reads_wall_minus_floor_p50_ms": {
            k: round(max(_stats(v)["p50"] - floor_p50, 0.0), 2)
            for k, v in walls.items()
        },
        "reads_transfers_per_query": transfers,
        "reads_wall_over_device": wall_over_device,
        "flight_recorder": recorder_report,
        "concurrent": concurrent,
        "mirror_parity": parity,
        "slo_concurrent_mirror": slo_concurrent,
        "timetier": timetier,
        "slo_windowed": timetier["slo"],
        "serving": serving,
        "slo_serving": slo_serving,
        "dependency_edges_transfer_ab": edges_ab,
        "program_device_ms_per_dispatch": program_ms,
        "incremental_ctx": ctx_report,
        "slo_50ms_program_time": slo_device,
        "device_ops_ms": device_ms,
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
