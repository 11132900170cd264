"""Fresh-dependency-read cost attribution + A/B (VERDICT r4 order 3).

r4 landed ``spmd_edges_fresh`` at 46.2 ms captured device time against
the 50 ms SLO — an 8% margin. This harness splits the fused program
into its parts at full AggConfig shapes on the chip and A/Bs the r5
candidates:

- ``edge_topk``: the [S^2] ``lax.top_k`` that compacts the merged call
  matrix to E=4096 edges. Candidate: prefix-sum nonzero compaction
  (cumsum + searchsorted + gather) — "top-E by calls" only exists to
  ship EVERY nonzero edge when they fit, so selecting the first E
  nonzero cells is equivalent (the host's all-slots-live dense fallback
  covers overflow identically).
- ``fresh_fused``: ctx + emit + compaction, the whole fresh-read shape.

All timings are XPlane DEVICE captures: only the profiler's device op
totals are trusted for a program's time (the r3/r4 convention).

Run on the chip: ``python -m benchmarks.profile_fresh_read``.
"""

from __future__ import annotations

import json
import shutil
import tempfile

import numpy as np


def capture_program_ms(fn, args, reps=3):
    """Median per-dispatch device ms of ``fn(*args)`` via XPlane."""
    import jax

    from benchmarks.xplane_tools import device_op_totals, latest_xspace

    out = fn(*args)  # compile outside the capture
    jax.block_until_ready(out)
    trace_dir = tempfile.mkdtemp(prefix="fresh_prof_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
            # force a real device->host pull so the capture window
            # covers the work
            np.asarray(jax.tree_util.tree_leaves(out)[0])
        totals = device_op_totals(latest_xspace(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    per_jit = {}
    for op, (us, n) in totals.items():
        if op.startswith("jit_"):
            name = op.split("(")[0][len("jit_"):]
            per_jit[name] = per_jit.get(name, 0.0) + us / 1e3
    return {k: round(v / reps, 2) for k, v in per_jit.items()}


def main() -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.profile_link_ctx import synthetic_ring
    from zipkin_tpu import readpack
    from zipkin_tpu.ops import linker
    from zipkin_tpu.tpu.state import AggConfig

    cfg = AggConfig()
    r = cfg.ring_capacity
    s = cfg.max_services
    num_edges = min(4096, s * s)
    cols = synthetic_ring(r)
    x = linker.LinkInput(**{k: jnp.asarray(v) for k, v in cols.items()})
    x = jax.device_put(x)

    def topk_current(calls, errors):
        cf = calls.reshape(-1)
        ef = errors.reshape(-1)
        top, idx = jax.lax.top_k(cf, num_edges)
        return idx, top, ef[idx]

    def topk_compact(calls, errors):
        cf = calls.reshape(-1)
        ef = errors.reshape(-1)
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

    def link_context(x):
        return linker.link_context(x)

    def emit_links(ctx, emit):
        return linker.emit_links(ctx, emit, s)

    def fresh_fused_current(x):
        c = linker.link_context(x)
        calls, errors = linker.emit_links(c, x.valid, s)
        return c, topk_current(calls, errors)

    def fresh_fused_compact(x):
        c = linker.link_context(x)
        calls, errors = linker.emit_links(c, x.valid, s)
        return c, topk_compact(calls, errors)

    def fresh_fused_packed(x):
        # the PRODUCTION wire shape: ctx stays on device and the edge
        # triple leaves as ONE packed ZPK1 buffer (readpack.pack fused
        # as the program's last stage)
        c = linker.link_context(x)
        calls, errors = linker.emit_links(c, x.valid, s)
        return c, readpack.pack(topk_compact(calls, errors))

    ctx = jax.jit(link_context)(x)
    ctx = jax.device_put(ctx)
    calls, errors = jax.jit(emit_links)(ctx, x.valid)
    calls, errors = jax.device_put((calls, errors))

    results = {}
    results.update(capture_program_ms(jax.jit(link_context), (x,)))
    results.update(capture_program_ms(jax.jit(emit_links), (ctx, x.valid)))
    results.update(capture_program_ms(jax.jit(topk_current), (calls, errors)))
    results.update(capture_program_ms(jax.jit(topk_compact), (calls, errors)))
    results.update(capture_program_ms(jax.jit(fresh_fused_current), (x,)))
    results.update(capture_program_ms(jax.jit(fresh_fused_compact), (x,)))
    results.update(capture_program_ms(jax.jit(fresh_fused_packed), (x,)))

    # -- transfers-per-query + wall/device: legacy 3-pull vs packed 1 ----
    import time

    legacy_fn = jax.jit(fresh_fused_compact)
    packed_fn = jax.jit(fresh_fused_packed)
    jax.block_until_ready(legacy_fn(x))
    jax.block_until_ready(packed_fn(x))

    def timed_read(fn, pull, reps=5):
        t0 = readpack.transfer_count()
        xs = []
        for _ in range(reps):
            w0 = time.perf_counter()
            pull(fn(x)[1])
            xs.append((time.perf_counter() - w0) * 1e3)
        per = (readpack.transfer_count() - t0) / reps
        return round(sorted(xs)[len(xs) // 2], 2), round(per, 2)

    legacy_wall, legacy_tr = timed_read(
        legacy_fn, lambda triple: [readpack.device_get(a) for a in triple]
    )
    packed_wall, packed_tr = timed_read(
        packed_fn, lambda buf: readpack.unpack(readpack.device_get(buf))
    )

    # equivalence of the two compactions on this corpus
    i1, c1, e1 = jax.jit(topk_current)(calls, errors)
    i2, c2, e2 = jax.jit(topk_compact)(calls, errors)
    cur = {
        (int(i), int(c), int(e))
        for i, c, e in zip(np.asarray(i1), np.asarray(c1), np.asarray(e1))
        if c > 0
    }
    new = {
        (int(i), int(c), int(e))
        for i, c, e in zip(np.asarray(i2), np.asarray(c2), np.asarray(e2))
        if c > 0
    }

    def ratio(wall, name):
        dev = results.get(name)
        return round(wall / dev, 2) if dev else None

    print(json.dumps({
        "artifact": "profile_fresh_read",
        "ring_capacity": r,
        "max_services": s,
        "device_ms_per_dispatch": results,
        "read_wall_ms": {"legacy_3pull": legacy_wall, "packed": packed_wall},
        "transfers_per_query": {"legacy_3pull": legacy_tr, "packed": packed_tr},
        "wall_over_device": {
            "legacy_3pull": ratio(legacy_wall, "fresh_fused_compact"),
            "packed": ratio(packed_wall, "fresh_fused_packed"),
        },
        "edge_sets_equal": cur == new,
        "n_edges": len(cur),
    }), flush=True)


if __name__ == "__main__":
    main()
