"""ZT-lint checkers. Importing this package registers every rule.

Rule catalog (grounded in real past regressions — see ARCHITECTURE.md
"Static analysis" for the full story per rule):

- ZT00 suppression hygiene (meta): a ``zt-lint: disable`` pragma with no
  justification text.
- ZT01 host-transfer chokepoint: device→host coercion outside
  ``readpack``.
- ZT02 multi-pull shapes: ≥2 host pulls in one function, or
  multi-``np.asarray`` return tuples.
- ZT03 jit-recompile hazards: ``jax.jit`` constructed per call/iteration;
  varying Python scalars passed positionally to jitted callables.
- ZT04 lock discipline: attributes written under a lock in one method
  but lock-free in another.
- ZT05 donation misuse: a donated argument read after the donating call.
- ZT06 blocking sync: ``block_until_ready`` on serving paths.
- ZT07 fresh-read searches: ``fori_loop``/``lax.scan`` (a binary
  search's shape) reachable from fresh-read entrypoints — the read holds
  every sender while it runs, and resolves by one sort instead.
- ZT08 obs stage discipline: ``obs.record`` reachable from
  device-traced code (host instrumentation runs once at trace time),
  or a stage argument outside the closed catalogue in
  ``obs/stages.py``.
- ZT09 dispatch-critical loops: Python ``for``/``while``/comprehensions
  inside functions marked ``# zt-dispatch-critical`` — the ingest
  fan-out's single dispatch core must do O(chunks)+O(new-vocab) work,
  never O(spans); justified non-per-span loops carry ZT09 pragmas.
- ZT10 mirror-served lock acquires: aggregator-lock acquisition (bare
  ``.lock`` holds, or calls into known lock-taking helpers) reachable
  from functions marked ``# zt-mirror-served`` within the module — the
  epoch-published read mirror's serve path must never re-queue readers
  on the lock (cross-module chains are ZT13's).
- ZT11 seqlock discipline: writes to registered shm seqlock regions
  (ring slot headers, mirror epoch, critpath ledger slots, recorder
  histograms) must sit inside an odd/even generation-stamp bracket on
  the SAME generation word; gen-aware readers must re-read the
  generation after copying.
- ZT12 durability commit: in ``wal``/``snapshot``/``timetier``/
  ``archive``, restore-readable files flow through the
  tmp+fsync+rename+dir-fsync chokepoints — a bare write-mode ``open``
  or an ``os.replace`` without fsync on its path is a finding.
- ZT13 reader isolation: aggregator-lock / ``InstrumentedRLock``
  acquires statically unreachable — at full interprocedural, cross-
  module depth over the whole-program call graph — from
  ``# zt-mirror-served`` and ``# zt-reader-process`` entrypoints (the
  static gate for the ROADMAP's multi-process read front end).
- ZT14 tenant admission: every ``# zt-ingest-boundary`` wire
  entrypoint must reach a ``# zt-tenant-admission`` chokepoint in the
  whole-program call graph (callable-reference hops like
  ``asyncio.to_thread(f, ...)`` included) — a transport that hands
  bytes to the fan-out tier without traversing admission silently
  breaks tenant isolation (ISSUE 18).

ZT07/ZT08/ZT13/ZT14 walk the shared whole-program call graph built once
per run (``lint/callgraph.py``: qualified-name resolution, bounded-depth
reachability, cross-module taint summaries); ZT01/ZT02/ZT04/ZT09/ZT10
consult it per module for summaries, caller proofs, and callee hops.
"""

from zipkin_tpu.lint.checkers import (  # noqa: F401 - import registers
    blocking,
    dispatchloop,
    donation,
    durability,
    freshread,
    locks,
    mirrorread,
    obsstage,
    pragmas,
    readeriso,
    recompile,
    seqlock,
    tenantadm,
    transfers,
)
